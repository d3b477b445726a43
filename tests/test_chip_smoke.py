"""chip_smoke.py is the check the driver runs on the chip, so its own
checks are tested here, on the CPU, without a chip and without JAX
programs: what it accepts and refuses in a response, how it reads the
lowered-IR dumps for Mosaic calls, and the start-up contract of the entry
points it parses (device line, refusal, compile-cache placement)."""

import ast
import json
import os
import re
import subprocess
import sys
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

import chip_smoke
from cloud_server_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_smoke_never_imports_jax():
    """A parent that has touched JAX holds the chip: the smoke's own
    process must import nothing outside the standard library."""
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name.split(".")[0] for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add((node.module or "").split(".")[0])
    assert mods <= set(sys.stdlib_module_names) | {"__future__"}, mods


def test_smoke_fails_outside_a_checkout(tmp_path):
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result line


def test_compiled_kernels_reads_mosaic_calls(tmp_path):
    call = ('stablehlo.custom_call @tpu_custom_call(%0) {backend_config = '
            '"", kernel_name = "paged_attention_wide"}')
    (tmp_path / "jax_ir0001_jit__mixed_step_compile.mlir").write_text(
        f"module {{ {call} {call.replace('wide', 'narrow')} }}")
    # an interpreted kernel or the XLA reference leaves no custom call
    (tmp_path / "jax_ir0002_jit__mixed_step_compile.mlir").write_text(
        "module { stablehlo.while }")
    (tmp_path / "jax_ir0003_jit_other_compile.mlir").write_text(call)
    got = chip_smoke.compiled_kernels(str(tmp_path), "_mixed_step")
    assert got == {
        "jax_ir0001_jit__mixed_step_compile.mlir":
            {"paged_attention_wide", "paged_attention_narrow"},
        "jax_ir0002_jit__mixed_step_compile.mlir": set()}


def _serve_once(lines):
    """A one-shot HTTP server that answers POST /generate with `lines`
    as ndjson; returns its base URL."""
    class Handler(BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers["Content-Length"]))
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Connection", "close")
            self.end_headers()
            for ln in lines:
                self.wfile.write((json.dumps(ln) + "\n").encode())

        def log_message(self, *args):
            pass

    srv = HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.handle_request, daemon=True).start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _stream(tokens, logprobs, **final):
    return [{"token": t, "logprob": lp} for t, lp in zip(tokens, logprobs)
            ] + [dict({"done": True, "finish_reason": "length",
                       "tokens": tokens, "logprobs": logprobs}, **final)]


def test_generate_accepts_a_complete_response():
    srv, base = _serve_once(_stream([5, 6, 7], [-1.0, -2.5, -0.1]))
    try:
        out = chip_smoke.generate(base, "hi", 3, vocab=10)
    finally:
        srv.server_close()
    assert out["tokens"] == 3 and out["finish_reason"] == "length"


@pytest.mark.parametrize("lines,why", [
    # a dispatch that failed to compile: HTTP 200, requests finished
    # with an error line while the process lives on
    ([{"error": "error: XlaRuntimeError", "retriable": True}], "failed"),
    (_stream([5, 6], [-1.0, -2.0]), "asked for 3"),
    (_stream([5, 6, 7], [-1.0, -2.0, -0.5], finish_reason="cancelled"),
     "bad final line"),
    (_stream([5, 6, 70], [-1.0, -2.0, -0.5]), "outside"),
    (_stream([5, 6, 7], [-1.0, float("nan"), -0.5]), "logprobs"),
])
def test_generate_refuses(lines, why):
    srv, base = _serve_once(lines)
    try:
        with pytest.raises(chip_smoke.SmokeError, match=why):
            chip_smoke.generate(base, "hi", 3, vocab=10)
    finally:
        srv.server_close()


def test_device_line_is_what_the_smoke_parses(capsys):
    info = platform.device_line("generate")
    m = re.search(r"\[generate\] device: (\{.*\})", capsys.readouterr().err)
    assert json.loads(m.group(1)) == info == {
        "platform": "cpu", "kind": "cpu", "count": 8}


def test_require_tpu_names_the_device_and_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit, match="refusing to run"):
        platform.require_tpu("bench")
    assert "[bench] device: " in capsys.readouterr().err


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it and no code sets a
    directory. Unset: one fixed path inside the checkout."""
    updates = []
    monkeypatch.setattr(platform.jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
    assert platform.enable_compile_cache() == "/placed/elsewhere"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(ROOT, ".jax_cache")
    assert platform.enable_compile_cache() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed)]
