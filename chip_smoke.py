#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the published widths of Llama-3.2-1B (configs/llama3_2_1b.json, seeded
random weights):

  1. serve:   `python -m cloud_server_tpu.generate --serve-http ...
              --decode-impl pallas` at full depth, CLI defaults otherwise
              (mixed scheduler, overlap on). A handful of /generate
              requests over HTTP: short and longer than one prefill chunk,
              alone and in flight together, all read as streams; one
              profiler capture (POST /debug/trace) while a request decodes.
  2. train:   `python -m cloud_server_tpu.train --synthetic ...` with flash
              attention, fused CE, remat "dots", f32 master weights, depth
              cut to what one chip holds (the config's `reduced`).
  3. kernels: the on_tpu tests (CST_TPU_TESTS=1) — every Mosaic kernel
              against its XLA reference at the same head geometry.

This process never imports JAX: a chip belongs to one process, so it
starts one child at a time and talks to the server with the standard
library. Each child prints its device line first; anything but a TPU
ends the run before any work. Each child also runs with JAX_DUMP_IR_TO
set, and the lowered programs it leaves behind must hold the Mosaic
custom calls of the kernels that path claims to use: a kernel that was
interpreted, or replaced by its XLA reference, fails the run.

Exit code 0 and a last stdout line
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
only if every phase passed; otherwise non-zero and no such line.
"""

from __future__ import annotations

import glob
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(ROOT, "configs", "llama3_2_1b.json")
REQUIRED_PLATFORM = "tpu"
DEADLINE_S = 1150.0           # the whole run, compilation included
MAX_LEN = 4096                # serving context (the config declares 131,072)
# jit names of the paged server's device programs (inference/paged_server.py)
SERVE_PROGRAMS = ["_mixed_step", "_decode_rounds", "_spec_rounds"]
KERNEL_TESTS = ["tests/test_paged_attention.py",
                "tests/test_flash_attention.py", "tests/test_fused_ce.py",
                "tests/test_moe.py", "tests/test_grouped_matmul.py"]

_T0 = time.monotonic()


class SmokeError(Exception):
    pass


def left() -> float:
    """Seconds until the run's own deadline (never below 1)."""
    return max(1.0, DEADLINE_S - (time.monotonic() - _T0))


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return "(no log)"


class Child:
    """One child process in its own process group, output to a log file;
    leaving the `with` block stops the whole group."""

    def __init__(self, name: str, argv: list[str], work: str,
                 env_extra: dict[str, str]):
        self.name = name
        self.log = os.path.join(work, f"{name}.log")
        env = dict(os.environ, PYTHONUNBUFFERED="1", **env_extra)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        self._out = open(self.log, "w")
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=self._out,
            stderr=subprocess.STDOUT, start_new_session=True)

    def __enter__(self) -> "Child":
        return self

    def __exit__(self, *exc) -> None:
        if self.proc.poll() is None:
            self.signal(signal.SIGKILL)
            self.proc.wait()
        self._out.close()

    def signal(self, sig: int) -> None:
        try:
            os.killpg(self.proc.pid, sig)
        except ProcessLookupError:
            pass

    def fail(self, why: str):
        raise SmokeError(f"{self.name}: {why}\n--- {self.name} log tail "
                         f"---\n{tail(self.log)}")

    def wait_for(self, pattern: str, what: str, timeout: float):
        """First regex match in the child's log; fails if the child exits
        or the time runs out first."""
        rx = re.compile(pattern)
        end = time.monotonic() + min(timeout, left())
        while True:
            with open(self.log, errors="replace") as f:
                m = rx.search(f.read())
            if m:
                return m
            if self.proc.poll() is not None:
                self.fail(f"exited with code {self.proc.returncode} "
                          f"before {what}")
            if time.monotonic() > end:
                self.fail(f"no {what} within {timeout:.0f}s")
            time.sleep(0.5)

    def device(self, tag: str) -> dict:
        """The child's start-up device line; anything but the required
        platform ends the run before the child is given any work."""
        m = self.wait_for(rf"\[{tag}\] device: (\{{.*\}})", "device line",
                          180)
        dev = json.loads(m.group(1))
        say(f"{self.name}: device {json.dumps(dev)}")
        if dev["platform"] != REQUIRED_PLATFORM:
            raise SmokeError(
                f"{self.name} runs on platform {dev['platform']!r} "
                f"({dev['kind']}), not {REQUIRED_PLATFORM!r}: no "
                "accelerator, nothing was run")
        return dev

    def wait_exit(self, timeout: float) -> int:
        try:
            return self.proc.wait(timeout=min(timeout, left()))
        except subprocess.TimeoutExpired:
            self.fail(f"still running after {timeout:.0f}s")


def compiled_kernels(irdir: str, module: str) -> dict[str, set[str]]:
    """{dumped module file: names of the Mosaic kernels it calls} for
    every lowered program whose jit name contains `module`."""
    out = {}
    for path in sorted(glob.glob(os.path.join(irdir, "*.mlir"))):
        if module not in os.path.basename(path):
            continue
        with open(path, errors="replace") as f:
            text = f.read()
        names = set(re.findall(r'kernel_name = "([^"]+)"', text))
        if "tpu_custom_call" not in text:
            names = set()
        out[os.path.basename(path)] = names
    return out


# ---------------------------------------------------------------------------
# phase 1: serving
# ---------------------------------------------------------------------------

def post(url: str, body: dict, timeout: float):
    req = urllib.request.Request(url, data=json.dumps(body).encode())
    return urllib.request.urlopen(req, timeout=min(timeout, left()))


def generate(base: str, prompt: str, max_new: int, vocab: int,
             on_token=None) -> dict:
    """One /generate request read as a stream; every property the smoke
    holds a response to is checked here."""
    t0 = time.monotonic()
    lines, first = [], None
    with post(f"{base}/generate",
              {"prompt": prompt, "max_new_tokens": max_new,
               "temperature": 0.0, "ignore_eos": True}, 900) as resp:
        if resp.status != 200:
            raise SmokeError(f"/generate answered HTTP {resp.status}")
        for raw in resp:
            line = json.loads(raw)
            lines.append(line)
            if first is None:
                first = time.monotonic() - t0
            if on_token is not None and "token" in line:
                on_token(len(lines))
    if not lines:
        raise SmokeError("/generate answered with an empty stream")
    last = lines[-1]
    if "error" in last:  # a failed dispatch finishes requests this way
        raise SmokeError(f"request failed: {last}")
    reason = last.get("finish_reason")
    if not last.get("done") or reason not in ("length", "stop"):
        raise SmokeError(f"bad final line: {last}")
    toks = last["tokens"]
    streamed = [ln["token"] for ln in lines[:-1]]
    if len(toks) != max_new or streamed != toks:
        raise SmokeError(
            f"asked for {max_new} tokens, final line holds {len(toks)}, "
            f"stream held {len(streamed)} (finish_reason {reason!r})")
    if not all(isinstance(t, int) and 0 <= t < vocab for t in toks):
        raise SmokeError(f"token id outside [0, {vocab}): {toks}")
    lps = last["logprobs"]
    if len(lps) != max_new or not all(
            isinstance(x, float) and math.isfinite(x) and x <= 1e-3
            for x in lps):
        raise SmokeError(f"logprobs not finite and <= 0: {lps}")
    return {"prompt_chars": len(prompt), "tokens": len(toks),
            "finish_reason": reason, "first_token_s": round(first, 2),
            "total_s": round(time.monotonic() - t0, 2)}


def check_trace(tracedir: str) -> str:
    """The profiler capture must hold an .xplane.pb with a TPU device
    plane (plane names are plain strings inside the protobuf)."""
    end = time.monotonic() + min(120, left())
    while True:
        found = glob.glob(os.path.join(tracedir, "**", "*.xplane.pb"),
                          recursive=True)
        if found and os.path.getsize(found[0]) > 0:
            break
        if time.monotonic() > end:
            raise SmokeError("POST /debug/trace left no .xplane.pb under "
                             f"{tracedir}")
        time.sleep(1.0)
    time.sleep(1.0)  # let the writer finish
    with open(found[0], "rb") as f:
        data = f.read()
    if b"/device:TPU:" not in data:
        raise SmokeError(f"{found[0]} ({len(data)} bytes) holds no "
                         "/device:TPU plane")
    return f"{os.path.basename(found[0])} {len(data)} bytes, TPU plane"


def phase_serve(work: str, vocab: int) -> dict:
    t_phase = time.monotonic()
    irdir = os.path.join(work, "ir_serve")
    argv = [sys.executable, "-m", "cloud_server_tpu.generate",
            "--config", CONFIG, "--serve-http", "0",
            "--decode-impl", "pallas", "--max-len", str(MAX_LEN)]
    say("serve: " + " ".join(argv[1:]))
    with Child("serve", argv, work, {"JAX_DUMP_IR_TO": irdir}) as child:
        dev = child.device("generate")
        m = child.wait_for(r"serving on http://([^:\s]+):(\d+)",
                           "listening line", 600)
        base = f"http://{m.group(1)}:{m.group(2)}"
        ready_s = time.monotonic() - t_phase
        say(f"serve: listening on {base} after {ready_s:.1f}s")

        short = "The quick brown fox jumps over the lazy dog."
        long_ = " ".join(f"line {i}: the server prefills this prompt in "
                         "chunks of 256 tokens." for i in range(12))
        assert len(long_) > 2 * 256 and len(long_) + 64 < MAX_LEN
        results = []
        # 1. one short request alone: first prefill and first decode
        results.append(generate(base, short, 16, vocab))
        say(f"serve: request 1 {results[-1]}")
        # 2. three in flight together, short and longer than one prefill
        #    chunk. Each joins once the one before it is decoding, so
        #    every admission is a mixed step (prefill rows and decode
        #    rows in one batch) and the dispatch shapes, hence the
        #    compiled programs, repeat from run to run
        batch = [(short, 96), (long_, 64), ("Hello!", 32)]
        outs: list = [None] * len(batch)
        decoding = [threading.Event() for _ in batch]

        def run(i, prompt, n):
            if i:
                decoding[i - 1].wait(min(900, left()))
            try:
                outs[i] = generate(base, prompt, n, vocab,
                                   on_token=lambda _: decoding[i].set())
            except Exception as e:  # noqa: BLE001 — re-raised below
                outs[i] = e
            finally:
                decoding[i].set()
        threads = [threading.Thread(target=run, args=(i, p, n))
                   for i, (p, n) in enumerate(batch)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for o in outs:
            if isinstance(o, Exception):
                raise o
            results.append(o)
            say(f"serve: request {len(results)} {o}")
        # 3. one more, with the profiler armed for two iterations once
        #    it is decoding
        tracedir = os.path.join(work, "trace")
        armed = []

        def arm(n_lines):
            if n_lines == 4 and not armed:
                with post(f"{base}/debug/trace",
                          {"steps": 2, "logdir": tracedir}, 60) as r:
                    armed.append(json.loads(r.read()))
        results.append(generate(base, long_[:300], 48, vocab, on_token=arm))
        say(f"serve: request {len(results)} {results[-1]}")
        if not armed or not armed[0].get("ok"):
            raise SmokeError(f"POST /debug/trace was not accepted: {armed}")
        trace = check_trace(tracedir)
        say(f"serve: profiler {trace}")

        child.signal(signal.SIGINT)  # generate.main stops front and server
        rc = child.wait_exit(120)
        if rc != 0:
            child.fail(f"exit code {rc} after SIGINT")

    # every device program of the scheduler (paged_server's jitted
    # cores) must carry the paged-attention kernel, the mixed step among
    # them
    kernels = {}
    for core in SERVE_PROGRAMS:
        kernels.update(compiled_kernels(irdir, core))
    if not any("_mixed_step" in k for k in kernels):
        raise SmokeError("serve: no _mixed_step program was lowered")
    bare = [k for k, v in kernels.items() if not v]
    if bare:
        raise SmokeError("serve: programs lowered without a Mosaic "
                         f"paged-attention call: {bare}")
    used = set().union(*kernels.values())
    want = {"paged_attention_narrow", "paged_attention_wide"}
    if not want <= used:
        raise SmokeError(f"serve: kernels compiled {sorted(used)}, "
                         f"expected {sorted(want)}")
    out = {"device": dev, "requests": len(results),
           "tokens": sum(r["tokens"] for r in results),
           "ready_s": round(ready_s, 1),
           "first_request_s": results[0]["total_s"],
           "programs": len(kernels),
           "kernels": sorted(used), "profiler": trace,
           "wall_s": round(time.monotonic() - t_phase, 1)}
    say(f"serve: ok {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------
# phase 2: training
# ---------------------------------------------------------------------------

def phase_train(work: str, raw: dict) -> dict:
    t_phase = time.monotonic()
    cfg = json.loads(json.dumps(raw))
    depth = raw["reduced"]["train_num_layers"]
    cfg["model"]["num_layers"] = depth
    steps = cfg["train"]["total_steps"]
    batch = cfg["train"]["batch_size"]
    path = os.path.join(work, "train.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    irdir = os.path.join(work, "ir_train")
    # as many examples as one batch holds: every step sees the same
    # batch, so a working step makes the loss fall
    argv = [sys.executable, "-m", "cloud_server_tpu.train", "--config", path,
            "--synthetic", str(batch), "--steps", str(steps)]
    say(f"train: depth {depth} of {raw['model']['num_layers']}, "
        + " ".join(argv[1:]))
    with Child("train", argv, work, {"JAX_DUMP_IR_TO": irdir}) as child:
        dev = child.device("train")
        rc = child.wait_exit(900)
        if rc != 0:
            child.fail(f"exit code {rc}")
        with open(child.log, errors="replace") as f:
            log = f.read()
    losses = [float(x) for x in
              re.findall(r"^\[step \d+\] .*?\bloss=(\S+)", log, re.M)]
    say(f"train: losses {losses}")
    if len(losses) != steps:
        raise SmokeError(f"train: {len(losses)} step lines, wanted {steps}"
                         f"\n{tail(os.path.join(work, 'train.log'))}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeError(f"train: loss not finite: {losses}")
    if losses[-1] >= losses[0] or any(
            b > a + 0.05 for a, b in zip(losses, losses[1:])):
        raise SmokeError(f"train: loss did not fall on a repeated batch: "
                         f"{losses}")
    kernels = compiled_kernels(irdir, "step_fn")
    used = set().union(*kernels.values()) if kernels else set()
    want = {"flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq", "fused_ce_fwd",
            "fused_ce_dx"}
    if not want <= used:
        raise SmokeError(f"train: kernels compiled {sorted(used)} in "
                         f"{sorted(kernels)}, expected {sorted(want)}")
    out = {"device": dev, "num_layers": depth, "steps": steps,
           "losses": losses, "kernels": sorted(used),
           "wall_s": round(time.monotonic() - t_phase, 1)}
    say(f"train: ok {json.dumps(out)}")
    return out


# ---------------------------------------------------------------------------
# phase 3: kernels against their references
# ---------------------------------------------------------------------------

def phase_kernels(work: str) -> dict:
    t_phase = time.monotonic()
    argv = [sys.executable, "-m", "pytest", *KERNEL_TESTS, "-m", "on_tpu",
            "-q", "-p", "no:cacheprovider"]
    say("kernels: CST_TPU_TESTS=1 " + " ".join(argv[1:]))
    with Child("kernels", argv, work, {"CST_TPU_TESTS": "1"}) as child:
        rc = child.wait_exit(600)
        with open(child.log, errors="replace") as f:
            log = f.read()
        m = re.search(r"(\d+) passed", log)
        if rc != 0 or not m or re.search(r"\d+ (skipped|failed|error)", log):
            child.fail(f"exit code {rc}")
    out = {"passed": int(m.group(1)),
           "wall_s": round(time.monotonic() - t_phase, 1)}
    say(f"kernels: ok {json.dumps(out)}")
    return out


def main() -> int:
    if not (os.path.isfile(CONFIG)
            and os.path.isdir(os.path.join(ROOT, "cloud_server_tpu"))):
        print(f"chip_smoke: {CONFIG} or the cloud_server_tpu package is "
              "missing: run from a checkout of the repo", file=sys.stderr)
        return 2
    with open(CONFIG) as f:
        raw = json.load(f)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"compile cache {cache}: {n_cached} entries at start "
        f"({'warm' if n_cached else 'cold'})")
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        serve = phase_serve(work, raw["model"]["vocab_size"])
        train = phase_train(work, raw)
        kernels = phase_kernels(work)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001 — e.g. the server died mid-request
        for log in sorted(glob.glob(os.path.join(work, "*.log"))):
            print(f"--- {os.path.basename(log)} tail ---\n{tail(log)}",
                  file=sys.stderr)
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    if serve["device"] != train["device"]:
        print(f"chip_smoke: FAILED: children disagree on the device: "
              f"{serve['device']} vs {train['device']}", file=sys.stderr)
        return 1
    say(f"all phases ok in {time.monotonic() - _T0:.1f}s "
        f"(cache {'warm' if n_cached else 'cold'}): serve "
        f"{serve['wall_s']}s, train {train['wall_s']}s, kernels "
        f"{kernels['wall_s']}s")
    print(json.dumps({"ok": True, "device": serve["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
