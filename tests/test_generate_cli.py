"""End-to-end CLI pipeline: tokenize -> train -> generate."""

import json

from cloud_server_tpu.data.tokenizer import main as tokenize_main


def test_tokenize_train_generate_pipeline(tmp_path, capsys, devices8):
    from cloud_server_tpu.generate import main as generate_main
    from cloud_server_tpu.train import main as train_main

    (tmp_path / "corpus.txt").write_text("abcdefgh\n" * 400)
    cfg = {"model": {"vocab_size": 259, "embed_dim": 32, "num_layers": 2,
                     "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
                     "mlp_dim": 64, "max_seq_len": 64, "dtype": "float32",
                     "param_dtype": "float32", "remat": "none"},
           "train": {"total_steps": 30, "batch_size": 8, "seq_len": 16,
                     "warmup_steps": 2, "learning_rate": 0.01},
           "loop": {"log_interval": 30}}
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))

    tokenize_main([str(tmp_path / "corpus.txt"), str(tmp_path / "t.bin")])
    train_main(["--config", str(tmp_path / "cfg.json"),
                "--data", str(tmp_path / "t.bin"),
                "--checkpoint-dir", str(tmp_path / "ckpt")])
    generate_main(["--config", str(tmp_path / "cfg.json"),
                   "--checkpoint-dir", str(tmp_path / "ckpt"),
                   "--prompt", "abcd", "--max-new", "8",
                   "--temperature", "0"])
    out = capsys.readouterr().out
    # 30 steps on a 9-char repeating corpus is enough for the byte model to
    # continue the alphabet pattern
    assert "'abcd'" in out
    assert "efgh" in out.rsplit("'abcd'", 1)[1]


def test_generate_speculative_cli(tmp_path, capsys, devices8):
    """--draft-config routes batch generation through speculative decoding
    and (greedy) must produce the same text as the plain path."""
    from cloud_server_tpu.generate import main as generate_main

    model = {"vocab_size": 259, "embed_dim": 32, "num_layers": 2,
             "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
             "mlp_dim": 64, "max_seq_len": 128, "dtype": "float32",
             "param_dtype": "float32", "remat": "none"}
    draft = dict(model, embed_dim=16, num_layers=1, num_heads=2, mlp_dim=32)
    (tmp_path / "cfg.json").write_text(json.dumps({"model": model}))
    (tmp_path / "draft.json").write_text(json.dumps({"model": draft}))

    base_args = ["--config", str(tmp_path / "cfg.json"),
                 "--prompt", "abcd", "--max-new", "8", "--temperature", "0"]
    generate_main(base_args)
    plain = capsys.readouterr().out
    generate_main(base_args + ["--draft-config", str(tmp_path / "draft.json"),
                               "--num-draft", "3"])
    spec = capsys.readouterr().out
    assert "'abcd'" in spec
    assert spec.rsplit("'abcd'", 1)[1] == plain.rsplit("'abcd'", 1)[1]


def test_generate_ngram_draft_cli(tmp_path, capsys, devices8):
    """--ngram-draft (no draft model) must match the plain greedy path."""
    from cloud_server_tpu.generate import main as generate_main

    model = {"vocab_size": 259, "embed_dim": 32, "num_layers": 2,
             "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
             "mlp_dim": 64, "max_seq_len": 128, "dtype": "float32",
             "param_dtype": "float32", "remat": "none"}
    (tmp_path / "cfg.json").write_text(json.dumps({"model": model}))
    base_args = ["--config", str(tmp_path / "cfg.json"),
                 "--prompt", "abab", "--max-new", "8", "--temperature", "0"]
    generate_main(base_args)
    plain = capsys.readouterr().out
    generate_main(base_args + ["--ngram-draft", "--num-draft", "3"])
    spec = capsys.readouterr().out
    assert spec.rsplit("'abab'", 1)[1] == plain.rsplit("'abab'", 1)[1]


def test_generate_spec_drafts_cli(tmp_path, capsys, devices8):
    """--spec-drafts (in-server speculation through the paged server)
    must match the plain greedy path token-for-token."""
    from cloud_server_tpu.generate import main as generate_main

    model = {"vocab_size": 259, "embed_dim": 32, "num_layers": 2,
             "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
             "mlp_dim": 64, "max_seq_len": 128, "dtype": "float32",
             "param_dtype": "float32", "remat": "none"}
    (tmp_path / "cfg.json").write_text(json.dumps({"model": model}))
    base_args = ["--config", str(tmp_path / "cfg.json"),
                 "--prompt", "abab", "--max-new", "8", "--temperature", "0"]
    generate_main(base_args)
    plain = capsys.readouterr().out
    generate_main(base_args + ["--spec-drafts", "2"])
    spec = capsys.readouterr().out
    assert spec == plain


def test_serve_http_cli_paged(tmp_path):
    """`generate --serve-http` must bring up the paged server end-to-end
    as a real process: POST a prompt, stream tokens, clean shutdown."""
    import os
    import signal
    import subprocess
    import sys
    import time
    import urllib.request

    model = {"vocab_size": 259, "embed_dim": 32, "num_layers": 2,
             "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
             "mlp_dim": 64, "max_seq_len": 64, "dtype": "float32",
             "param_dtype": "float32", "remat": "none"}
    draft = dict(model, embed_dim=16, num_layers=1, num_heads=2,
                 num_kv_heads=2, mlp_dim=32)
    (tmp_path / "cfg.json").write_text(json.dumps({"model": model}))
    (tmp_path / "draft.json").write_text(json.dumps({"model": draft}))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "cloud_server_tpu.generate",
         "--config", str(tmp_path / "cfg.json"),
         "--serve-http", "0", "--page-size", "8", "--max-slots", "2",
         # in-server DRAFT-MODEL speculation through the real CLI
         "--draft-config", str(tmp_path / "draft.json"),
         "--num-draft", "2",
         # anomaly watchdog + tail retention knobs through the real
         # CLI (armed-but-quiet: default thresholds, tiny tail ring)
         "--anomaly-config", '{"warmup": 4}',
         "--trace-tail-capacity", "8", "--trace-capacity", "16",
         "--bundle-on-anomaly"],
        env=env, stderr=subprocess.PIPE, text=True)
    try:
        import queue
        import threading
        lines: queue.Queue = queue.Queue()

        def _pump():
            try:
                for ln in proc.stderr:
                    lines.put(ln)
            except ValueError:
                pass  # stderr closed when the server is killed
            lines.put(None)

        threading.Thread(target=_pump, daemon=True).start()
        address = None
        deadline = time.time() + 120
        # read through a queue so a silently-wedged child (no stderr
        # output at all) fails at the deadline instead of hanging the
        # suite on a blocking readline
        while time.time() < deadline:
            try:
                line = lines.get(timeout=min(5.0, deadline - time.time()))
            except queue.Empty:
                continue
            if line is None:
                break
            if "serving on http://" in line:
                address = line.split("http://", 1)[1].split(" ")[0].strip()
                break
        assert address, "server never announced its address"
        req = urllib.request.Request(
            f"http://{address}/generate",
            data=json.dumps({"prompt": "abcd",
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = [json.loads(ln) for ln in resp if ln.strip()]
        assert out[-1]["done"] is True
        assert len(out[-1]["tokens"]) == 4
        # the CLI really armed the watchdog + tail ring: /stats grows
        # the anomaly and tail_retention blocks (quiet — no windows)
        with urllib.request.urlopen(f"http://{address}/stats?n=4",
                                    timeout=120) as resp:
            stats = json.loads(resp.read())
        assert stats["anomaly"]["active"] == []
        assert stats["tail_retention"]["capacity"] == 8
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_generate_quantized(tmp_path, capsys, devices8):
    """--quantize serves int8 weights end-to-end through the CLI."""
    from cloud_server_tpu.generate import main as generate_main

    cfg = {"model": {"vocab_size": 259, "embed_dim": 32, "num_layers": 2,
                     "num_heads": 4, "num_kv_heads": 2, "head_dim": 8,
                     "mlp_dim": 64, "max_seq_len": 64, "dtype": "float32",
                     "param_dtype": "float32", "remat": "none"}}
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    generate_main(["--config", str(tmp_path / "cfg.json"),
                   "--prompt", "abcd", "--max-new", "8",
                   "--temperature", "0", "--quantize"])
    out = capsys.readouterr().out
    assert "'abcd'" in out  # produced a completion without crashing
