"""The bench's one contract with the driver: a parsed headline JSON
line (incl. approx_mfu) must exist on stdout EVEN IF a later section
times out or dies — r4 shipped rc=124 with zero parsed output because
the only print sat after every section. These tests monkeypatch the
heavy sections and check the printing protocol itself."""

import json

import pytest

import bench


def _fake_train():
    return {"tokens_per_sec": 1000.0, "step_time_ms": 100.0,
            "approx_mfu": 0.5}


def _lines(capsys):
    out = []
    for ln in capsys.readouterr().out.splitlines():
        try:
            out.append(json.loads(ln))
        except ValueError:
            continue
    return out


@pytest.fixture
def patched(monkeypatch):
    monkeypatch.setattr(bench, "train_bench", _fake_train)
    monkeypatch.setattr(bench, "longseq_attention_bench",
                        lambda: {"s2048_fwdbwd_flash_ms": 1.0})
    monkeypatch.setattr(bench, "serving_bench",
                        lambda: {"decode_tok_s_pallas_bf16": 2.0})
    monkeypatch.setattr(bench, "_longcontext_attention_bench",
                        lambda: {"attn1k_us_pallas": 3.0})
    monkeypatch.setattr(bench, "_trained_spec_bench",
                        lambda: {"trained_tok_s_plain": 4.0})


def test_headline_printed_before_sections(patched, monkeypatch, capsys):
    """A section that hangs forever (here: raises after we've captured
    stdout) must not prevent the headline: the FIRST JSON line appears
    before any section runs and already carries approx_mfu."""
    def boom():
        raise RuntimeError("section died")
    monkeypatch.setattr(bench, "serving_bench", boom)
    bench.main()
    lines = _lines(capsys)
    assert len(lines) >= 2  # headline + re-prints
    first = lines[0]
    assert first["metric"] == "train_tokens_per_sec_330M_bf16"
    assert first["value"] == 1000.0
    assert first["extra"]["approx_mfu"] == 0.5
    # the failed section is recorded, later sections still ran
    last = lines[-1]
    assert "serving_error" in last["extra"]
    assert last["extra"]["attn1k_us_pallas"] == 3.0


def test_every_section_reprints_enriched_line(patched, capsys):
    bench.main()
    lines = _lines(capsys)
    # train + longseq + serving + longcontext + trained_spec
    assert len(lines) == 5
    last = lines[-1]
    for key in ("approx_mfu", "s2048_fwdbwd_flash_ms",
                "decode_tok_s_pallas_bf16", "attn1k_us_pallas",
                "trained_tok_s_plain"):
        assert key in last["extra"], key
    # every line is a superset-consistent headline
    for ln in lines:
        assert ln["metric"] == "train_tokens_per_sec_330M_bf16"
        assert ln["unit"] == "tokens/s"


def test_budget_gates_trained_spec(patched, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_TIME_BUDGET_S", "0")  # always over budget
    bench.main()
    lines = _lines(capsys)
    last = lines[-1]
    assert "trained_tok_s_plain" not in last["extra"]
    assert "trained_spec_skipped_at_s" in last["extra"]


def test_skip_env_vars(patched, monkeypatch, capsys):
    monkeypatch.setenv("BENCH_SKIP_LONGSEQ", "1")
    monkeypatch.setenv("BENCH_SKIP_SERVING", "1")
    bench.main()
    lines = _lines(capsys)
    last = lines[-1]
    assert "s2048_fwdbwd_flash_ms" not in last["extra"]
    assert "decode_tok_s_pallas_bf16" not in last["extra"]
    assert "trained_tok_s_plain" not in last["extra"]
    assert last["extra"]["approx_mfu"] == 0.5
