"""Metrics accounting, aggregation, logging, tracing smoke tests."""

import itertools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.utils import (
    MetricAggregator, MetricLogger, StepTimer, annotate, capture_trace,
    param_count, read_jsonl, transformer_flops_per_token)

TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=32, dtype="float32",
    param_dtype="float32", remat="none")


def test_param_count_matches_shapes():
    params = transformer.init_params(TINY, jax.random.key(0))
    want = sum(int(np.prod(s)) for s in jax.tree.leaves(
        transformer.param_shapes(TINY), is_leaf=lambda x: isinstance(x, tuple)))
    assert param_count(params) == want


def test_flops_per_token_internal_estimate_matches_param_count():
    """The cfg-derived matmul param estimate must equal the real non-norm,
    non-embedding-gather parameter count (tied embeddings: lm_head == D*V)."""
    params = transformer.init_params(TINY, jax.random.key(0))
    n_matmul = param_count(params["layers"]) - 2 * TINY.num_layers * TINY.embed_dim
    n_matmul += TINY.embed_dim * TINY.vocab_size  # tied lm_head matmul
    got = transformer_flops_per_token(TINY, seq_len=16)
    want = transformer_flops_per_token(TINY, seq_len=16, n_params=n_matmul)
    assert got == want


def test_flops_training_is_3x_inference():
    train = transformer_flops_per_token(TINY, 16, n_params=1000)
    infer = transformer_flops_per_token(TINY, 16, n_params=1000,
                                        training=False)
    assert train == pytest.approx(3 * infer)


def test_step_timer_tokens_per_sec_and_mfu(monkeypatch):
    # the timer's clock steps 10 ms a read: a loaded host stretches a
    # sleep past any tolerance
    reads = itertools.count()
    monkeypatch.setattr(time, "perf_counter", lambda: next(reads) * 0.01)
    t = StepTimer(flops_per_token=1e6, n_devices=1, peak_flops=1e12,
                  window=10)
    for _ in range(3):
        out = t.tick(tokens=1000)
    assert out["tokens_per_sec"] == pytest.approx(1000 / 0.01, rel=0.5)
    assert out["mfu"] == pytest.approx(
        out["tokens_per_sec"] * 1e6 / 1e12, rel=1e-6)
    assert out["step_time_s"] == pytest.approx(0.01, rel=0.5)


def test_no_invented_peak_on_cpu():
    """A device the peaks table does not know is an error where a
    utilization is asked for, and on the CPU the step timer leaves MFU
    out instead of dividing by a made-up peak."""
    from cloud_server_tpu.utils.metrics import device_peaks
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks()
    out = StepTimer(flops_per_token=1e6).tick(tokens=1000)
    assert "mfu" not in out and out["tokens_per_sec"] > 0


def test_metric_aggregator_means_and_resets():
    agg = MetricAggregator()
    agg.update({"loss": jnp.asarray(2.0), "acc": 0.5})
    agg.update({"loss": jnp.asarray(4.0), "acc": 0.7})
    out = agg.flush()
    assert out["loss"] == pytest.approx(3.0)
    assert out["acc"] == pytest.approx(0.6)
    agg.update({"loss": 10.0})
    assert agg.flush()["loss"] == pytest.approx(10.0)  # window reset


def test_metric_logger_writes_jsonl_and_stdout(tmp_path, capsys):
    with MetricLogger(tmp_path, name="t") as log:
        log.log(1, {"loss": jnp.asarray(1.5)})
        log.log(2, {"loss": 1.25})
    records = read_jsonl(tmp_path / "t.jsonl")
    assert [r["step"] for r in records] == [1, 2]
    assert records[0]["loss"] == 1.5
    out = capsys.readouterr().out
    assert "[step 1] loss=1.5" in out


def test_annotate_and_trace_smoke(tmp_path):
    with annotate("unit-test-region", step=0):  # no capture: inactive
        jnp.ones((8, 8)).sum().block_until_ready()
    with capture_trace(tmp_path / "trace"):
        with annotate("unit-test-region", step=1):
            jnp.ones((8, 8)).sum().block_until_ready()
    # a trace landed (tests/test_trace_events.py reads one)
    assert list((tmp_path / "trace").rglob("*.xplane.pb"))
    # what utils/tracing.py keeps is what has a caller in the program
    from cloud_server_tpu import utils
    assert not hasattr(utils, "StepProfiler")
