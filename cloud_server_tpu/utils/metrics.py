"""Training metrics: FLOP accounting, step timing, windowed aggregation.

MFU follows the PaLM-style accounting: matmul FLOPs/token = 6·N (2·N
forward, 4·N backward) plus causal attention score/value FLOPs; the
denominator is the device's published peak bf16 FLOP/s (`DEVICE_PEAKS`,
keyed by device_kind; overridable). Numbers are comparable across
frameworks because nothing here depends on how the step is implemented.
"""

from __future__ import annotations

import collections
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import ModelConfig


class DevicePeaks(NamedTuple):
    bf16_flops: float       # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float  # HBM bandwidth per chip


# Published per-chip peaks, keyed by `jax.devices()[0].device_kind` as
# JAX reports it (a v5e reports "TPU v5 lite"; both spellings of a part
# are listed). Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e"). The one table for every utilization and
# roofline figure in the repo (training MFU here, bench.py's sanity
# floor). A device that is not here is an error, not a default.
_V5E = DevicePeaks(197e12, 0.819e12)
_V5P = DevicePeaks(459e12, 2.765e12)
_V6E = DevicePeaks(918e12, 1.640e12)
DEVICE_PEAKS: dict[str, DevicePeaks] = {
    "TPU v4": DevicePeaks(275e12, 1.228e12),
    "TPU v5 lite": _V5E, "TPU v5e": _V5E,
    "TPU v5": _V5P, "TPU v5p": _V5P,
    "TPU v6 lite": _V6E, "TPU v6e": _V6E,
}


def device_peaks() -> DevicePeaks:
    """Peaks of the device this process runs on; ValueError for a
    device the table does not know (the CPU included — a utilization
    against an invented peak is not a measurement)."""
    kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {kind!r}; add it to "
            "utils.metrics.DEVICE_PEAKS with its source") from None


def param_count(params: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def transformer_flops_per_token(cfg: ModelConfig, seq_len: int,
                                n_params: int | None = None,
                                training: bool = True) -> float:
    """Matmul FLOPs per token for one step (fwd+bwd when training).

    6·N_matmul covers every weight matmul (embedding lookup is a gather,
    so the tied/untied lm_head is counted explicitly); attention adds
    2·2·S·H·Dh per token forward, halved for causality, tripled for bwd.
    """
    if n_params is None:
        D, L = cfg.embed_dim, cfg.num_layers
        per_layer = (D * cfg.num_heads * cfg.head_dim * 2  # wq, wo
                     + D * cfg.num_kv_heads * cfg.head_dim * 2  # wk, wv
                     + 3 * D * cfg.mlp_dim)  # gate, up, down
        n_params = L * per_layer + D * cfg.vocab_size  # + lm_head/tied
    mult = 3.0 if training else 1.0
    weight = 2.0 * mult * n_params
    attn = (2.0 * mult * 2 * seq_len * cfg.num_heads * cfg.head_dim
            * cfg.num_layers * 0.5)  # 0.5: causal
    return weight + attn


class StepTimer:
    """Wall-clock per-step timing -> tokens/sec and MFU.

    Call `tick(tokens_processed)` once per step *after* blocking on the
    step's output (jit steps return before the device finishes otherwise).
    Keeps a sliding window so throughput reflects steady state, not the
    compile step.
    """

    def __init__(self, *, flops_per_token: float | None = None,
                 n_devices: int | None = None,
                 peak_flops: float | None = None, window: int = 20):
        self.flops_per_token = flops_per_token
        self.n_devices = n_devices or jax.device_count()
        if (peak_flops is None and flops_per_token
                and jax.devices()[0].platform != "cpu"):
            peak_flops = device_peaks().bf16_flops
        # None on the CPU: no MFU is reported there, not an invented one
        self.peak_flops = peak_flops
        self._times: collections.deque = collections.deque(maxlen=window + 1)
        self._tokens: collections.deque = collections.deque(maxlen=window)
        self._times.append(time.perf_counter())

    def tick(self, tokens: int) -> dict[str, float]:
        self._times.append(time.perf_counter())
        self._tokens.append(tokens)
        dt = self._times[-1] - self._times[0]
        toks = sum(self._tokens)
        out = {"step_time_s": self._times[-1] - self._times[-2],
               "tokens_per_sec": toks / dt if dt > 0 else 0.0}
        if self.flops_per_token and self.peak_flops:
            out["mfu"] = (out["tokens_per_sec"] * self.flops_per_token
                          / (self.peak_flops * self.n_devices))
        return out


class MetricAggregator:
    """Mean-aggregates scalar metrics between log flushes (device scalars
    are only pulled to host at flush, keeping steps async)."""

    def __init__(self):
        self._sums: dict[str, float] = {}
        self._counts: dict[str, int] = {}
        self._pending: list[dict] = []

    def update(self, metrics: dict[str, Any]) -> None:
        self._pending.append(metrics)

    def flush(self) -> dict[str, float]:
        for metrics in self._pending:
            for k, v in metrics.items():
                v = float(jax.device_get(v)) if isinstance(
                    v, (jax.Array, jnp.ndarray)) else float(v)
                self._sums[k] = self._sums.get(k, 0.0) + v
                self._counts[k] = self._counts.get(k, 0) + 1
        self._pending.clear()
        out = {k: self._sums[k] / self._counts[k] for k in self._sums}
        self._sums.clear()
        self._counts.clear()
        return out
