"""Steady-state decode-attention microbench on the real TPU.

Compares, at the serving-bench shape (B=8 slots, S=1024 context, MHA
KH=16, Dh=64, single-layer pools):

  * xla-dense      — `causal_attention` over the contiguous cache (the
                     engine's default decode path), bf16 and int8 caches
  * paged-pallas   — `ops.paged_attention` kernel (W in {1, 4}), bf16 and
                     int8 pools

Methodology — one call of a ~50 us kernel is mostly the fixed cost of a
dispatch and a sync, not the kernel. Each case therefore runs TWO jits
that scan the attention N1 and N2 times with the output fed back into
the query (nothing hoists), and reports (t(N2) - t(N1)) / (N2 - N1): the
fixed cost cancels, leaving the per-iteration device time. Effective
bandwidth counts one cache read per iteration.

Run on the chip:  python benchmarks/decode_attention_bench.py
(one process holds the chip; run nothing else concurrently.)
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# allow `python benchmarks/decode_attention_bench.py` from anywhere —
# bench.py lives at the repo root, one level up
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from bench import diff_time_scan  # noqa: E402
from cloud_server_tpu.inference.engine import _kv_quant
from cloud_server_tpu.inference.paged_engine import quantize_pool
from cloud_server_tpu.ops.attention import causal_attention
from cloud_server_tpu.ops.paged_attention import paged_attention

B, S, H, KH, D = 8, 1024, 16, 16, 64
PS = 128
# 1500-iteration delta at ~50-200 us/iter >> the fixed cost's
# variance (shorter deltas have produced negative estimates)
N1, N2 = 100, 1600


def _diff_time(make_fn, q0):
    return diff_time_scan(make_fn, (q0,), N1, N2, reps=3)


def window_against_full(rows: int, heads: int, kv_heads: int, head_dim: int,
                        contexts: list[int], window: int) -> None:
    """The paged kernel at one decode round's shape of a configuration
    with sliding-window layers: `rows` slots whose contexts cycle through
    `contexts`, every key read (a full layer) against the last `window`
    (a window layer, whose table holds no page behind the bound). Prints
    us a call and the bytes a second of the keys that had to be read."""
    ks = jax.random.split(jax.random.key(1), 4)
    dtype = jnp.bfloat16
    lens_np = np.asarray([contexts[i % len(contexts)] for i in range(rows)])
    mp = -(-int(lens_np.max()) // PS)
    full_pages = int(sum(-(-n // PS) for n in lens_np))
    k_pool = jax.random.normal(ks[0], (1, full_pages, kv_heads, head_dim, PS),
                               dtype)
    v_pool = jax.random.normal(ks[1], (1, full_pages, kv_heads, head_dim, PS),
                               dtype)
    tables = np.full((rows, mp), full_pages, np.int32)
    behind = np.full((rows, mp), full_pages, np.int32)  # pages given back
    at = 0
    for i, n in enumerate(lens_np):
        used = -(-int(n) // PS)
        tables[i, :used] = np.arange(at, at + used)
        first = max(int(n) - window, 0) // PS
        behind[i, first:used] = tables[i, first:used]
        at += used
    lens = jnp.asarray(lens_np, jnp.int32)
    q = jax.random.normal(ks[2], (rows, 1, heads, head_dim), dtype)

    def scan_of(body, n):
        def fn(q0):
            def f(q, _):
                return body(q).astype(q.dtype), None
            return lax.scan(f, q0, None, length=n)[0]
        return fn

    per_key = 2 * kv_heads * head_dim * 2
    for name, tab, win in (("full", tables, 0), ("window", behind, window)):
        keys = int(np.minimum(lens_np, win).sum() if win else lens_np.sum())
        for npb in (4, 8):
            def body(q, tab=jnp.asarray(tab), win=win, npb=npb):
                return paged_attention(q, k_pool, v_pool, lens, tab, 0,
                                       pages_per_block=npb, interpret=False,
                                       window=win)
            dt = diff_time_scan(lambda n: scan_of(body, n), (q,), 20, 120,
                                reps=3)
            print(f"paged {name:6s} rows={rows} G={heads // kv_heads} "
                  f"npb={npb} keys={keys} {dt * 1e6:9.1f} us/call "
                  f"{keys * per_key / dt / 1e9:7.1f} GB/s of keys", flush=True)


def main():
    if len(sys.argv) > 1:
        import argparse
        ap = argparse.ArgumentParser()
        ap.add_argument("--rows", type=int, default=64)
        ap.add_argument("--heads", type=int, default=28)
        ap.add_argument("--kv-heads", type=int, default=4)
        ap.add_argument("--head-dim", type=int, default=128)
        ap.add_argument("--contexts", default="8192,10240,12288,14336")
        ap.add_argument("--window", type=int, default=4096)
        a = ap.parse_args()
        window_against_full(a.rows, a.heads, a.kv_heads, a.head_dim,
                            [int(c) for c in a.contexts.split(",")], a.window)
        return
    ks = jax.random.split(jax.random.key(0), 8)
    dtype = jnp.bfloat16
    lens = jnp.full((B,), S, jnp.int32)

    # contiguous cache (engine layout)
    k_cat = jax.random.normal(ks[0], (B, S, KH, D), dtype)
    v_cat = jax.random.normal(ks[1], (B, S, KH, D), dtype)
    kq_cat, ksc_cat = _kv_quant(k_cat)
    vq_cat, vsc_cat = _kv_quant(v_cat)

    # paged pools (1 "layer"), transposed pages (L, P, KH, Dh, ps)
    mp = S // PS
    num_pages = B * mp
    perm = np.random.RandomState(0).permutation(num_pages)
    tables = jnp.asarray(perm.reshape(B, mp), jnp.int32)
    k_pool = jax.random.normal(ks[2], (1, num_pages, KH, D, PS), dtype)
    v_pool = jax.random.normal(ks[3], (1, num_pages, KH, D, PS), dtype)

    kq_pool, ksc_pool = quantize_pool(k_pool)
    vq_pool, vsc_pool = quantize_pool(v_pool)

    cache_bytes = {"bf16": 2 * B * S * KH * D * 2,
                   "int8": 2 * B * S * KH * D + 2 * B * S * KH * 4}
    results = {}
    only = os.environ.get("BENCH_CASES", "")  # substring filter

    def report(name, timer, kind):
        if only and only not in name:
            return
        dt = timer()
        gbs = cache_bytes[kind] / dt / 1e9
        results[name] = dt
        print(f"{name:30s} {dt * 1e6:9.1f} us/iter   {gbs:7.1f} GB/s eff",
              flush=True)

    def scan_of(body, n):
        def fn(q0):
            def f(q, _):
                return body(q).astype(q.dtype), None
            return lax.scan(f, q0, None, length=n)[0]
        return fn

    q1 = jax.random.normal(ks[4], (B, 1, H, D), dtype)

    def xla_body(q):
        return causal_attention(q, k_cat, v_cat,
                                q_positions=(lens - 1)[:, None],
                                kv_length=lens)

    report("xla-dense bf16 W=1",
           lambda: _diff_time(lambda n: scan_of(xla_body, n), q1), "bf16")

    def xla8_body(q):
        return causal_attention(q, kq_cat, vq_cat,
                                q_positions=(lens - 1)[:, None],
                                kv_length=lens,
                                k_scale=ksc_cat, v_scale=vsc_cat)

    report("xla-dense int8 W=1",
           lambda: _diff_time(lambda n: scan_of(xla8_body, n), q1), "int8")

    for w in (1, 4):
        qw = jax.random.normal(ks[5], (B, w, H, D), dtype)
        for npb in (2, 4, 8):
            def paged_body(q, npb=npb):
                return paged_attention(q, k_pool, v_pool, lens, tables, 0,
                                       pages_per_block=npb,
                                       interpret=False)

            report(f"paged-pallas bf16 W={w} npb={npb}",
                   lambda: _diff_time(
                       lambda n: scan_of(paged_body, n), qw),
                   "bf16")

        for npb in (4, 8):
            def paged8_body(q, npb=npb):
                return paged_attention(q, kq_pool, vq_pool, lens, tables, 0,
                                       pages_per_block=npb, interpret=False,
                                       k_scale_pool=ksc_pool,
                                       v_scale_pool=vsc_pool)

            report(f"paged-pallas int8 W={w} npb={npb}",
                   lambda: _diff_time(
                       lambda n: scan_of(paged8_body, n), qw), "int8")

    base = results.get("xla-dense bf16 W=1")
    if base:
        for name, dt in results.items():
            print(f"{name:30s} speedup vs xla-dense: {base / dt:5.2f}x")


if __name__ == "__main__":
    main()
