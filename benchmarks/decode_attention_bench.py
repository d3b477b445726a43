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

With arguments it times the paged kernel alone at a serving shape:
`--preset mixtral-decode | smallthinker-decode | chunk | narrow | all`
are the calls of the benchmark's two cells (64 decode rows at the
traffic's contexts, the chunk rows at W = 256, the narrow kernel at 8
rows), each with the bytes of the keys the rows read, of the pages that
hold them and of the whole blocks, as us at 819 GB/s; `--rows --heads
--kv-heads --contexts --window` is one decode round of a configuration
with window layers, a full layer against a window layer.
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


HBM_GBS = 819.0  # one v5e chip (cellbench/peaks.json)


def scan_of(body, n):
    def fn(q0):
        def f(q, _):
            return body(q).astype(q.dtype), None
        return lax.scan(f, q0, None, length=n)[0]
    return fn


def _paged_case(lens_np, w: int, heads: int, kv_heads: int, head_dim: int,
                window: int):
    """bf16 pools, queries and a table a row for contexts `lens_np`; with
    `window`, the entries for pages wholly behind the first query's bound
    point past the pool, as after the host's hand-back."""
    ks = jax.random.split(jax.random.key(1), 3)
    dtype = jnp.bfloat16
    rows = len(lens_np)
    mp = -(-int(lens_np.max()) // PS)
    pages = int(sum(-(-int(n) // PS) for n in lens_np))
    k_pool = jax.random.normal(ks[0], (1, pages, kv_heads, head_dim, PS),
                               dtype)
    v_pool = jax.random.normal(ks[1], (1, pages, kv_heads, head_dim, PS),
                               dtype)
    tables = np.full((rows, mp), pages, np.int32)
    at = 0
    for i, n in enumerate(lens_np):
        used = -(-int(n) // PS)
        first = max(int(n) - w - (window - 1), 0) // PS if window else 0
        tables[i, first:used] = np.arange(at + first, at + used)
        at += used
    q = jax.random.normal(ks[2], (rows, w, heads, head_dim), dtype)
    return (q, k_pool, v_pool, jnp.asarray(lens_np, jnp.int32),
            jnp.asarray(tables))


def _key_counts(lens_np, w: int, window: int, npb: int):
    """Keys of one call: those some query reads, those of the pages that
    hold them, and those of the whole blocks from the first block read to
    the last (what a kernel fetches that does not bound a block's fetch
    by the row)."""
    need = by_page = by_block = 0
    blk = PS * npb
    for n in map(int, lens_np):
        lo = max(n - w - (window - 1), 0) if window else 0
        need += n - lo
        by_page += (-(-n // PS) - lo // PS) * PS
        by_block += (max(-(-n // blk), 1) - min(lo // blk,
                                                max(-(-n // blk), 1) - 1)) * blk
    return need, by_page, by_block


def time_paged_call(name: str, lens_np, *, w: int, heads: int, kv_heads: int,
                    head_dim: int, window: int, npbs) -> None:
    """One `paged_attention` call at a cell's shape: us a call, and beside
    it the bytes of the keys the rows read, of the pages that hold them
    and of the whole blocks, each as us at the chip's 819 GB/s."""
    args = _paged_case(lens_np, w, heads, kv_heads, head_dim, window)
    per_key = 2 * kv_heads * head_dim * 2  # k and v, bf16
    for npb in npbs:
        def make(n, npb=npb):
            # the pools are operands: as constants of the program they
            # would be compiled into it, a gigabyte at a cell's shape
            def fn(q0, k_pool, v_pool, lens, tables):
                def f(q, _):
                    o = paged_attention(q, k_pool, v_pool, lens, tables, 0,
                                        pages_per_block=npb, interpret=False,
                                        window=window)
                    return o.astype(q.dtype), None
                return lax.scan(f, q0, None, length=n)[0]
            return fn
        dt = diff_time_scan(make, args, 20, 120, reps=3)
        counts = _key_counts(lens_np, w, window, npb)
        cols = " ".join(
            f"{what}={keys * per_key / 1e6:.1f}MB/"
            f"{keys * per_key / HBM_GBS / 1e3:.1f}us"
            for what, keys in zip(("read", "pages", "blocks"), counts))
        need = counts[0] * per_key
        print(f"{name:24s} rows={len(lens_np)} w={w} kh={kv_heads} "
              f"G={heads // kv_heads} window={window} npb={npb} "
              f"{dt * 1e6:9.1f} us/call {cols} "
              f"roofline={need / HBM_GBS / 1e3 / (dt * 1e6) * 100:.1f}%",
              flush=True)


def _served_contexts(n: int, seed: int = 0):
    """Contexts of `n` live rows of the serving cells' traffic
    (cellbench/workloads): a lognormal prompt (median 512, sigma 0.6,
    272..1536) and a uniform share of a lognormal answer (median 128,
    sigma 0.7, 16..512): about 300 to 2,000 keys."""
    rng = np.random.RandomState(seed)
    prompt = np.clip(np.exp(np.log(512) + 0.6 * rng.randn(n)), 272, 1536)
    answer = np.clip(np.exp(np.log(128) + 0.7 * rng.randn(n)), 16, 512)
    return (prompt + rng.rand(n) * answer).astype(np.int64)


def presets(which: str) -> None:
    """The paged kernel at the shapes the benchmark's two cells call it
    with (`--preset`): each cell's 64 decode rows, the chunk rows, and the
    narrow kernel at 8 rows."""
    mixtral = dict(heads=32, kv_heads=8, head_dim=128)
    thinker = dict(heads=28, kv_heads=4, head_dim=128)
    short = _served_contexts(64)
    long_rows = np.random.RandomState(1).randint(8192, 14337, size=35)
    mixed = np.concatenate([long_rows, short[:29]])
    np.random.RandomState(2).shuffle(mixed)
    sweep = (2, 4, 8)
    if which in ("mixtral-decode", "all"):
        time_paged_call("mixtral decode", short, w=1, window=0, npbs=sweep,
                        **mixtral)
    if which in ("smallthinker-decode", "all"):
        time_paged_call("smallthinker window", mixed, w=1, window=4096,
                        npbs=sweep, **thinker)
        time_paged_call("smallthinker full", mixed, w=1, window=0,
                        npbs=sweep, **thinker)
    if which in ("chunk", "all"):
        # 8 prompts mid-prefill, their newest chunk of 256 included
        time_paged_call("mixtral chunk", np.arange(1, 9) * 192 + 64, w=256,
                        window=0, npbs=(2, 4), **mixtral)
        deep = np.arange(1, 9) * 1536 + 256
        time_paged_call("smallthinker chunk window", deep, w=256,
                        window=4096, npbs=(2, 4), **thinker)
        time_paged_call("smallthinker chunk full", deep, w=256, window=0,
                        npbs=(2, 4), **thinker)
    if which in ("narrow", "all"):
        for w in (1, 4):
            time_paged_call("narrow", short[:8] + w, w=w, window=0,
                            npbs=(8,), **mixtral)


def window_against_full(rows: int, heads: int, kv_heads: int, head_dim: int,
                        contexts: list[int], window: int) -> None:
    """The paged kernel at one decode round's shape of a configuration
    with sliding-window layers: `rows` slots whose contexts cycle through
    `contexts`, every key read (a full layer) against the last `window`
    (a window layer, whose table holds no page behind the bound)."""
    lens_np = np.asarray([contexts[i % len(contexts)] for i in range(rows)])
    for name, win in (("full", 0), ("window", window)):
        time_paged_call(name, lens_np, w=1, heads=heads, kv_heads=kv_heads,
                        head_dim=head_dim, window=win, npbs=(4, 8))


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
        ap.add_argument("--preset", default=None, choices=(
            "mixtral-decode", "smallthinker-decode", "chunk", "narrow",
            "all"))
        a = ap.parse_args()
        if a.preset:
            presets(a.preset)
            return
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
