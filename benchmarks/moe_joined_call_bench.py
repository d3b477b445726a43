"""One expert layer over T + 64 tokens against two calls of T and 64,
both dispatches of it at every T, and the grouped matmul's tiles.

The question a mixed step's one walk of the layers rests on (PERF.md,
PR 32): the decode round's 64 rows and a prefill group's T chunk tokens
each stream a layer's experts (2.8 GB at Mixtral's widths) through
`moe.moe_mlp_block`; how much of that does one call over both save?
Whole layer (norm, router, dispatch, experts, combine, residual), `stack`
given as the paged engine gives it, times from the host's clock around a
queue of calls that ends in `block_until_ready`.

The widths, the experts, the experts a token, the activation and where
the router reads come from a configuration file of the benchmark
(`--config`, through its family's `model_config`), so one script made
the table of every configuration (PERF.md, PR 35).

    python benchmarks/moe_joined_call_bench.py            # on a TPU
    python benchmarks/moe_joined_call_bench.py \
        --config cellbench/configs/smallthinker-21b-a3b-instruct.json \
        --tokens 16,64,256,512,1024,2048 --tiles
    JAX_PLATFORMS=cpu python benchmarks/moe_joined_call_bench.py --tiny

Beside every sorted case's ms a layer stand the row-tile visits of its
grouped matmuls (megablox's own `make_group_metadata` on the call's own
`group_sizes`, which the bench fetches and the server never does) and
the rows of the buffer the kernel is handed: the kernel is paid by the
visit (PERF.md, PR 39), so the next writer reads the fit. The router
of the bench is even; `--skew 1.5` gives it the second cell's deeper
layers' (a third of the experts near empty, a few with most rows), where
the visits are the experts that have rows and a layout moves little.
Since PR 53 a visit computes only the sub-tiles its expert has a row in:
`rows_computed_ratio` is the rows the way in computes (each expert's
count rounded up to the kernel's sub-tiles) over the call's assignments,
the cells' `expert_rows_computed_ratio` for one call; `--sub-rows`
measures the `--sorted` calls again under other sub-tiles (256, the row
tile, is a visit that computes its whole tile).

Prints one JSON line per measurement and writes them to
`chiprun_out/moe_joined_call_bench.jsonl`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import dataclasses
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cloud_server_tpu.config import ModelConfig  # noqa: E402
from cloud_server_tpu.models import moe  # noqa: E402
from cloud_server_tpu.ops import grouped_matmul  # noqa: E402

DECODE_ROWS = 64


def _layers(cfg: ModelConfig, n_layers: int, key):
    d, e, f = cfg.embed_dim, cfg.num_experts, cfg.expert_width
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 7)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    # what the model states of the block beside its routed experts
    stated = {}
    if cfg.shared_expert_dim:
        fs = cfg.shared_expert_dim
        stated.update(shared_w_gate=w(ks[4], (n_layers, d, fs), d),
                      shared_w_up=w(ks[5], (n_layers, d, fs), d),
                      shared_w_down=w(ks[6], (n_layers, fs, d), fs))
    if cfg.post_norms:
        stated["mlp_post_norm"] = jnp.ones((n_layers, d), dt)
    return {**stated,
            "mlp_norm": jnp.ones((n_layers, d), dt),
            # a share's router has columns for experts held elsewhere
            "router": w(ks[0], (n_layers, d, cfg.router_width), d),
            "router_bias": jnp.zeros((n_layers, cfg.router_width), dt),
            "w_gate": w(ks[1], (n_layers, e, d, f), d),
            "w_up": w(ks[2], (n_layers, e, d, f), d),
            "w_down": w(ks[3], (n_layers, e, f, d), f)}


def _block(x, layers, cfg, layer):
    lp = jax.tree.map(lambda p: p[layer], layers)
    # the stream stands in for the layer's input where the router reads it
    return moe.moe_mlp_block(x, lp, cfg, (layers, layer), layer_in=x)[0]


def _config(path: str) -> ModelConfig:
    """The expert layer of a benchmark configuration file, by its family."""
    from cellbench import families
    with open(path) as f:
        cfg_file = json.load(f)
    return families.of(cfg_file).model_config(cfg_file)


def _time_ms(fn, args, reps: int, sets: int) -> list[float]:
    jax.block_until_ready(fn(*args))  # compile
    out = []
    for _ in range(sets):
        t0 = time.perf_counter()
        for _ in range(reps):
            y = fn(*args)
        jax.block_until_ready(y)
        out.append((time.perf_counter() - t0) * 1e3 / reps)
    return out


def _sorted_call(fn, args) -> dict:
    """The sorted dispatch's call inside `fn(*args)`: the rows of the
    buffer `_grouped_experts` is handed and the row-tile visits of each of
    its grouped matmuls, counted by the kernel's own metadata from the
    call's `group_sizes`. Empty where `fn` takes the dense dispatch."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)
    seen = []
    real = moe._grouped_experts

    def spy(rows, w_gate, w_up, w_down, group_sizes, group_rows, **kw):
        seen.append((rows.shape[0], (group_sizes, group_rows)))
        return real(rows, w_gate, w_up, w_down, group_sizes, group_rows,
                    **kw)

    def sizes(*xs):
        fn(*xs)
        return seen[-1][1] if seen else None

    moe._grouped_experts = spy
    try:
        groups = jax.jit(sizes)(*args)
    finally:
        moe._grouped_experts = real
    if groups is None:
        return {}
    group_sizes, group_rows = groups
    _, visits = make_group_metadata(
        group_sizes=group_sizes, m=seen[-1][0], tm=moe._GMM_ROWS,
        start_group=0, num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=False)
    return {"visits": int(visits), "buffer_rows": seen[-1][0],
            "extent_rows": int(group_sizes.sum()),
            "rows_computed_ratio": round(
                int(grouped_matmul.rows_computed(group_rows))
                / int(group_rows.sum()), 4)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="tiny widths: a CPU rehearsal of the control flow")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--sets", type=int, default=5)
    ap.add_argument("--config",
                    default="cellbench/configs/mixtral-8x7b-v0.1.json",
                    help="a configuration file of the benchmark")
    ap.add_argument("--tokens", default="16,64,128,256,512,1024,2048",
                    help="the T of the joined and the two-call cases")
    ap.add_argument("--between", default="192,256,320,384,448,512,576",
                    help="the T at which both dispatches are measured")
    ap.add_argument("--tiles", action="store_true",
                    help="sweep the way in's column tile and grid order")
    ap.add_argument("--sorted", default="",
                    help="the T at which the sorted dispatch alone is "
                         "measured")
    ap.add_argument("--sub-rows", default="",
                    help="sub-tiles of the way in to measure the --sorted "
                         "calls under, beside the placed one")
    ap.add_argument("--tile-tokens", default="320,576,1088,2112",
                    help="the T of the tile sweep's calls")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="how far the router is from even: every token "
                         "gets this much of one common vector, so the "
                         "experts' logits share a bias of that deviation "
                         "(0: even; 1 to 2: the second cell's layers 3 to "
                         "7, where a few experts take most rows and a "
                         "third take none, PERF.md PR 39)")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    cfg = _config(a.config)
    if a.tiny:
        cfg = dataclasses.replace(
            cfg, embed_dim=64, mlp_dim=128, dtype="float32",
            expert_mlp_dim=128 if cfg.expert_mlp_dim else 0)
        a.reps, a.sets = 2, 1
    elif dev.platform != "tpu":
        raise SystemExit("real widths are measured on a TPU only")
    print(json.dumps({"config": a.config, "embed_dim": cfg.embed_dim,
                      "expert_width": cfg.expert_width,
                      "experts": cfg.num_experts,
                      "a_token": cfg.num_experts_per_token,
                      "activation": cfg.mlp_activation,
                      "router_input": cfg.router_input,
                      "skew": a.skew,
                      "placed_min_tokens": moe.grouped_min_tokens(cfg),
                      "placed_sub_rows": grouped_matmul.SUB_ROWS,
                      "placed_tilings": {
                          t: moe._gmm_tilings(
                              cfg, t * cfg.num_experts_per_token)
                          for t in (320, 576, 1088, 2112)}}), flush=True)
    layers = _layers(cfg, 2, jax.random.PRNGKey(0))
    lines = []

    common = jax.random.normal(jax.random.PRNGKey(9), (cfg.embed_dim,),
                               jnp.float32)

    def x_of(t, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed),
                              (1, t, cfg.embed_dim), jnp.float32)
        return (x + a.skew * common).astype(cfg.dtype)

    def measure(name, fn, args, **extra):
        # a new function object a case: `GROUPED_MIN_TOKENS` is read at
        # trace time, and jit's cache is keyed by the function
        # a shape the compiler refuses (PR 39 met one: XLA's own gather of
        # bf16[8192,2560] rows over its scoped VMEM) is a line, not the end
        try:
            ms = _time_ms(jax.jit(lambda *xs: fn(*xs)), args, a.reps,
                          a.sets)
        except Exception as exc:  # noqa: BLE001
            print(json.dumps({"case": name, "refused": repr(exc)[:300],
                              **extra}), flush=True)
            return float("nan")
        line = {"case": name, "ms_median": statistics.median(ms),
                "ms_min": min(ms), "ms_max": max(ms), **extra,
                **_sorted_call(fn, args)}
        lines.append(line)
        print(json.dumps(line), flush=True)
        return line["ms_median"]

    def one(x, layers):
        return _block(x, layers, cfg, 1)

    def two(xc, xd, layers):
        return _block(xc, layers, cfg, 1), _block(xd, layers, cfg, 1)

    xd = x_of(DECODE_ROWS, 1)
    for t in [int(t) for t in a.tokens.split(",") if t]:
        xc = x_of(t, 2)
        apart = measure(f"two_calls_{t}_and_{DECODE_ROWS}", two,
                        (xc, xd, layers), tokens=t)
        joined = measure(f"one_call_{t + DECODE_ROWS}", one,
                         (x_of(t + DECODE_ROWS, 3), layers), tokens=t,
                         sorted=moe._dispatch_grouped(
                             cfg, t + DECODE_ROWS, (layers, 1)))
        print(json.dumps({"tokens": t, "saved_ms_a_layer": apart - joined}),
              flush=True)

    # both dispatches where the threshold is looked for (PR 26 left a gap
    # between 256 and 512 at Mixtral's widths) and at the neighbours a
    # joined call moves to (256 + 64, 512 + 64). The dense dispatch's
    # one-hot is (T, k, E, C): quadratic in T, so it is not asked past
    # 2 GB of it
    placed = moe.grouped_min_tokens
    for t in [int(t) for t in a.between.split(",") if t]:
        for name, floor in (("dense", 1 << 30), ("sorted", 1)):
            if name == "dense" and (t * t * cfg.num_experts_per_token
                                    * cfg.num_experts * 4) > 2 << 30:
                continue
            moe.grouped_min_tokens = lambda cfg, floor=floor: floor
            measure(f"{name}_{t}", one, (x_of(t, 4), layers), tokens=t)
    moe.grouped_min_tokens = placed

    # the sorted dispatch alone, as placed, at the calls a step makes;
    # then under other sub-tiles of the way in (the kernel reads the
    # constant when it is traced: the traces are given back between them)
    moe.grouped_min_tokens = lambda cfg: 1
    placed_sub = grouped_matmul.SUB_ROWS
    for sub in [placed_sub] + [int(r) for r in a.sub_rows.split(",") if r]:
        grouped_matmul.SUB_ROWS = sub
        jax.clear_caches()
        for t in [int(t) for t in a.sorted.split(",") if t]:
            measure(f"sorted_{t}", one, (x_of(t, 4), layers), tokens=t,
                    sub_rows=sub)
    grouped_matmul.SUB_ROWS = placed_sub
    jax.clear_caches()
    moe.grouped_min_tokens = placed

    if a.tiles:
        # the sorted dispatch alone at calls whose experts have one row
        # tile and at calls whose experts have several, over the way in's
        # column tiles in both grid orders (the block the whole width: the
        # visits outermost; the block one column tile: the columns
        # outermost); the placed pair first. `_grouped_experts` takes the
        # pair as a static argument, so every case is a trace of its own
        d, f = cfg.embed_dim, cfg.expert_width
        placed_t = moe._gmm_tilings
        moe.grouped_min_tokens = lambda cfg: 1
        way_out = moe._gmm_tiling(f, d)
        columns = [tn for tn in (256, 512, 768, 1024, 2048) if f % tn == 0]
        for t in [int(t) for t in a.tile_tokens.split(",") if t]:
            cases = [placed_t(cfg, t * cfg.num_experts_per_token)]
            for tn in columns:
                for pair in (((moe._GMM_ROWS, tn, f), way_out),
                             ((moe._GMM_ROWS, tn, tn), way_out)):
                    if pair not in cases:
                        cases.append(pair)
            for pair in cases:
                moe._gmm_tilings = lambda cfg, n, pair=pair: pair
                measure(f"tiles_{t}", one, (x_of(t, 5), layers),
                        tokens=t, tilings=pair)
        moe._gmm_tilings = placed_t
        moe.grouped_min_tokens = placed

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_joined_call_bench.jsonl", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
