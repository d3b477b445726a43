"""One expert layer over T + 64 tokens against two calls of T and 64.

The question a mixed step's one walk of the layers rests on (PERF.md,
PR 32): the decode round's 64 rows and a prefill group's T chunk tokens
each stream a layer's experts (2.8 GB at Mixtral's widths) through
`moe.moe_mlp_block`; how much of that does one call over both save?
Whole layer (norm, router, dispatch, experts, combine, residual), `stack`
given as the paged engine gives it, times from the host's clock around a
queue of calls that ends in `block_until_ready`.

    python benchmarks/moe_joined_call_bench.py            # on a TPU
    JAX_PLATFORMS=cpu python benchmarks/moe_joined_call_bench.py --tiny

Prints one JSON line per measurement and writes them to
`chiprun_out/moe_joined_call_bench.jsonl`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import moe

DECODE_ROWS = 64


def _layers(cfg: ModelConfig, n_layers: int, key):
    d, e, f = cfg.embed_dim, cfg.num_experts, cfg.mlp_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    return {"mlp_norm": jnp.ones((n_layers, d), dt),
            "router": w(ks[0], (n_layers, d, e), d),
            "w_gate": w(ks[1], (n_layers, e, d, f), d),
            "w_up": w(ks[2], (n_layers, e, d, f), d),
            "w_down": w(ks[3], (n_layers, e, f, d), f)}


def _block(x, layers, cfg, layer):
    lp = jax.tree.map(lambda p: p[layer], layers)
    return moe.moe_mlp_block(x, lp, cfg, (layers, layer))[0]


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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="tiny widths: a CPU rehearsal of the control flow")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--sets", type=int, default=5)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    if a.tiny:
        cfg = ModelConfig(embed_dim=64, mlp_dim=128, num_experts=8,
                          num_experts_per_token=2,
                          expert_capacity_factor=4.0, dtype="float32")
        a.reps, a.sets = 2, 1
    else:
        if dev.platform != "tpu":
            raise SystemExit("real widths are measured on a TPU only")
        cfg = ModelConfig(embed_dim=4096, mlp_dim=14336, num_experts=8,
                          num_experts_per_token=2,
                          expert_capacity_factor=4.0, dtype="bfloat16")
    layers = _layers(cfg, 2, jax.random.PRNGKey(0))
    lines = []

    def x_of(t, seed):
        return jax.random.normal(jax.random.PRNGKey(seed),
                                 (1, t, cfg.embed_dim),
                                 jnp.float32).astype(cfg.dtype)

    def measure(name, fn, args, **extra):
        # a new function object a case: `GROUPED_MIN_TOKENS` is read at
        # trace time, and jit's cache is keyed by the function
        ms = _time_ms(jax.jit(lambda *xs: fn(*xs)), args, a.reps, a.sets)
        line = {"case": name, "ms_median": statistics.median(ms),
                "ms_min": min(ms), "ms_max": max(ms), **extra}
        lines.append(line)
        print(json.dumps(line), flush=True)
        return line["ms_median"]

    def one(x, layers):
        return _block(x, layers, cfg, 1)

    def two(xc, xd, layers):
        return _block(xc, layers, cfg, 1), _block(xd, layers, cfg, 1)

    xd = x_of(DECODE_ROWS, 1)
    for t in (16, 64, 128, 256, 512, 1024, 2048):
        xc = x_of(t, 2)
        apart = measure(f"two_calls_{t}_and_{DECODE_ROWS}", two,
                        (xc, xd, layers), tokens=t)
        joined = measure(f"one_call_{t + DECODE_ROWS}", one,
                         (x_of(t + DECODE_ROWS, 3), layers), tokens=t,
                         sorted=moe._dispatch_grouped(
                             cfg, t + DECODE_ROWS, (layers, 1)))
        print(json.dumps({"tokens": t, "saved_ms_a_layer": apart - joined}),
              flush=True)

    # both dispatches in the gap PR 26 left between 256 and 512, and at
    # the neighbours a joined call moves to (256 + 64, 512 + 64)
    placed = moe.GROUPED_MIN_TOKENS
    for t in (192, 256, 320, 384, 448, 512, 576):
        for name, floor in (("dense", 1 << 30), ("sorted", 1)):
            moe.GROUPED_MIN_TOKENS = floor
            measure(f"{name}_{t}", one, (x_of(t, 4), layers), tokens=t)
    moe.GROUPED_MIN_TOKENS = placed

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_joined_call_bench.jsonl", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
