"""The tail of the walk over G + 64 rows against two tails of G and 64.

The question a one-walk step's one product with the head rests on
(PERF.md, PR 50): a prefill group's G `sample_at` rows and the decode
round's 64 rows each stream the head, the model's largest matrix (2.67 GB
at Falcon-H1's 5,120 x 261,120), through `transformer.unembed`; what does
one call over both save? The tail alone as `paged_engine.forward_sets`
runs it (final norm, the product with the head in float32, the head's
multiplier and soft cap where the configuration has them), at the
(hidden size, vocabulary) of each configuration of the benchmark, times
from the host's clock around a queue of calls that ends in
`block_until_ready`.

    python benchmarks/unembed_join_bench.py            # on a TPU
    python benchmarks/unembed_join_bench.py \
        --configs cellbench/configs/falcon-h1-34b-instruct.json --groups 1,8
    JAX_PLATFORMS=cpu python benchmarks/unembed_join_bench.py --tiny

Prints one JSON line per measurement and writes them to
`chiprun_out/unembed_join_bench.jsonl`.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cloud_server_tpu.config import ModelConfig  # noqa: E402
from cloud_server_tpu.models import transformer  # noqa: E402
from cloud_server_tpu.ops import rms_norm  # noqa: E402

DECODE_ROWS = 64
CONFIGS = ",".join(
    f"cellbench/configs/{name}.json" for name in (
        "mixtral-8x7b-v0.1", "smallthinker-21b-a3b-instruct",
        "longcat-flash-chat", "falcon-h1-34b-instruct"))


def _config(path: str) -> ModelConfig:
    """A benchmark configuration file's ModelConfig, by its family."""
    from cellbench import families
    with open(path) as f:
        cfg_file = json.load(f)
    return families.of(cfg_file).model_config(cfg_file)


def _params(cfg: ModelConfig, key):
    """What the tail reads of a model: the final norm's scale and the
    head (the embedding itself where the two are tied)."""
    d, v = cfg.embed_dim, cfg.vocab_size
    dt = jnp.dtype(cfg.param_dtype)
    params = {"final_norm": {"scale": jnp.ones((d,), dt)}}
    if cfg.tie_embeddings:
        params["embed"] = {"tokens": (jax.random.normal(
            key, (v, d), jnp.float32) * d ** -0.5).astype(dt)}
    else:
        params["lm_head"] = {"kernel": (jax.random.normal(
            key, (d, v), jnp.float32) * d ** -0.5).astype(dt)}
    return params


def _tail(x, params, cfg: ModelConfig):
    """(rows, D) of the stream -> (rows, V) float32 logits."""
    out = transformer.unembed(
        rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps),
        params, cfg)
    return (out if cfg.lm_head_multiplier == 1.0
            else out * cfg.lm_head_multiplier)


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
    ap.add_argument("--configs", default=CONFIGS,
                    help="configuration files of the benchmark")
    ap.add_argument("--groups", default="1,2,4,8",
                    help="the G of the joined and the two-call cases")
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": {"platform": dev.platform,
                                 "kind": dev.device_kind}}), flush=True)
    if a.tiny:
        a.reps, a.sets = 2, 1
    elif dev.platform != "tpu":
        raise SystemExit("real widths are measured on a TPU only")
    lines = []
    for path in [p for p in a.configs.split(",") if p]:
        cfg = _config(path)
        if a.tiny:
            cfg = dataclasses.replace(cfg, embed_dim=64, vocab_size=509,
                                      dtype="float32",
                                      param_dtype="float32")
        params = _params(cfg, jax.random.PRNGKey(0))
        head_bytes = (cfg.embed_dim * cfg.vocab_size
                      * jnp.dtype(cfg.dtype).itemsize)
        print(json.dumps({
            "config": path, "embed_dim": cfg.embed_dim,
            "vocab_size": cfg.vocab_size, "tied": cfg.tie_embeddings,
            "lm_head_multiplier": cfg.lm_head_multiplier,
            "logits_softcap": cfg.logits_softcap,
            "head_bytes": head_bytes}), flush=True)

        def x_of(rows, seed):
            return jax.random.normal(
                jax.random.PRNGKey(seed), (rows, cfg.embed_dim),
                jnp.float32).astype(cfg.dtype)

        def one(x, params):
            return _tail(x, params, cfg)

        def two(xp, xd, params):
            return _tail(xp, params, cfg), _tail(xd, params, cfg)

        def measure(name, fn, args, **extra):
            ms = _time_ms(jax.jit(fn), args, a.reps, a.sets)
            line = {"config": os.path.basename(path), "case": name,
                    "ms_median": statistics.median(ms), "ms_min": min(ms),
                    "ms_max": max(ms), **extra}
            lines.append(line)
            print(json.dumps(line), flush=True)
            return line["ms_median"]

        xd = x_of(DECODE_ROWS, 1)
        alone = measure(f"one_call_{DECODE_ROWS}", one, (xd, params), group=0)
        for g in [int(g) for g in a.groups.split(",") if g]:
            apart = measure(f"two_calls_{g}_and_{DECODE_ROWS}", two,
                            (x_of(g, 2), xd, params), group=g)
            joined = measure(f"one_call_{g + DECODE_ROWS}", one,
                             (x_of(g + DECODE_ROWS, 3), params), group=g)
            print(json.dumps({"config": os.path.basename(path), "group": g,
                              "saved_ms": apart - joined,
                              "joined_over_decode_alone": joined / alone}),
                  flush=True)
        del params, xd

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/unembed_join_bench.jsonl", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
