"""Fused-CE kernel A/B at the exact 330M bench config (r5 MFU attack).

Times the FULL train step with ce_impl dense (baseline, r5 measured
220.0 ms / decomposition put the CE block at ~16.5 ms) vs pallas
(ops/fused_ce.py), plus the isolated CE fwd+bwd for the kernel-level
differential. Run on the chip, one process at a time."""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.training import init_train_state, make_train_step

BASE = ModelConfig(
    vocab_size=32000, embed_dim=1024, num_layers=16, num_heads=16,
    num_kv_heads=16, head_dim=64, mlp_dim=4096, max_seq_len=1024,
    dtype="bfloat16", param_dtype="float32", remat="dots",
    attention_impl="flash")
B, S = 8, 1024


def timeit(fn, n=10, warmup=3):
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return 1000 * (time.perf_counter() - t0) / n


def step_time(cfg):
    mesh = make_mesh(MeshConfig())
    tcfg = TrainConfig(batch_size=B, seq_len=S, warmup_steps=10,
                       total_steps=100)
    state = init_train_state(cfg, tcfg, mesh, jax.random.key(0))
    step, bsh = make_train_step(cfg, tcfg, mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, S), 0,
                           cfg.vocab_size), bsh)
    batch = {"tokens": tokens}
    holder = {"s": state}

    def one():
        s2, m = step(holder["s"], batch)
        holder["s"] = s2
        return m["loss"]

    ms = timeit(one)
    loss = float(jax.device_get(holder["s"] and one()))
    return ms, loss


def ce_only(cfg):
    params = transformer.init_params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(2), (B, S, cfg.embed_dim),
                          jnp.bfloat16)
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}

    if cfg.ce_impl == "pallas":
        def loss_fn(p, x):
            return transformer.pallas_cross_entropy(x, p, batch, cfg)[0]
    else:
        def loss_fn(p, x):
            logits = transformer.unembed(x, p, cfg)
            return transformer.masked_cross_entropy(logits, batch)[0]
    g = jax.jit(jax.grad(loss_fn, argnums=(0, 1)))
    return timeit(lambda: jax.tree.leaves(g(params, x))[0])


def main():
    out = {}
    for tag, cfg in (("dense", BASE),
                     ("pallas", dataclasses.replace(BASE,
                                                    ce_impl="pallas"))):
        out[f"ce_fwdbwd_ms_{tag}"] = round(ce_only(cfg), 2)
        print(json.dumps({k: v for k, v in out.items() if tag in k}),
              flush=True)
    for tag, cfg in (("dense", BASE),
                     ("pallas", dataclasses.replace(BASE,
                                                    ce_impl="pallas"))):
        ms, loss = step_time(cfg)
        out[f"step_ms_{tag}"] = round(ms, 2)
        out[f"loss_{tag}"] = round(loss, 4)
        print(json.dumps({k: v for k, v in out.items() if tag in k}),
              flush=True)
    print("FINAL " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
