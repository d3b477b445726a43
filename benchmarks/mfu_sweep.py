"""MFU lever sweep at the bench's 330M config (run on the real TPU).

Training MFU has sat at ~0.377 for two rounds; the r3 sweep exhausted
the flash-attention levers, so this probes the MODEL-level ones the
verdict called out:

  * remat policy — "dots" recomputes most of the layer in the backward;
    at 330M / B=8 / S=1024 the activations may simply fit, making
    remat="none" pure win.
  * vocab_chunk — 0 materialises the (B*S, 32000) f32 logits (~1 GB
    written + re-read around the softmax); the fused blockwise CE never
    does, at the price of recomputing the unembed matmul chunk-by-chunk
    in the backward.
  * flash vs xla attention at this sequence length, crossed with remat.

Usage: python benchmarks/mfu_sweep.py  (takes a few minutes; one config
per compile).
"""

import itertools
import os
import sys
import time

# run as `python benchmarks/mfu_sweep.py` from the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def measure(model_cfg, steps=20, warm=3):
    from cloud_server_tpu.config import MeshConfig, TrainConfig
    from cloud_server_tpu.parallel.mesh import make_mesh
    from cloud_server_tpu.training import init_train_state, make_train_step

    batch, seq = 8, 1024
    train_cfg = TrainConfig(batch_size=batch, seq_len=seq, warmup_steps=10,
                            total_steps=100)
    mesh = make_mesh(MeshConfig())
    state = init_train_state(model_cfg, train_cfg, mesh, jax.random.key(0))
    step, batch_sharding = make_train_step(model_cfg, train_cfg, mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (batch, seq), 0,
                           model_cfg.vocab_size), batch_sharding)
    data = {"tokens": tokens}
    for _ in range(warm):
        state, metrics = step(state, data)
    jax.device_get(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, data)
    loss = float(jax.device_get(metrics["loss"]))
    dt = (time.perf_counter() - t0) / steps
    assert loss == loss, "NaN loss"
    return 1000 * dt


def main():
    import dataclasses

    from cloud_server_tpu.config import ModelConfig

    base = ModelConfig(
        vocab_size=32000, embed_dim=1024, num_layers=16, num_heads=16,
        num_kv_heads=16, head_dim=64, mlp_dim=4096, max_seq_len=1024,
        dtype="bfloat16", param_dtype="float32", remat="dots",
        attention_impl="flash")

    results = {}
    for remat, vc in itertools.product(("dots", "none"), (0, 4096, 8192)):
        cfg = dataclasses.replace(base, remat=remat, vocab_chunk=vc)
        try:
            ms = measure(cfg)
        except Exception as exc:  # noqa: BLE001 — OOM etc: record and go on
            print(f"remat={remat} vocab_chunk={vc}: FAILED {exc!r}"[:200],
                  flush=True)
            continue
        results[(remat, vc)] = ms
        print(f"remat={remat} vocab_chunk={vc}: {ms:.1f} ms/step",
              flush=True)

    # cross attention impl at the best (remat, vc)
    if results:
        (best_remat, best_vc), best = min(results.items(),
                                          key=lambda kv: kv[1])
        for impl in ("xla",):
            cfg = dataclasses.replace(base, remat=best_remat,
                                      vocab_chunk=best_vc,
                                      attention_impl=impl)
            try:
                ms = measure(cfg)
                print(f"best+{impl} attention: {ms:.1f} ms/step",
                      flush=True)
            except Exception as exc:  # noqa: BLE001
                print(f"best+{impl}: FAILED {exc!r}"[:200], flush=True)
        print(f"BEST: remat={best_remat} vocab_chunk={best_vc} "
              f"{best:.1f} ms/step (r3 baseline 221.2)", flush=True)


if __name__ == "__main__":
    main()
