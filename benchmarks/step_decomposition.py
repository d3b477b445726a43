"""Where do the 330M bench step's milliseconds go? (r5 MFU attack)

Differential timings on the real chip, at EXACTLY the bench config
(bench.py train_bench: 330M, B=8, S=1024, bf16, flash, remat="dots"):

  full step            = fwd + bwd + optimizer
  loss fwd             : next_token_loss under jit
  fwd+bwd              : jax.grad(next_token_loss)
  hidden fwd           : forward_hidden (stack without unembed/CE)
  hidden fwd+bwd       : grad through forward_hidden (sum of hiddens)
  CE fwd / CE fwd+bwd  : masked_cross_entropy given PRE-COMPUTED
                         hidden states (isolates unembed matmul + CE)
  optimizer            : full step minus fwd+bwd (plus direct measure)

The CE rows bound what a fused (Liger-style) unembed+CE pallas kernel
could recover; the hidden rows bound what qkv/rope/norm fusion could.
Usage (on the chip, nothing else running):  python benchmarks/step_decomposition.py
"""

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

CFG = ModelConfig(
    vocab_size=32000, embed_dim=1024, num_layers=16, num_heads=16,
    num_kv_heads=16, head_dim=64, mlp_dim=4096, max_seq_len=1024,
    dtype="bfloat16", param_dtype="float32", remat="dots",
    attention_impl="flash")
B, S = 8, 1024


def timeit(fn, *args, n=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1000 * (time.perf_counter() - t0) / n


def main():
    mesh = make_mesh(MeshConfig())
    tcfg = TrainConfig(batch_size=B, seq_len=S, warmup_steps=10,
                       total_steps=100)
    state = init_train_state(CFG, tcfg, mesh, jax.random.key(0))
    step, bsh = make_train_step(CFG, tcfg, mesh)
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, S), 0, CFG.vocab_size),
        bsh)
    batch = {"tokens": tokens}
    out = {}

    holder = {"state": state}

    def full():
        # the train step DONATES its state buffers: thread the new
        # state through or the second call reads freed memory
        s2, m = step(holder["state"], batch)
        holder["state"] = s2
        return m["loss"]
    out["full_step_ms"] = timeit(full)
    params = holder["state"].params

    loss_fwd = jax.jit(lambda p, b: transformer.next_token_loss(
        p, b, CFG)[0])
    out["loss_fwd_ms"] = timeit(lambda: loss_fwd(params, batch))

    loss_grad = jax.jit(lambda p, b: jax.grad(
        lambda q: transformer.next_token_loss(q, b, CFG)[0])(p))
    out["loss_fwdbwd_ms"] = timeit(
        lambda: jax.tree.leaves(loss_grad(params, batch))[0])

    hid_fwd = jax.jit(lambda p, t: transformer.forward_hidden(p, t, CFG))
    out["hidden_fwd_ms"] = timeit(lambda: hid_fwd(params, tokens))

    hid_grad = jax.jit(lambda p, t: jax.grad(
        lambda q: transformer.forward_hidden(q, t, CFG)
        .astype(jnp.float32).sum())(p))
    out["hidden_fwdbwd_ms"] = timeit(
        lambda: jax.tree.leaves(hid_grad(params, tokens))[0])

    x = jax.jit(lambda p, t: transformer.forward_hidden(p, t, CFG))(
        params, tokens)
    x = jax.block_until_ready(x)

    def ce(p, x, b):
        logits = transformer.unembed(x, p, CFG)
        return transformer.masked_cross_entropy(logits, b)[0]
    ce_fwd = jax.jit(ce)
    out["ce_fwd_ms"] = timeit(lambda: ce_fwd(params, x, batch))
    ce_grad = jax.jit(lambda p, x, b: jax.grad(ce, argnums=(0, 1))(
        p, x, b))
    out["ce_fwdbwd_ms"] = timeit(
        lambda: jax.tree.leaves(ce_grad(params, x, batch))[0])

    out["optimizer_ms"] = out["full_step_ms"] - out["loss_fwdbwd_ms"]
    out["ce_share_of_fwdbwd"] = round(
        out["ce_fwdbwd_ms"] / out["loss_fwdbwd_ms"], 3)
    for k, v in out.items():
        out[k] = round(v, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
