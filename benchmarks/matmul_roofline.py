"""Pure-matmul efficiency at the 330M bench model's exact shapes.

The training step has sat at ~0.38 MFU for three rounds with every
model-level lever measured (flash blocks, remat, vocab_chunk, staged-dq
— see bench.py provenance notes). This isolates the question the step
time cannot answer: what fraction of the chip's peak bf16 FLOP/s do the
model's OWN matmul shapes reach, with no attention, no norms, no
optimizer — i.e. what ceiling is the (embed_dim=1024, mlp_dim=4096)
geometry itself imposing?

Method: a jitted lax.scan chains each matmul N times (output feeds
back), timed at two lengths so a call's fixed cost cancels
(bench.diff_time_scan). FLOPs = 2*M*K*N per matmul.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from bench import diff_time_scan
from cloud_server_tpu.utils.metrics import device_peaks


def matmul_case(m, k, n, note):
    a = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (k, n), jnp.bfloat16)
    wb = jax.random.normal(jax.random.key(2), (n, k), jnp.bfloat16)

    def make(nit):
        def fn(a0):
            def body(x, _):
                y = jnp.dot(x, w, preferred_element_type=jnp.bfloat16)
                # the nonlinearity blocks XLA's (x@w)@wb -> x@(w@wb)
                # associativity rewrite, which would hoist a
                # loop-invariant w@wb and void the FLOP count
                y = jnp.maximum(y, 0)
                x2 = jnp.dot(y, wb, preferred_element_type=jnp.bfloat16)
                return x2, None
            return lax.scan(body, a0, None, length=nit)[0]
        return fn

    sec = diff_time_scan(make, (a,), 20, 120, reps=3)
    flops = 2 * m * k * n + 2 * m * n * k  # the two dots per iteration
    eff = flops / sec / device_peaks().bf16_flops
    print(f"{note}: ({m}x{k})@({k}x{n}) pair {sec * 1e6:.0f} us/iter "
          f"-> {flops / sec / 1e12:.1f} TF/s = {eff:.2f} of peak",
          flush=True)
    return eff


def main():
    m = 8192  # B*S tokens of the bench config
    print("tokens M =", m, flush=True)
    matmul_case(m, 1024, 4096, "mlp up/down (bench model)")
    matmul_case(m, 1024, 1024, "attn qkv/out-ish (bench model)")
    matmul_case(m, 1024, 32000, "unembed (bench model)")
    # the same FLOPs in a wider geometry, for contrast
    matmul_case(m, 4096, 4096, "wide 4096 contrast")
    matmul_case(m, 2048, 8192, "wide 2048x8192 contrast")


if __name__ == "__main__":
    main()
