"""Flash attention kernel vs dense XLA reference (pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.ops.attention import causal_attention
from cloud_server_tpu.ops.flash_attention import flash_attention


def _rand_qkv(key, b, s, h, kh, d):
    kq, kk, kv = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, kh, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, kh, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("s,block", [(64, 16), (64, 64), (96, 32)])
def test_forward_matches_dense(s, block):
    q, k, v = _rand_qkv(0, 2, s, 4, 4, 32)
    got = flash_attention(q, k, v, block_q=block, block_kv=block, interpret=True)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_forward_gqa():
    q, k, v = _rand_qkv(1, 2, 64, 8, 2, 16)
    got = flash_attention(q, k, v, block_q=32, block_kv=16, interpret=True)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_backward_matches_dense():
    q, k, v = _rand_qkv(2, 1, 64, 4, 4, 16)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_kv=16,
                                interpret=True) ** 2).sum()

    def f_dense(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        # Blockwise online-softmax accumulates in a different order than the
        # dense path; fp32 round-off alone reaches ~2e-4 on these shapes.
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


def test_backward_gqa():
    q, k, v = _rand_qkv(3, 1, 32, 4, 2, 16)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_kv=16,
                                interpret=True) * 0.3).sum()

    def f_dense(q, k, v):
        return (causal_attention(q, k, v) * 0.3).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-3, err_msg=f"d{name}")


@pytest.mark.on_tpu
def test_compiled_on_tpu():
    """Regression guard for Mosaic lowering: r1's (1, 1, block_q) LSE block
    spec failed to lower on-chip while every interpret-mode test passed."""
    q, k, v = _rand_qkv(4, 2, 512, 8, 4, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(flash_attention)(q, k, v)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v).astype(jnp.float32) ** 2).sum()

    def f_dense(q, k, v):
        return (causal_attention(q, k, v).astype(jnp.float32) ** 2).sum()

    gf = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(f_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.15,
                                   err_msg=f"d{name}")


@pytest.mark.on_tpu
def test_segments_compiled_on_tpu():
    """The segment-mask variant must also lower on-chip (its extra
    (bq,1)/(1,bkv) seg block specs are exactly the shape class that broke
    the r1 LSE spec) — fwd and all three bwd kernels."""
    q, k, v = _rand_qkv(6, 2, 512, 8, 4, 64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    segs = jnp.asarray(
        np.repeat([[1] * 200 + [2] * 250 + [0] * 62], 2, axis=0))
    got = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, segment_ids=segs, block_q=256, block_kv=256))(q, k, v)
    want = causal_attention(q, k, v, segment_ids=segs)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, segment_ids=segs, block_q=256,
                                block_kv=256).astype(jnp.float32) ** 2).sum()

    def f_dense(q, k, v):
        return (causal_attention(q, k, v, segment_ids=segs
                                 ).astype(jnp.float32) ** 2).sum()

    gf = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(f_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.15,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2)])
def test_backward_fused_single_block(h, kh):
    """S <= block takes the fused one-pass dq/dk/dv kernel; it must match
    dense exactly like the blocked two-kernel path does."""
    q, k, v = _rand_qkv(5, 2, 64, h, kh, 16)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=64, block_kv=64,
                                interpret=True) ** 2).sum()

    def f_dense(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=1e-3, err_msg=f"d{name}")
