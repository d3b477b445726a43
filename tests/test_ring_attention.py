"""Ring attention over an sp-sharded virtual mesh vs dense attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import MeshConfig
from cloud_server_tpu.ops.attention import causal_attention
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.parallel.ring_attention import ring_attention_sharded


def _rand_qkv(key, b, s, h, kh, d):
    kq, kk, kv = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(kq, (b, s, h, d), jnp.float32),
            jax.random.normal(kk, (b, s, kh, d), jnp.float32),
            jax.random.normal(kv, (b, s, kh, d), jnp.float32))


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_matches_dense(devices8, sp):
    mesh = make_mesh(MeshConfig(sp=sp))
    q, k, v = _rand_qkv(0, 2, 32, 4, 4, 16)
    got = ring_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_gqa(devices8):
    mesh = make_mesh(MeshConfig(sp=4))
    q, k, v = _rand_qkv(1, 1, 32, 8, 2, 8)
    got = ring_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_with_tp_and_batch_sharding(devices8):
    mesh = make_mesh(MeshConfig(fsdp=2, sp=2, tp=2))
    q, k, v = _rand_qkv(2, 2, 16, 4, 4, 8)
    got = ring_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ring_grads_match_dense(devices8):
    mesh = make_mesh(MeshConfig(sp=4))
    q, k, v = _rand_qkv(3, 1, 16, 2, 2, 8)

    f_ring = lambda q, k, v: (ring_attention_sharded(q, k, v, mesh) ** 2).sum()
    f_dense = lambda q, k, v: (causal_attention(q, k, v) ** 2).sum()
    gr = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"d{n}")
