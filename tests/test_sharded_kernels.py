"""The pallas kernels under a device mesh: jit cannot partition a compiled
Mosaic kernel, so on TPU `parallel.mesh.kernel_mesh()` routes flash
attention and the fused CE through shard_map wrappers. Here the wrappers
run interpreted on the virtual CPU mesh and must equal the unsharded
kernels, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import MeshConfig
from cloud_server_tpu.ops.flash_attention import (
    flash_attention, flash_attention_sharded)
from cloud_server_tpu.ops.fused_ce import (
    fused_ce_stats, fused_ce_stats_sharded)
from cloud_server_tpu.parallel.mesh import kernel_mesh, make_mesh


def test_kernel_mesh_is_none_off_tpu(devices8):
    """Interpreted kernels partition like any XLA op: no wrapper."""
    make_mesh(MeshConfig(fsdp=4, tp=2))
    assert kernel_mesh() is None


def test_flash_sharded_matches_unsharded(devices8):
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (4, 32, 4, 16))
    k = jax.random.normal(ks[1], (4, 32, 2, 16))
    v = jax.random.normal(ks[2], (4, 32, 2, 16))
    segs = jnp.asarray(np.repeat([[1] * 12 + [2] * 16 + [0] * 4], 4, 0))
    kw = dict(block_q=16, block_kv=16, interpret=True, segment_ids=segs)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * 0.3).sum()

    ref = loss(lambda q, k, v: flash_attention(q, k, v, **kw))
    got = loss(lambda q, k, v: flash_attention_sharded(q, k, v, mesh, **kw))
    np.testing.assert_allclose(jax.jit(got)(q, k, v), ref(q, k, v),
                               rtol=1e-5)
    g_ref = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    g_got = jax.jit(jax.grad(got, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_got, g_ref, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_fused_ce_sharded_matches_unsharded(devices8):
    mesh = make_mesh(MeshConfig(fsdp=2, sp=2, tp=2))
    n, d, v = 8 * 128, 32, 256
    ks = jax.random.split(jax.random.key(1), 5)
    x = jax.random.normal(ks[0], (n, d))
    w = jax.random.normal(ks[1], (d, v)) * 0.1
    t = jax.random.randint(ks[2], (n,), 0, v)
    gz = jax.random.normal(ks[3], (n,))
    gt = jax.random.normal(ks[4], (n,))

    def loss(stats):
        def f(x, w):
            logz, tl, _ = stats(x, w)
            return (logz * gz).sum() + (tl * gt).sum()
        return f

    ref = lambda x, w: fused_ce_stats(x, w, t, True)
    got = lambda x, w: fused_ce_stats_sharded(x, w, t, mesh, True)
    for a, b in zip(jax.jit(got)(x, w), ref(x, w)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    g_ref = jax.grad(loss(ref), argnums=(0, 1))(x, w)
    g_got = jax.jit(jax.grad(loss(got), argnums=(0, 1)))(x, w)
    for a, b, name in zip(g_got, g_ref, ("dx", "dw")):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                   err_msg=name)
