"""Fused pallas cross-entropy: stats + gradient parity with the dense
path (interpret mode on CPU; same kernels run compiled on TPU)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import ModelConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.ops.fused_ce import fused_ce_stats

CFG = ModelConfig(
    vocab_size=512, embed_dim=64, num_layers=2, num_heads=4,
    num_kv_heads=4, head_dim=16, mlp_dim=128, max_seq_len=128,
    dtype="float32", param_dtype="float32", remat="none")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def test_stats_match_dense():
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    n, d, v = 256, 64, 512
    x = jax.random.normal(k1, (n, d), jnp.float32)
    w = jax.random.normal(k2, (d, v), jnp.float32) * 0.05
    t = jax.random.randint(k3, (n,), 0, v)
    logz, tl, am = fused_ce_stats(x, w, t)
    logits = x @ w
    np.testing.assert_allclose(np.asarray(logz),
                               np.asarray(jax.nn.logsumexp(logits, -1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tl),
        np.asarray(logits[jnp.arange(n), t]), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(am),
                                  np.asarray(logits.argmax(-1)))


def test_grads_match_dense():
    k1, k2, k3, k4 = jax.random.split(jax.random.key(2), 4)
    n, d, v = 128, 64, 384
    x = jax.random.normal(k1, (n, d), jnp.float32)
    w = jax.random.normal(k2, (d, v), jnp.float32) * 0.05
    t = jax.random.randint(k3, (n,), 0, v)
    gz = jax.random.normal(k4, (n,), jnp.float32)
    gt = jax.random.normal(jax.random.key(5), (n,), jnp.float32)

    def fused(x, w):
        logz, tl, _ = fused_ce_stats(x, w, t)
        return (logz * gz).sum() + (tl * gt).sum()

    def dense(x, w):
        logits = x @ w
        logz = jax.nn.logsumexp(logits, -1)
        tl = logits[jnp.arange(n), t]
        return (logz * gz).sum() + (tl * gt).sum()

    gxf, gwf = jax.grad(fused, argnums=(0, 1))(x, w)
    gxd, gwd = jax.grad(dense, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gxf), np.asarray(gxd),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gwf), np.asarray(gwd),
                               rtol=2e-4, atol=2e-5)


def test_loss_path_matches_dense(params):
    """next_token_loss with ce_impl='pallas' equals the dense path —
    loss, metrics, AND parameter gradients (f32 model: tight)."""
    cfg_p = dataclasses.replace(CFG, ce_impl="pallas")
    tokens = jax.random.randint(jax.random.key(3), (2, 64), 0,
                                CFG.vocab_size)
    mask = jnp.ones((2, 64), jnp.float32).at[1, 40:].set(0.0)
    batch = {"tokens": tokens, "mask": mask}

    ld, md = transformer.next_token_loss(params, batch, CFG)
    lp, mp = transformer.next_token_loss(params, batch, cfg_p)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)
    np.testing.assert_allclose(float(mp["accuracy"]),
                               float(md["accuracy"]), rtol=1e-6)

    gd = jax.grad(lambda p: transformer.next_token_loss(p, batch,
                                                        CFG)[0])(params)
    gp = jax.grad(lambda p: transformer.next_token_loss(p, batch,
                                                        cfg_p)[0])(params)
    for leaf_d, leaf_p in zip(jax.tree.leaves(gd), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(leaf_p),
                                   np.asarray(leaf_d),
                                   rtol=5e-4, atol=1e-5)


def test_zloss_and_tied_embeddings(params):
    cfg_p = dataclasses.replace(CFG, ce_impl="pallas")
    tokens = jax.random.randint(jax.random.key(7), (2, 64), 0,
                                CFG.vocab_size)
    batch = {"tokens": tokens}
    ld, md = transformer.next_token_loss(params, batch, CFG,
                                         z_loss_coef=1e-3)
    lp, mp = transformer.next_token_loss(params, batch, cfg_p,
                                         z_loss_coef=1e-3)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)
    np.testing.assert_allclose(float(mp["z_loss"]), float(md["z_loss"]),
                               rtol=1e-5)


def test_config_validation():
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, ce_impl="nope")
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, ce_impl="pallas", logits_softcap=30.0)
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, ce_impl="pallas", vocab_chunk=512)
    with pytest.raises(ValueError):  # indivisible vocab
        fused_ce_stats(jnp.zeros((128, 8)), jnp.zeros((8, 100)),
                       jnp.zeros((128,), jnp.int32))


def test_moe_loss_honors_pallas_ce():
    """ce_impl='pallas' must not be silently ignored by the MoE loss."""
    from cloud_server_tpu.models import moe
    cfg = dataclasses.replace(CFG, num_experts=4,
                              expert_capacity_factor=4.0)
    cfg_p = dataclasses.replace(cfg, ce_impl="pallas")
    params = moe.init_params(cfg, jax.random.key(1))
    tokens = jax.random.randint(jax.random.key(4), (2, 64), 0,
                                cfg.vocab_size)
    ld, _ = moe.next_token_loss(params, {"tokens": tokens}, cfg)
    lp, _ = moe.next_token_loss(params, {"tokens": tokens}, cfg_p)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)


def test_pipeline_loss_honors_pallas_ce():
    from cloud_server_tpu.config import MeshConfig
    from cloud_server_tpu.parallel.mesh import make_mesh
    from cloud_server_tpu.parallel.pipeline import make_pipelined_loss
    if len(jax.devices()) < 2:
        pytest.skip("needs a 2-device mesh")
    cfg_p = dataclasses.replace(CFG, ce_impl="pallas")
    mesh = make_mesh(MeshConfig(pp=2))
    params = transformer.init_params(CFG, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(5), (2, 64), 0,
                                CFG.vocab_size)
    dense_fn = make_pipelined_loss(CFG, mesh, num_microbatches=2)
    pallas_fn = make_pipelined_loss(cfg_p, mesh, num_microbatches=2)
    ld, _ = dense_fn(params, {"tokens": tokens}, CFG)
    lp, _ = pallas_fn(params, {"tokens": tokens}, cfg_p)
    np.testing.assert_allclose(float(lp), float(ld), rtol=1e-5)


@pytest.mark.on_tpu
def test_compiled_on_tpu_llama_vocab():
    """Both kernels compiled by Mosaic at the Llama-3.2-1B head: hidden
    2,048, vocabulary 128,256 (vocab tile 768 — the largest 128-multiple
    under 3,200 that divides it), bf16, forward and backward against the
    dense f32 computation of the same bf16 operands."""
    n, d, v = 256, 2048, 128256
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(k1, (n, d), jnp.bfloat16)
    w = (jax.random.normal(k2, (d, v), jnp.float32) * 0.02).astype(
        jnp.bfloat16)
    t = jax.random.randint(k3, (n,), 0, v)
    gz = jax.random.normal(k4, (n,), jnp.float32)
    gt = jax.random.normal(k5, (n,), jnp.float32)

    def dense_stats(x, w):
        logits = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))
        return (jax.nn.logsumexp(logits, -1), logits[jnp.arange(n), t],
                logits)

    logz, tl, am = jax.jit(lambda x, w: fused_ce_stats(x, w, t, False))(x, w)
    want_z, want_t, logits = jax.jit(dense_stats)(x, w)
    np.testing.assert_allclose(np.asarray(logz), np.asarray(want_z),
                               atol=2e-2, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(tl), np.asarray(want_t),
                               atol=2e-2, rtol=1e-3)
    # the argmax may differ only where bf16 MXU rounding reorders a
    # near-tie: the chosen column's logit must equal the row maximum
    picked = np.asarray(logits)[np.arange(n), np.asarray(am)]
    np.testing.assert_allclose(picked, np.asarray(logits).max(-1),
                               atol=2e-2)

    def fused(x, w):
        logz, tl, _ = fused_ce_stats(x, w, t, False)
        return (logz * gz).sum() + (tl * gt).sum()

    def dense(x, w):
        logz, tl, _ = dense_stats(x, w)
        return (logz * gz).sum() + (tl * gt).sum()

    gf = jax.jit(jax.grad(fused, argnums=(0, 1)))(x, w)
    gd = jax.jit(jax.grad(dense, argnums=(0, 1)))(x, w)
    for a, b, name in zip(gf, gd, ("dx", "dw")):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        # d_logits is cast to bf16 before the gradient matmuls (module
        # docstring), so agreement is at bf16 resolution of the
        # gradient's own scale
        np.testing.assert_allclose(a, b, atol=2e-2 * np.abs(b).max(),
                                   err_msg=name)
