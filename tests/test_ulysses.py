"""Ulysses (all-to-all) sequence parallelism vs dense attention, plus an
end-to-end train step with attention_impl="ulysses"."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.ops.attention import causal_attention
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.parallel.ulysses import ulysses_attention_sharded
from cloud_server_tpu.training import init_train_state, make_train_step


def _rand_qkv(key, b, s, h, kh, d):
    kq, kk, kv = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(kq, (b, s, h, d), jnp.float32),
            jax.random.normal(kk, (b, s, kh, d), jnp.float32),
            jax.random.normal(kv, (b, s, kh, d), jnp.float32))


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ulysses_matches_dense(devices8, sp):
    mesh = make_mesh(MeshConfig(sp=sp))
    q, k, v = _rand_qkv(0, 2, 32, 8, 8, 16)
    got = ulysses_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_gqa_divisible(devices8):
    """KH_local (4) divides sp (4): kv ride the all-to-all directly."""
    mesh = make_mesh(MeshConfig(sp=4))
    q, k, v = _rand_qkv(1, 1, 32, 8, 4, 8)
    got = ulysses_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_gqa_mha_expansion(devices8):
    """KH_local (2) does NOT divide sp (4): the kv repeat fallback."""
    mesh = make_mesh(MeshConfig(sp=4))
    q, k, v = _rand_qkv(2, 1, 32, 8, 2, 8)
    got = ulysses_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_with_tp_and_batch_sharding(devices8):
    mesh = make_mesh(MeshConfig(fsdp=2, sp=2, tp=2))
    q, k, v = _rand_qkv(3, 2, 16, 4, 4, 8)
    got = ulysses_attention_sharded(q, k, v, mesh)
    want = causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_head_count_not_divisible_raises(devices8):
    mesh = make_mesh(MeshConfig(sp=8))
    q, k, v = _rand_qkv(4, 1, 32, 4, 4, 8)  # 4 heads, sp=8
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, k, v, mesh)


def test_ulysses_grads_match_dense(devices8):
    mesh = make_mesh(MeshConfig(sp=4))
    q, k, v = _rand_qkv(5, 1, 16, 4, 2, 8)

    f_u = lambda q, k, v: (ulysses_attention_sharded(q, k, v, mesh) ** 2).sum()
    f_d = lambda q, k, v: (causal_attention(q, k, v) ** 2).sum()
    gu = jax.grad(f_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(f_d, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gu, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"d{n}")


def test_ulysses_train_step_matches_dp_only(devices8):
    """attention_impl="ulysses" on an sp=2 mesh reproduces the dp-only loss
    trajectory — sequence re-sharding must not change the math."""
    cfg_u = ModelConfig(
        vocab_size=64, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=4, head_dim=8, mlp_dim=64, max_seq_len=32,
        dtype="float32", param_dtype="float32", remat="none",
        attention_impl="ulysses")
    cfg_d = ModelConfig(**{**cfg_u.__dict__, "attention_impl": "xla"})
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10)
    tokens = np.asarray(jax.random.randint(jax.random.key(1), (8, 32), 0, 64))

    losses = {}
    for name, cfg, mcfg in (("dp", cfg_d, MeshConfig(fsdp=8)),
                            ("sp", cfg_u, MeshConfig(fsdp=4, sp=2))):
        mesh = make_mesh(mcfg)
        state = init_train_state(cfg, tcfg, mesh, jax.random.key(0))
        step, bsh = make_train_step(cfg, tcfg, mesh)
        data = {"tokens": jax.device_put(tokens, bsh)}
        out = []
        for _ in range(3):
            state, metrics = step(state, data)
            out.append(float(metrics["loss"]))
        losses[name] = out
    np.testing.assert_allclose(losses["sp"], losses["dp"], rtol=1e-5)
