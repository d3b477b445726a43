"""End-to-end sequence parallelism: with sp > 1 the residual stream is
sharded over the sequence dim, so norms/MLP/CE compute S/sp per device
(not just attention). Ring attention handles the cross-chunk part."""

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.training import init_train_state, make_train_step


RING = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=4,
    head_dim=8, mlp_dim=64, max_seq_len=32, dtype="float32",
    param_dtype="float32", remat="none", attention_impl="ring")


def test_activations_sharded_over_sp(devices8):
    """The hidden-state shards must cover S/sp of the sequence per device —
    the r1 gap was a fully replicated S outside the attention shard_map."""
    mesh = make_mesh(MeshConfig(fsdp=4, sp=2))
    params = transformer.init_params(RING, jax.random.key(0))
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (4, 32), 0, 64),
        NamedSharding(mesh, P(("dp", "fsdp"), "sp")))
    fwd = jax.jit(lambda p, t: transformer.forward_hidden(p, t, RING))
    out = fwd(params, tokens)  # (4, 32, 32)
    shard = next(iter(out.addressable_shards))
    assert shard.data.shape == (1, 16, 32), shard.data.shape


def test_sp_loss_and_grads_match_dp_only(devices8):
    """A train step on an sp=2 mesh computes the same loss trajectory as
    the dp-only mesh (sequence sharding must not change the math)."""
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10)
    tokens = np.asarray(
        jax.random.randint(jax.random.key(1), (8, 32), 0, 64))

    losses = {}
    for name, mcfg in (("dp", MeshConfig(fsdp=8)),
                       ("sp", MeshConfig(fsdp=4, sp=2))):
        mesh = make_mesh(mcfg)
        state = init_train_state(RING, tcfg, mesh, jax.random.key(0))
        step, bsh = make_train_step(RING, tcfg, mesh)
        data = {"tokens": jax.device_put(tokens, bsh)}
        out = []
        for _ in range(3):
            state, metrics = step(state, data)
            out.append(float(metrics["loss"]))
        losses[name] = out
    np.testing.assert_allclose(losses["sp"], losses["dp"], rtol=1e-5)


def test_fused_ce_sharded_over_sp(devices8):
    """vocab_chunk > 0 under sp: the blockwise CE consumes the S-sharded
    hidden states without gathering the sequence."""
    import dataclasses
    cfg = dataclasses.replace(RING, vocab_chunk=32)
    tcfg = TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=10)
    mesh = make_mesh(MeshConfig(fsdp=4, sp=2))
    state = init_train_state(cfg, tcfg, mesh, jax.random.key(0))
    step, bsh = make_train_step(cfg, tcfg, mesh)
    tokens = jax.device_put(
        np.asarray(jax.random.randint(jax.random.key(1), (8, 32), 0, 64)),
        bsh)
    state, metrics = step(state, {"tokens": tokens})
    dense_cfg = RING
    mesh2 = make_mesh(MeshConfig(fsdp=8))
    state2 = init_train_state(dense_cfg, tcfg, mesh2, jax.random.key(0))
    step2, bsh2 = make_train_step(dense_cfg, tcfg, mesh2)
    tokens2 = jax.device_put(np.asarray(tokens), bsh2)
    state2, metrics2 = step2(state2, {"tokens": tokens2})
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(metrics2["loss"]), rtol=1e-5)
