"""Packed sequences through the parallel paths: ring/ulysses sp-sharded
attention with segment masks, and the pipelined packed loss — the combos
that used to raise (transformer._packed_attention_fn / pipeline loss
guards)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import MeshConfig, ModelConfig, TrainConfig
from cloud_server_tpu.data.packing import pack_documents
from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel.mesh import make_mesh


TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=4,
    head_dim=8, mlp_dim=64, max_seq_len=64, dtype="float32",
    param_dtype="float32", remat="none")


def _packed_batch(seq_len=32, rows=4, seed=0):
    rng = np.random.RandomState(seed)
    rows_toks, rows_segs = [], []
    for r in range(rows):
        docs = [list(rng.randint(1, 60, rng.randint(4, 12)))
                for _ in range(3)]
        t, s = pack_documents(docs, seq_len)
        rows_toks.append(np.asarray(t)[0])
        rows_segs.append(np.asarray(s)[0])
    return {"tokens": jnp.asarray(np.stack(rows_toks)),
            "segment_ids": jnp.asarray(np.stack(rows_segs))}


@pytest.mark.parametrize("impl,sp", [("ring", 2), ("ring", 4),
                                     ("ulysses", 2), ("ulysses", 4)])
def test_sp_packed_loss_matches_single_device(devices8, impl, sp):
    """Packed loss under sp-sharded ring/ulysses attention == the
    single-device XLA packed loss, gradients included."""
    batch = _packed_batch()
    params = transformer.init_params(TINY, jax.random.key(0))

    ref_loss, _ = transformer.next_token_loss(params, batch, TINY)
    ref_grad = jax.grad(
        lambda p: transformer.next_token_loss(p, batch, TINY)[0])(params)

    cfg = dataclasses.replace(TINY, attention_impl=impl)
    mesh = make_mesh(MeshConfig(sp=sp))
    with mesh:
        from cloud_server_tpu.parallel.mesh import set_current_mesh
        set_current_mesh(mesh)
        loss, _ = jax.jit(
            lambda p, b: transformer.next_token_loss(p, b, cfg))(params,
                                                                 batch)
        grad = jax.jit(jax.grad(
            lambda p, b: transformer.next_token_loss(p, b, cfg)[0]))(
                params, batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-5)
    for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(ref_grad)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_sp_packed_grads_nonzero_cross_chunk(devices8):
    """A document spanning an sp chunk boundary still attends across it
    (the rotating segment mask must not sever in-document attention)."""
    # one long document filling the row: every position same segment
    toks = jnp.asarray([[(i * 7) % 60 + 1 for i in range(32)]] * 4)
    seg = jnp.ones((4, 32), jnp.int32)
    batch = {"tokens": toks, "segment_ids": seg}
    params = transformer.init_params(TINY, jax.random.key(0))
    ref_loss, _ = transformer.next_token_loss(params, batch, TINY)
    cfg = dataclasses.replace(TINY, attention_impl="ring")
    mesh = make_mesh(MeshConfig(sp=4))
    with mesh:
        from cloud_server_tpu.parallel.mesh import set_current_mesh
        set_current_mesh(mesh)
        loss, _ = jax.jit(
            lambda p, b: transformer.next_token_loss(p, b, cfg))(params,
                                                                 batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-5)


def test_pipelined_packed_loss_matches_plain(devices8):
    """The pipelined loss accepts packed batches and matches the
    unpipelined packed loss (the old ValueError guard is gone)."""
    from cloud_server_tpu.parallel.pipeline import make_pipelined_loss

    batch = _packed_batch()
    params = transformer.init_params(TINY, jax.random.key(0))
    want, _ = transformer.next_token_loss(params, batch, TINY)

    mesh = make_mesh(MeshConfig(pp=2, fsdp=2))
    loss_fn = make_pipelined_loss(TINY, mesh, num_microbatches=2)
    with mesh:
        got, _ = jax.jit(lambda p, b: loss_fn(p, b, TINY))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=2e-5)


def test_pipelined_packed_grads_match(devices8):
    from cloud_server_tpu.parallel.pipeline import make_pipelined_loss

    batch = _packed_batch(seed=3)
    params = transformer.init_params(TINY, jax.random.key(1))
    ref = jax.grad(
        lambda p: transformer.next_token_loss(p, batch, TINY)[0])(params)

    mesh = make_mesh(MeshConfig(pp=2, fsdp=2))
    loss_fn = make_pipelined_loss(TINY, mesh, num_microbatches=2)
    with mesh:
        got = jax.jit(jax.grad(
            lambda p, b: loss_fn(p, b, TINY)[0]))(params, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_pipelined_packed_moe(devices8):
    """MoE pipeline with packed batches (segment ids + positions ride the
    ring next to the router stats)."""
    from cloud_server_tpu.models import moe
    from cloud_server_tpu.parallel.pipeline import make_pipelined_loss

    cfg = dataclasses.replace(TINY, num_experts=4, num_experts_per_token=2,
                              expert_capacity_factor=4.0)
    batch = _packed_batch(seed=5)
    params = moe.init_params(cfg, jax.random.key(0))
    want, _ = moe.next_token_loss(params, batch, cfg, aux_loss_coef=0.0)

    mesh = make_mesh(MeshConfig(pp=2, fsdp=2))
    loss_fn = make_pipelined_loss(cfg, mesh, num_microbatches=2,
                                  loss_fn_module=moe, aux_loss_coef=0.0)
    with mesh:
        got, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=2e-4)
