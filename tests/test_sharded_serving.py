"""Tensor-parallel serving: the inference engine and continuous-batching
server run with params sharded over a tp (and fsdp) mesh, producing
exactly the single-device outputs. No serving-specific sharding code is
needed — params carry NamedShardings, jit propagates them through the
cache and decode loop, and XLA inserts the tp collectives."""

import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import InferConfig, MeshConfig, ModelConfig
from cloud_server_tpu.inference.engine import generate
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import transformer
from cloud_server_tpu.parallel.mesh import make_mesh
from cloud_server_tpu.parallel.sharding import logical_to_sharding

TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=4,
    head_dim=8, mlp_dim=64, max_seq_len=128, dtype="float32",
    param_dtype="float32", remat="none")


def _sharded_params(mesh):
    params = transformer.init_params(TINY, jax.random.key(0))
    shardings = logical_to_sharding(
        transformer.param_logical_axes(TINY), mesh)
    return jax.tree.map(jax.device_put, params, shardings)


def test_engine_generate_tp_sharded_matches_single_device(devices8):
    icfg = InferConfig(max_decode_len=16, temperature=0.0, eos_token_id=-1,
                       pad_token_id=0)
    prompt = jnp.asarray([[3, 7, 11, 2], [9, 1, 4, 8]], jnp.int32)
    want = np.asarray(generate(
        transformer.init_params(TINY, jax.random.key(0)), prompt,
        jax.random.key(1), cfg=TINY, infer_cfg=icfg))

    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    params = _sharded_params(mesh)
    got = generate(params, prompt, jax.random.key(1), cfg=TINY,
                   infer_cfg=icfg)
    # the tp-sharded kv heads force real collectives; outputs must agree
    np.testing.assert_array_equal(np.asarray(got), want)


PAGED_KW = dict(max_slots=2, max_context=64, page_size=8, prefill_chunk=16,
                prompt_buckets=[16])
_ICFG = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                    pad_token_id=0)
_PROMPTS = [[3, 7, 11], [9, 1, 4, 8, 2]]


def _paged_single_device_reference(cfg=TINY, **kw):
    srv = PagedInferenceServer(
        transformer.init_params(TINY, jax.random.key(0)), cfg, _ICFG,
        **PAGED_KW, **kw)
    return srv.generate(_PROMPTS, max_new_tokens=8)


def test_server_tp_sharded_matches_single_device(devices8):
    """Sharded params alone, no `mesh=` handed to the server: jit
    propagates the params' NamedShardings through the dispatches."""
    want = _paged_single_device_reference()
    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    srv = PagedInferenceServer(_sharded_params(mesh), TINY, _ICFG,
                               **PAGED_KW)
    assert srv.generate(_PROMPTS, max_new_tokens=8) == want


def test_paged_server_tp_sharded_matches_single_device(devices8):
    """tp/fsdp-sharded params through the PAGED server (XLA decode
    path): page pools shard on kv heads, outputs match single-device
    exactly — plain and speculative decode."""

    want = _paged_single_device_reference()
    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    params = _sharded_params(mesh)
    srv = PagedInferenceServer(params, TINY, _ICFG, mesh=mesh, **PAGED_KW)
    assert srv.generate(_PROMPTS, max_new_tokens=8) == want

    spec = PagedInferenceServer(params, TINY, _ICFG, mesh=mesh,
                                spec_drafts=2, **PAGED_KW)
    assert spec.generate(_PROMPTS, max_new_tokens=8) == want


def test_paged_server_tp_pallas_kernel_matches(devices8):
    """The pallas paged-attention kernel under shard_map (kv heads over
    tp) matches the single-device kernel path exactly."""
    import dataclasses

    cfg = dataclasses.replace(TINY, decode_attention_impl="pallas")

    want = _paged_single_device_reference(cfg=cfg)
    assert want == _paged_single_device_reference()  # kernel == XLA

    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    params = _sharded_params(mesh)
    srv = PagedInferenceServer(params, cfg, _ICFG, mesh=mesh, **PAGED_KW)
    assert srv.generate(_PROMPTS, max_new_tokens=8) == want


def test_paged_kernel_tp_rejects_indivisible_heads(devices8):
    import dataclasses

    import pytest

    cfg = dataclasses.replace(TINY, num_kv_heads=2, decode_attention_impl="pallas")
    mesh = make_mesh(MeshConfig(fsdp=2, tp=4))
    with pytest.raises(ValueError, match="num_kv_heads"):
        PagedInferenceServer(
            transformer.init_params(cfg, jax.random.key(0)), cfg, _ICFG,
            mesh=mesh, **PAGED_KW)
