"""Int8 KV cache: quantization accuracy, engine/server paths, guards."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.engine import (
    _kv_dequant, _kv_quant, generate, init_cache, prefill)
from cloud_server_tpu.models import transformer

BASE = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=128, dtype="float32",
    param_dtype="float32", remat="none")
INT8 = dataclasses.replace(BASE, kv_cache_dtype="int8")


def test_quant_roundtrip_error_small():
    x = jax.random.normal(jax.random.key(0), (4, 16, 2, 8), jnp.float32)
    q, s = _kv_quant(x)
    back = _kv_dequant(q, s, jnp.float32)
    # symmetric absmax int8: worst-case per-element error is scale/2
    assert float(jnp.abs(back - x).max()) <= float(s.max()) / 2 + 1e-6
    rel = float(jnp.abs(back - x).max() / jnp.abs(x).max())
    assert rel < 0.01


def test_init_cache_dtypes():
    cache = init_cache(INT8, 2, 16)
    assert cache.k.dtype == jnp.int8 and cache.v.dtype == jnp.int8
    assert cache.k_scale.shape == (2, 2, 16, 2, 1)
    plain = init_cache(BASE, 2, 16)
    assert plain.k_scale is None
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        init_cache(dataclasses.replace(BASE, kv_cache_dtype="fp4"), 2, 16)


def test_prefill_decode_logits_close():
    """Prefill + one decode step with the int8 cache tracks the exact
    path closely (per-head absmax keeps error ~1%)."""
    params = transformer.init_params(BASE, jax.random.key(0))
    tokens = jnp.asarray([[5, 9, 3, 17, 6, 2, 40, 8]], jnp.int32)

    outs = {}
    for name, cfg in (("fp", BASE), ("int8", INT8)):
        cache = init_cache(cfg, 1, 32)
        logits, cache = prefill(params, tokens, cfg, cache)
        outs[f"{name}_prefill"] = np.asarray(logits)
        step_logits, _ = engine.decode_step(
            params, jnp.asarray([7], jnp.int32), cfg, cache)
        outs[f"{name}_decode"] = np.asarray(step_logits)

    # prefill logits don't read the cache => must be identical
    np.testing.assert_allclose(outs["int8_prefill"], outs["fp_prefill"],
                               atol=1e-5)
    np.testing.assert_allclose(outs["int8_decode"], outs["fp_decode"],
                               atol=0.05)


def test_generate_greedy_matches_fp():
    """On a tiny model the quantization error shouldn't flip greedy
    argmaxes over a short horizon."""
    params = transformer.init_params(BASE, jax.random.key(0))
    icfg = InferConfig(max_decode_len=12, temperature=0.0, eos_token_id=-1,
                       pad_token_id=0)
    prompt = jnp.asarray([[3, 7, 11, 2]], jnp.int32)
    want = np.asarray(generate(params, prompt, jax.random.key(1), cfg=BASE,
                               infer_cfg=icfg))
    got = np.asarray(generate(params, prompt, jax.random.key(1), cfg=INT8,
                              infer_cfg=icfg))
    np.testing.assert_array_equal(got, want)


def test_server_int8_cache_runs():
    from cloud_server_tpu.inference.paged_server import (
        PagedInferenceServer)

    params = transformer.init_params(BASE, jax.random.key(0))
    icfg = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                       pad_token_id=0)
    kw = dict(max_slots=2, max_context=32, page_size=8, prefill_chunk=8,
              prompt_buckets=[8])
    srv_fp = PagedInferenceServer(params, BASE, icfg, **kw)
    want = srv_fp.generate([[3, 7, 11], [9, 1, 4, 8]], max_new_tokens=8)
    srv = PagedInferenceServer(params, INT8, icfg, **kw)
    got = srv.generate([[3, 7, 11], [9, 1, 4, 8]], max_new_tokens=8)
    assert got == want


def test_speculative_with_int8_cache(devices8):
    from cloud_server_tpu.inference.speculative import speculative_generate

    params = transformer.init_params(BASE, jax.random.key(0))
    icfg = InferConfig(max_decode_len=10, temperature=0.0, eos_token_id=-1,
                       pad_token_id=0)
    prompt = jnp.asarray([[3, 7, 11, 2]], jnp.int32)
    want = np.asarray(generate(params, prompt, jax.random.key(1), cfg=BASE,
                               infer_cfg=icfg))
    got = np.asarray(speculative_generate(
        params, params, prompt, jax.random.key(2), cfg=INT8,
        draft_cfg=INT8, infer_cfg=icfg, num_draft=3))
    np.testing.assert_array_equal(got, want)
