import jax
import jax.numpy as jnp
import numpy as np

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import generate, init_cache, prefill
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.engine import decode_step
from cloud_server_tpu.inference.sampling import sample_logits
from cloud_server_tpu.models import transformer

TINY = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=64, dtype="float32",
    param_dtype="float32", remat="none")


def _params():
    return transformer.init_params(TINY, jax.random.key(0))


def test_prefill_then_decode_matches_full_forward():
    """Teacher-forced cache decode must reproduce the training forward."""
    params = _params()
    tokens = jax.random.randint(jax.random.key(1), (2, 12), 0, TINY.vocab_size)
    p = 6
    full_logits = transformer.forward(params, tokens, TINY)  # (B, S, V)

    cache = init_cache(TINY, 2, 16)
    logits, cache = prefill(params, tokens[:, :p], TINY, cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, p - 1]), atol=2e-5)
    for t in range(p, 12):
        logits, cache = decode_step(params, tokens[:, t], TINY, cache)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]), atol=3e-5,
            err_msg=f"step {t}")


def test_greedy_generate_matches_naive_rollout():
    params = _params()
    prompt = jax.random.randint(jax.random.key(2), (2, 4), 0, TINY.vocab_size)
    icfg = InferConfig(max_decode_len=6, temperature=0.0)
    got = generate(params, prompt, jax.random.key(0), cfg=TINY,
                   infer_cfg=icfg)

    # naive: repeatedly run the full forward and take argmax
    seq = prompt
    naive = []
    for _ in range(6):
        logits = transformer.forward(params, seq, TINY)[:, -1]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        naive.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    naive = jnp.stack(naive, axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(naive))


def test_eos_freezes_sequence_to_pad():
    params = _params()
    prompt = jnp.zeros((1, 4), jnp.int32)
    icfg0 = InferConfig(max_decode_len=8, temperature=0.0)
    base = np.asarray(generate(params, prompt, jax.random.key(0), cfg=TINY,
                               infer_cfg=icfg0))
    # declare the first generated token to be "eos"; everything after must
    # be pad (and the eos itself is emitted)
    eos = int(base[0, 0])
    icfg = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=eos,
                       pad_token_id=63)
    out = np.asarray(generate(params, prompt, jax.random.key(0), cfg=TINY,
                              infer_cfg=icfg))
    assert out[0, 0] == eos
    assert np.all(out[0, 1:] == 63)


def test_topk1_equals_greedy():
    logits = jax.random.normal(jax.random.key(0), (4, 64))
    greedy = sample_logits(logits, jax.random.key(1),
                           InferConfig(temperature=0.0))
    topk1 = sample_logits(logits, jax.random.key(1),
                          InferConfig(temperature=1.0, top_k=1))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(topk1))


def test_top_p_keeps_minimum_one_token():
    logits = jnp.array([[10.0, 0.0, -10.0, -10.0]])
    tok = sample_logits(logits, jax.random.key(0),
                        InferConfig(temperature=1.0, top_p=0.01))
    assert int(tok[0]) == 0


def test_top_p_zero_degrades_to_top_token():
    """top_p <= 0 must keep the argmax, not mask the entire vocab."""
    logits = jnp.array([[10.0, 0.0, -10.0, -10.0]])
    for p in (0.0, -1.0):
        tok = sample_logits(logits, jax.random.key(0),
                            InferConfig(temperature=1.0, top_p=p))
        assert int(tok[0]) == 0


def test_ragged_prefill_decode_matches_unpadded():
    """Right-padded ragged batch must match each prompt run unpadded."""
    params = _params()
    lens = [3, 6]
    p = max(lens)
    tokens = jax.random.randint(jax.random.key(7), (2, p), 1, TINY.vocab_size)
    lengths = jnp.array(lens, jnp.int32)
    padded = tokens * (jnp.arange(p)[None, :] < lengths[:, None])

    cache = init_cache(TINY, 2, 16)
    logits, cache = prefill(params, padded, TINY, cache, lengths)
    # decode 4 greedy steps on the ragged batch
    ragged_out = []
    for _ in range(4):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ragged_out.append(tok)
        logits, cache = decode_step(params, tok, TINY, cache)

    # reference: each sequence alone, unpadded
    for i, ln in enumerate(lens):
        c = init_cache(TINY, 1, 16)
        lg, c = prefill(params, tokens[i:i + 1, :ln], TINY, c)
        for t in range(4):
            tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            assert int(tok[0]) == int(ragged_out[t][i]), (
                f"seq {i} diverged at decode step {t}")
            lg, c = decode_step(params, tok, TINY, c)


def test_sampling_distribution_respects_top_k():
    logits = jnp.array([[0.0, 0.1, 0.2, 5.0]])
    cfg = InferConfig(temperature=1.0, top_k=2)
    toks = [int(sample_logits(logits, jax.random.key(i), cfg)[0])
            for i in range(20)]
    assert set(toks) <= {2, 3}


def test_moe_prefill_decode_matches_full_forward():
    """MoE teacher-forced cache decode reproduces the MoE training forward
    (generous capacity so routing is batch-composition independent)."""
    from cloud_server_tpu.models import moe

    cfg = ModelConfig(
        vocab_size=64, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=32,
        dtype="float32", param_dtype="float32", remat="none", num_experts=4,
        num_experts_per_token=2, expert_capacity_factor=8.0)
    params = moe.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 10), 0, 64)

    full_logits, _ = moe.forward(params, tokens, cfg)
    cache = engine.init_cache(cfg, 2, 16)
    logits, cache = engine.prefill(params, tokens[:, :4], cfg, cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full_logits[:, 3]), atol=2e-4)
    for t in range(4, 10):
        logits, cache = engine.decode_step(params, tokens[:, t], cfg, cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits[:, t]), atol=3e-4)


def test_moe_server_generates(devices8):
    """The continuous-batching server serves the MoE family end-to-end."""
    from cloud_server_tpu.inference.paged_server import (
        PagedInferenceServer)
    from cloud_server_tpu.models import moe

    cfg = ModelConfig(
        vocab_size=64, embed_dim=32, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=8, mlp_dim=64, max_seq_len=64,
        dtype="float32", param_dtype="float32", remat="none", num_experts=4,
        num_experts_per_token=2, expert_capacity_factor=8.0)
    params = moe.init_params(cfg, jax.random.key(0))
    icfg = InferConfig(max_decode_len=6, temperature=0.0, eos_token_id=-1,
                       pad_token_id=0)
    srv = PagedInferenceServer(params, cfg, icfg, max_slots=2,
                               max_context=32, page_size=8,
                               prefill_chunk=8, prompt_buckets=[8])
    outs = srv.generate([[5, 9, 3], [17, 2]], max_new_tokens=6)
    # greedy reference from the batch engine
    for prompt, out in zip([[5, 9, 3], [17, 2]], outs):
        ref = engine.generate(
            params, np.asarray([prompt], np.int32), jax.random.key(1),
            cfg=cfg, infer_cfg=icfg)
        assert out == list(np.asarray(ref)[0]), prompt
