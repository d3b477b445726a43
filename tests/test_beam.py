"""Beam search (exact, batched) + sequence embeddings + best_of."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.beam import beam_search
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _ref_beam(params, prompt, k, max_new, eos, pen):
    """Independent reference: the same 2k-candidate algorithm, but one
    full-prompt prefill per beam per step — no shared cache, no batched
    reorder. Slow and obviously correct."""
    def logprobs_of(toks):
        cache = engine.init_cache(CFG, 1, len(toks))
        logits, _ = engine.prefill(
            params, jnp.asarray([toks], jnp.int32), CFG, cache)
        return np.asarray(
            jax.nn.log_softmax(logits[0].astype(jnp.float32)))

    live = [(list(prompt), 0.0)]
    fin = []  # (norm_score, generated tokens)
    for t in range(max_new):
        cands = []
        for toks, cum in live:
            lp = logprobs_of(toks)
            for v in range(len(lp)):
                cands.append((cum + float(lp[v]), toks, v))
        cands.sort(key=lambda c: -c[0])
        top, live = cands[:2 * k], []
        for sc, toks, v in top:
            if v == eos:
                fin.append((sc / (t + 1) ** pen, toks[len(prompt):]))
            elif len(live) < k:
                live.append((toks + [v], sc))
    for toks, cum in live:
        fin.append((cum / max_new ** pen, toks[len(prompt):]))
    fin.sort(key=lambda c: -c[0])
    return fin[:k]


@pytest.mark.parametrize("eos,pen", [(-1, 1.0), (7, 1.0), (7, 0.0)])
def test_beam_matches_reference(params, eos, pen):
    prompt = [5, 9, 3]
    k, max_new = 3, 5
    toks, scores = beam_search(
        params, jnp.asarray([prompt], jnp.int32), cfg=CFG, k=k,
        max_new=max_new, eos_token_id=eos, length_penalty=pen)
    toks, scores = np.asarray(toks)[0], np.asarray(scores)[0]
    ref = _ref_beam(params, prompt, k, max_new, eos, pen)
    np.testing.assert_allclose(scores, [s for s, _ in ref],
                               rtol=1e-4, atol=1e-5)
    best = [int(t) for t in toks[0][:len(ref[0][1])]]
    assert best == ref[0][1], (best, ref[0][1])


def test_beam_batched_prompts_independent(params):
    """Each batch row's beams equal the row run alone."""
    prompts = [[5, 9, 3], [17, 2, 40]]
    both_t, both_s = beam_search(
        params, jnp.asarray(prompts, jnp.int32), cfg=CFG, k=2,
        max_new=4, eos_token_id=-1)
    for i, p in enumerate(prompts):
        one_t, one_s = beam_search(
            params, jnp.asarray([p], jnp.int32), cfg=CFG, k=2,
            max_new=4, eos_token_id=-1)
        np.testing.assert_array_equal(np.asarray(both_t)[i],
                                      np.asarray(one_t)[0])
        np.testing.assert_allclose(np.asarray(both_s)[i],
                                   np.asarray(one_s)[0], rtol=1e-5)


def test_beam_k1_is_greedy(params):
    """Width 1 with no EOS reduces to greedy decoding."""
    prompt = [5, 9, 3]
    icfg = InferConfig(max_decode_len=6, temperature=0.0,
                       eos_token_id=-1, pad_token_id=0)
    greedy = engine.generate(params, jnp.asarray([prompt], jnp.int32),
                             jax.random.key(0), cfg=CFG, infer_cfg=icfg)
    toks, _ = beam_search(params, jnp.asarray([prompt], jnp.int32),
                          cfg=CFG, k=1, max_new=6, eos_token_id=-1)
    np.testing.assert_array_equal(np.asarray(toks)[0, 0],
                                  np.asarray(greedy)[0])


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

SRV_KW = dict(max_slots=2, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32])
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)


def test_embeddings_ragged_match_singles(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    prompts = [[5, 9, 3], [17, 2, 40, 8, 21, 33, 7], [60]]
    batch = srv.embed(prompts)
    assert batch.shape == (3, CFG.embed_dim)
    np.testing.assert_allclose(np.linalg.norm(batch, axis=-1), 1.0,
                               rtol=1e-5)
    for i, p in enumerate(prompts):
        single = srv.embed([p])[0]
        np.testing.assert_allclose(batch[i], single, rtol=1e-4,
                                   atol=1e-5)
    # distinct prompts embed differently
    assert abs(float(batch[0] @ batch[1])) < 0.999


def test_embeddings_over_http(params):
    from urllib import request as urq
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address
        body = json.dumps({"input": [[5, 9, 3], [60]]}).encode()
        with urq.urlopen(urq.Request(
                f"http://{host}:{port}/v1/embeddings", data=body),
                timeout=300) as resp:
            out = json.loads(resp.read())
        assert len(out["data"]) == 2
        vec = np.asarray(out["data"][0]["embedding"])
        assert vec.shape == (CFG.embed_dim,)
        assert abs(np.linalg.norm(vec) - 1.0) < 1e-4
        assert out["usage"]["prompt_tokens"] == 4
    finally:
        front.stop()
        srv.stop()


# ---------------------------------------------------------------------------
# best_of
# ---------------------------------------------------------------------------


def test_best_of_ranks_by_mean_logprob(params):
    """best_of=4, n=1 returns exactly the candidate a client could
    reproduce with derived seeds (seed+k) whose mean token logprob is
    highest — sampling is deterministic in (seed, position), so the
    ranking is checkable bit-for-bit."""
    from urllib import request as urq
    from cloud_server_tpu.inference.http_server import HttpFrontend
    from cloud_server_tpu.inference.sampling import SamplingParams
    icfg = InferConfig(max_decode_len=8, temperature=1.0,
                       eos_token_id=-1, pad_token_id=0)
    srv = PagedInferenceServer(params, CFG, icfg, **SRV_KW).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address
        body = json.dumps({"prompt": [5, 9, 3], "max_tokens": 6,
                           "n": 1, "best_of": 4, "seed": 11}).encode()
        with urq.urlopen(urq.Request(
                f"http://{host}:{port}/v1/completions", data=body),
                timeout=300) as resp:
            out = json.loads(resp.read())
        assert len(out["choices"]) == 1
        got = out["choices"][0]["tokens"]  # no tokenizer attached
        # reproduce the 4 candidates with the derived per-choice seeds
        reqs = [srv.submit([5, 9, 3], max_new_tokens=6,
                           sampling=SamplingParams(seed=11 + k))
                for k in range(4)]
        srv.run_until_idle()
        best = max(reqs,
                   key=lambda r: sum(r.logprobs) / len(r.logprobs))
        assert got == best.tokens
        import urllib.error as uerr
        with pytest.raises(uerr.HTTPError) as ei:  # best_of < n: 400
            urq.urlopen(urq.Request(
                f"http://{host}:{port}/v1/completions",
                data=json.dumps({"prompt": [5], "n": 3,
                                 "best_of": 2}).encode()), timeout=60)
        assert ei.value.code == 400
    finally:
        front.stop()
        srv.stop()


def test_beam_tiny_vocab_rejected(params):
    """2*k > vocab_size breaks the 2k-candidate selection
    (NEG_INF dead-beam candidates get picked, yielding duplicate
    hypotheses silently) — it must be a trace-time ValueError."""
    tiny = ModelConfig(
        vocab_size=6, embed_dim=32, num_layers=1, num_heads=2,
        num_kv_heads=2, head_dim=8, mlp_dim=32, max_seq_len=64,
        dtype="float32", param_dtype="float32", remat="none")
    tiny_params = transformer.init_params(tiny, jax.random.key(0))
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    with pytest.raises(ValueError, match="vocab"):
        beam_search(tiny_params, prompt, cfg=tiny, k=4, max_new=4)
    # at the boundary (2*k == V) the search still runs
    toks, scores = beam_search(tiny_params, prompt, cfg=tiny, k=3,
                               max_new=4)
    assert toks.shape == (1, 3, 4)
