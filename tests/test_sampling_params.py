"""Per-request sampling: SamplingParams rows through the server.

Covers the filter chain units (top-k/top-p/min-p/penalties), per-request
seed reproducibility across batch compositions, mixed greedy/sampled
batches, stop sequences / ignore_eos (host side), and — the delicate
one — penalty EXACTNESS through in-server speculative decoding (greedy
+ repetition penalty must match the non-speculative server token for
token, which only holds if the verify window applies cumulative counts
position by position).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.sampling import (
    SamplingParams, filtered_logits_rows, make_rows,
    sample_logits_rows, sampling_probs, sampling_probs_rows)
from cloud_server_tpu.inference.request import Request, emit_token
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
SAMPLED = dataclasses.replace(GREEDY, temperature=1.0)

PAGED_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
                prompt_buckets=[16, 32])


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


# ---------------------------------------------------------------------------
# unit: filter chain
# ---------------------------------------------------------------------------


def test_rows_match_global_filter():
    """With rows equal to the InferConfig, the rows chain reproduces the
    global chain's probabilities exactly (shared source of truth)."""
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 64)),
                         jnp.float32)
    cfg = dataclasses.replace(SAMPLED, temperature=0.7, top_k=5, top_p=0.9)
    rows = make_rows([None] * 3, cfg, [0, 0, 0])
    got = sampling_probs_rows(logits, rows)
    want = sampling_probs(logits, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


def test_top_k_one_is_greedy():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(2, 64)),
                         jnp.float32)
    rows = make_rows([SamplingParams(temperature=5.0, top_k=1)] * 2,
                     SAMPLED, [7, 8])
    toks = sample_logits_rows(logits, rows, jnp.asarray([3, 4]))
    np.testing.assert_array_equal(np.asarray(toks),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_min_p_masks_tail():
    """min_p keeps exactly the tokens with prob >= min_p * p_max."""
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.2, 0.05]], jnp.float32))
    rows = make_rows([SamplingParams(temperature=1.0, min_p=0.3)],
                     SAMPLED, [0])
    filt, _ = filtered_logits_rows(logits, rows)
    kept = np.asarray(filt[0]) > -1e29
    # p_max = 0.5 -> threshold 0.15: tokens 0, 1, 2 stay, 3 masked
    np.testing.assert_array_equal(kept, [True, True, True, False])


def test_penalties_adjust_logits():
    """Presence/frequency hit generated counts; repetition also hits
    prompt tokens; untouched tokens keep their logits."""
    logits = jnp.asarray([[1.0, -1.0, 2.0, 0.5]], jnp.float32)
    rows = make_rows(
        [SamplingParams(temperature=1.0, repetition_penalty=2.0,
                        presence_penalty=0.25, frequency_penalty=0.5)],
        SAMPLED, [0])
    prompt_mask = jnp.asarray([[False, True, False, False]])
    out_counts = jnp.asarray([[0, 0, 3, 0]], jnp.int32)
    _, raw = filtered_logits_rows(logits, rows, prompt_mask=prompt_mask,
                                  out_counts=out_counts)
    raw = np.asarray(raw[0])
    # token 1: prompt-only -> repetition penalty on negative: * 2
    assert raw[1] == pytest.approx(-2.0)
    # token 2: generated 3x -> vLLM order: 2.0 / 2 = 1.0 (repetition
    # first, on the raw logit), then - .25 - 1.5 = -0.75
    assert raw[2] == pytest.approx(-0.75)
    # tokens 0, 3: untouched
    assert raw[0] == pytest.approx(1.0)
    assert raw[3] == pytest.approx(0.5)


def test_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(repetition_penalty=0.0)
    with pytest.raises(ValueError):
        SamplingParams(stop=((),))


# ---------------------------------------------------------------------------
# unit: host-side emit rule (stop sequences / ignore_eos)
# ---------------------------------------------------------------------------


def _req(max_new_tokens=16, **kw):
    return Request(prompt=[1], max_new_tokens=max_new_tokens, **kw)


def test_stop_sequence_truncates():
    req = _req(sampling=SamplingParams(stop=((7, 8),)))
    for t in (5, 7):
        assert not emit_token(req, t, -1.0, GREEDY)
    assert emit_token(req, 8, -1.0, GREEDY)
    assert req.finish_reason == "stop"
    assert req.tokens == [5]          # the match is removed
    assert len(req.logprobs) == 1


def test_stop_truncation_keeps_partial_logprobs_aligned():
    """When logprobs cover only a prefix of the tokens (logprob=None
    path), a stop-sequence match must not strip entries belonging to
    KEPT tokens."""
    req = _req(sampling=SamplingParams(stop=((7, 8),)))
    assert not emit_token(req, 5, -1.0, GREEDY)   # has a logprob
    assert not emit_token(req, 7, None, GREEDY)   # no logprob recorded
    assert emit_token(req, 8, None, GREEDY)
    assert req.tokens == [5]
    assert req.logprobs == [-1.0]  # the kept token's entry survives


def test_ignore_eos_runs_to_length():
    cfg = dataclasses.replace(GREEDY, eos_token_id=9)
    req = _req(max_new_tokens=2, sampling=SamplingParams(ignore_eos=True))
    assert not emit_token(req, 9, -1.0, cfg)
    assert emit_token(req, 9, -1.0, cfg)
    assert req.finish_reason == "length"
    assert req.tokens == [9, 9]


# ---------------------------------------------------------------------------
# servers: mixed batches, seeds, penalties
# ---------------------------------------------------------------------------

PROMPTS = [[5, 9, 3], [17, 2, 40, 8, 21], [60], list(range(1, 14))]


def _greedy_ref(params, prompt, n_new, **kw):
    srv = PagedInferenceServer(params, CFG, GREEDY, **kw)
    return srv.generate([prompt], max_new_tokens=n_new)[0]


def test_mixed_greedy_and_sampled_batch(params):
    """Greedy rows inside a sampled batch still match the pure-greedy
    reference (per-row temperature routing)."""
    srv = PagedInferenceServer(params, CFG, SAMPLED, **PAGED_KW)
    ref = _greedy_ref(params, PROMPTS[0], 8, **PAGED_KW)
    r_greedy = srv.submit(PROMPTS[0], max_new_tokens=8,
                          sampling=SamplingParams(temperature=0.0))
    r_hot = srv.submit(PROMPTS[1], max_new_tokens=8,
                       sampling=SamplingParams(temperature=1.5, seed=3))
    srv.run_until_idle()
    assert r_greedy.result() == ref
    assert len(r_hot.result()) == 8


def test_seed_reproducible_across_batch_compositions(params):
    """A seeded request's stream does not depend on its batch mates or
    slot placement."""
    def run(extra_first):
        srv = PagedInferenceServer(params, CFG, SAMPLED, seed=123,
                                   **PAGED_KW)
        if extra_first:  # occupy slot 0 with an unrelated request
            srv.submit(PROMPTS[3], max_new_tokens=8,
                       sampling=SamplingParams(temperature=1.0, seed=999))
        r = srv.submit(PROMPTS[1], max_new_tokens=8,
                       sampling=SamplingParams(temperature=1.0, seed=42))
        srv.run_until_idle()
        return r.result()

    alone = run(False)
    batched = run(True)
    assert alone == batched
    assert len(alone) == 8


def test_repetition_penalty_breaks_loops(params):
    """Greedy decoding with a strong repetition penalty cannot emit the
    same token twice (V=64 toy model loops hard without it)."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    pen = srv.submit(PROMPTS[2], max_new_tokens=12,
                     sampling=SamplingParams(repetition_penalty=1e9))
    srv.run_until_idle()
    toks = pen.result()
    assert len(set(toks)) == len(toks), toks  # no repeats at all
    assert PROMPTS[2][0] not in toks  # prompt tokens are penalised too


@pytest.mark.parametrize("spec_drafts", [2, 3])
def test_spec_decoding_exact_with_penalties(params, spec_drafts):
    """THE exactness check: greedy + repetition penalty through the
    speculative paged server matches the plain paged server token for
    token. Only true if verification applies counts cumulatively inside
    the (G+1) window."""
    sp = SamplingParams(repetition_penalty=3.0, presence_penalty=0.1)
    plain = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    spec = PagedInferenceServer(params, CFG, GREEDY,
                                spec_drafts=spec_drafts, **PAGED_KW)
    for prompt in PROMPTS[:3]:
        a = plain.submit(prompt, max_new_tokens=10, sampling=sp)
        b = spec.submit(prompt, max_new_tokens=10, sampling=sp)
        plain.run_until_idle()
        spec.run_until_idle()
        assert a.result() == b.result(), prompt


def test_spec_decoding_greedy_rows_parity(params):
    """Mixed rows batch through the speculative server: greedy rows keep
    exact parity with the non-speculative greedy reference."""
    ref = _greedy_ref(params, PROMPTS[1], 10, **PAGED_KW)
    srv = PagedInferenceServer(params, CFG, SAMPLED, spec_drafts=2,
                               **PAGED_KW)
    r0 = srv.submit(PROMPTS[1], max_new_tokens=10,
                    sampling=SamplingParams(temperature=0.0))
    srv.submit(PROMPTS[0], max_new_tokens=10,
               sampling=SamplingParams(temperature=1.2, seed=5))
    srv.run_until_idle()
    assert r0.result() == ref


def test_stop_sequence_through_server(params):
    """Token-level stop: generate greedily once, then require the same
    generation to stop just before a sequence it is known to emit."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    full = srv.generate([PROMPTS[0]], max_new_tokens=8)[0]
    stop = tuple(full[3:5])
    # expected: the greedy stream truncated at the FIRST tail match (the
    # bigram may recur earlier than position 3 in a looping toy model)
    want = None
    for i in range(len(full)):
        if tuple(full[i - 1:i + 1]) == stop and i >= 1:
            want = full[:i - 1]
            break
    assert want is not None
    r = srv.submit(PROMPTS[0], max_new_tokens=8,
                   sampling=SamplingParams(stop=(stop,)))
    srv.run_until_idle()
    assert r.finish_reason == "stop"
    assert r.result() == want


def test_preemption_preserves_sampling(params):
    """A seeded+penalised request preempted mid-decode resumes with the
    same rows (seed_used is stable) and completes deterministically."""
    kw = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32])
    sp = SamplingParams(temperature=0.8, seed=11, repetition_penalty=1.3)

    # reference: alone, no memory pressure
    srv = PagedInferenceServer(params, CFG, SAMPLED, num_pages=32, **kw)
    want = srv.generate([PROMPTS[1]], max_new_tokens=10)
    r_ref = srv.submit(PROMPTS[1], max_new_tokens=10, sampling=sp)
    srv.run_until_idle()

    # tight pool: concurrent requests force preemptions
    tight = PagedInferenceServer(params, CFG, SAMPLED, num_pages=10, **kw)
    r = tight.submit(PROMPTS[1], max_new_tokens=10, sampling=sp)
    others = [tight.submit(PROMPTS[3], max_new_tokens=10)
              for _ in range(2)]
    tight.run_until_idle()
    del want, others
    assert r.result() == r_ref.result()


# ---------------------------------------------------------------------------
# logit_bias / min_tokens
# ---------------------------------------------------------------------------


def test_logit_bias_forces_and_forbids(params):
    """A large positive bias forces a token; a large negative bias
    forbids one — through the live paged server, greedy."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    forced = srv.submit(PROMPTS[0], max_new_tokens=4,
                        sampling=SamplingParams(logit_bias=((42, 1e9),)))
    plain = srv.submit(PROMPTS[0], max_new_tokens=4)
    srv.run_until_idle()
    assert forced.result() == [42, 42, 42, 42]
    ban = plain.result()[0]
    srv2 = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    banned = srv2.submit(PROMPTS[0], max_new_tokens=4,
                         sampling=SamplingParams(logit_bias=((ban, -1e9),)))
    srv2.run_until_idle()
    assert ban not in banned.result()


def test_logit_bias_validation():
    with pytest.raises(ValueError):
        SamplingParams(logit_bias=tuple((i, 1.0) for i in range(65)))
    with pytest.raises(ValueError):
        SamplingParams(logit_bias=((-1, 1.0),))
    with pytest.raises(ValueError):
        SamplingParams(min_tokens=-1)


@pytest.mark.parametrize("spec_drafts", [0, 2])
def test_min_tokens_suppresses_eos(params, spec_drafts):
    """With EOS biased to +inf the model would stop immediately;
    min_tokens forces exactly that many tokens first — and the
    suppression stays exact through speculative windows."""
    eos_cfg = dataclasses.replace(GREEDY, eos_token_id=13)
    srv = PagedInferenceServer(params, CFG, eos_cfg,
                               spec_drafts=spec_drafts, **PAGED_KW)
    sp = SamplingParams(logit_bias=((13, 1e9),), min_tokens=5)
    r = srv.submit(PROMPTS[0], max_new_tokens=10, sampling=sp)
    rush = srv.submit(PROMPTS[0], max_new_tokens=10,
                      sampling=SamplingParams(logit_bias=((13, 1e9),)))
    srv.run_until_idle()
    assert r.finish_reason == "eos"
    assert len(r.result()) == 5  # exactly min_tokens, then eos
    assert rush.result() == []   # without min_tokens: immediate eos
