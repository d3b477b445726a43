"""Stall-free mixed batching: the token-budget scheduler that fuses
chunked-prefill rows and decode rows into one ragged dispatch.

The exactness property (greedy served == the dense engine,
token-for-token; seeded served the same under another schedule) is the
load-bearing guarantee: the fused dispatch computes the same logits
positions against the same per-slot cache contents, so only the
SCHEDULE differs. Every test here compares with the engine reference,
or with the same server under another schedule (`OTHER_SCHEDULE`),
over scenarios where decode and prefill genuinely overlap.
"""

import dataclasses

import jax
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.sampling import SamplingParams
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)

SRV_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32])


# another schedule for the same traffic: one round a dispatch and a
# budget that funds one minimal chunk beside the decode rows, where the
# default runs eight rounds and every waiting chunk at once
OTHER_SCHEDULE = dict(decode_chunk=1, mixed_token_budget=28)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _engine_reference(params, prompt, n_new, cfg=CFG):
    icfg = dataclasses.replace(GREEDY, max_decode_len=n_new)
    toks = engine.generate(
        params, np.asarray([prompt], np.int32), jax.random.key(1),
        cfg=cfg, infer_cfg=icfg)
    return list(np.asarray(toks)[0])


def _staggered_run(srv, prompts, max_new):
    """Admit prompts in two waves so later admissions genuinely overlap
    earlier requests' decode (the regime the schedulers differ in)."""
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts[:2]]
    for _ in range(3):
        srv.step()
    reqs += [srv.submit(p, max_new_tokens=max_new) for p in prompts[2:]]
    srv.run_until_idle()
    return [r.result() for r in reqs]


LONG = [(i * 7) % 60 + 1 for i in range(30)]  # spans several chunks
PROMPTS = [[5, 9, 3], [17, 2, 40, 8, 21], LONG, list(range(1, 14))]


def test_mixed_greedy_equals_the_dense_engine(params):
    """THE acceptance property: every request's token stream is the
    dense engine's, with admissions landing mid-decode."""
    mixed = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    for p, o in zip(PROMPTS, _staggered_run(mixed, PROMPTS, 12)):
        assert o == _engine_reference(params, p, 12), p


def test_mixed_seeded_sampling_is_schedule_invariant(params):
    """Seeded per-request sampling draws from (seed, position) keys, so
    the schedule must not change sampled outputs either."""
    icfg = dataclasses.replace(GREEDY, temperature=1.0)
    sp = [SamplingParams(seed=100 + i, temperature=0.9, top_p=0.9,
                         presence_penalty=0.4)
          for i in range(len(PROMPTS))]

    def run(**kw):
        srv = PagedInferenceServer(params, CFG, icfg, **SRV_KW, **kw)
        reqs = [srv.submit(p, max_new_tokens=10, sampling=s)
                for p, s in zip(PROMPTS[:2], sp[:2])]
        for _ in range(3):
            srv.step()
        reqs += [srv.submit(p, max_new_tokens=10, sampling=s)
                 for p, s in zip(PROMPTS[2:], sp[2:])]
        srv.run_until_idle()
        return [r.result() for r in reqs]

    assert run() == run(**OTHER_SCHEDULE)


def test_mixed_speculative_greedy_parity(params):
    """Mixed decode rows at W = drafts + 1: speculative mixed must stay
    token-for-token exact, including on repetitive prompts where drafts
    actually accept."""
    rep = [3, 4, 5, 6] * 5 + [3, 4]
    prompts = [rep, PROMPTS[0], LONG]
    spec = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3,
                                **SRV_KW)
    out = _staggered_run(spec, prompts, 10)
    for p, o in zip(prompts, out):
        assert o == _engine_reference(params, p, 10), p


def test_mixed_stall_free_itl_bound(params):
    """The property the scheduler exists for: while a multi-chunk
    admission is in flight, every live decode slot advances on EVERY
    scheduler iteration — no decode step is skipped for a prefill-only
    dispatch."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=40)
    while not srv.active.any():
        srv.step()
    srv.submit(LONG, max_new_tokens=4)
    steps_with_admission = 0
    while srv._jobs or srv.num_pending:
        before = len(r0.tokens)
        srv.step()
        if r0.done:
            break
        assert len(r0.tokens) > before, "decode stalled during admission"
        steps_with_admission += 1
    assert steps_with_admission >= 2  # the admission really was chunked
    srv.run_until_idle()
    assert r0.result() == _engine_reference(params, PROMPTS[0], 40)


def test_mixed_budget_caps_prefill_rows(params):
    """The token budget is respected: with room for one decode row plus
    one chunk, the SECOND concurrent admission is not selected (width 0,
    inert) until the first finishes — and still completes exactly."""
    srv = PagedInferenceServer(params, CFG, GREEDY, mixed_token_budget=17, **SRV_KW)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=24)
    while not srv.active.any():
        srv.step()
    pa = LONG
    pb = [(i * 11) % 60 + 1 for i in range(28)]
    ra = srv.submit(pa, max_new_tokens=6)
    rb = srv.submit(pb, max_new_tokens=6)
    srv.step()
    # both admitted into slots, but budget - 1 live decode row leaves
    # exactly 16 prefill tokens: only the FIFO-older admission
    # advances. The budget's selection is read off the PLANNED
    # (dispatched) cursor — with the async scheduler (default) the
    # chunk is still in flight after one step and `done` catches up
    # at its commit; planned == done on the sequential path, so this
    # reads identically either way.
    assert len(srv._jobs) == 2
    planned = [j.planned for j in srv._jobs]
    assert planned[0] > 0 and planned[1] == 0, planned
    srv.step()  # the in-flight chunk commits: done catches up
    assert len(srv._jobs) == 2
    dones = [j.done for j in srv._jobs]
    assert dones[0] > 0 and dones[1] == 0, dones
    srv.run_until_idle()
    assert r0.result() == _engine_reference(params, PROMPTS[0], 24)
    assert ra.result() == _engine_reference(params, pa, 6)
    assert rb.result() == _engine_reference(params, pb, 6)


def test_mixed_sentinel_safety_mid_admission(params):
    """A slot mid-admission must never have its freshly prefilled pages
    clobbered by the fused batch: decode rows, selected prefill rows and
    the inert row all share one dispatch here, and the waiting
    admission's output stays exact."""
    srv = PagedInferenceServer(params, CFG, GREEDY, mixed_token_budget=SRV_KW["max_slots"] + 16,
                               **SRV_KW)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=24)  # decodes throughout
    for _ in range(3):
        srv.step()
    ra = srv.submit(LONG, max_new_tokens=6)
    rb = srv.submit([(i * 13) % 60 + 1 for i in range(28)],
                    max_new_tokens=6)
    srv.run_until_idle()
    assert r0.result() == _engine_reference(params, PROMPTS[0], 24)
    assert ra.result() == _engine_reference(params, LONG, 6)
    assert rb.result() == _engine_reference(
        params, [(i * 13) % 60 + 1 for i in range(28)], 6)


def test_mixed_preemption_while_dispatching(params):
    """Preemption/requeue fired from inside the mixed loop (page famine
    during _extend_chains) keeps every output exact — the preempted
    request re-admits as a continuation THROUGH the mixed scheduler."""
    prompts = [[(i * 9 + k) % 60 + 1 for k in range(8)] for i in range(6)]
    srv = PagedInferenceServer(
        params, CFG, GREEDY, allocation="ondemand",
        max_slots=6, max_context=64, page_size=8, prefill_chunk=16,
        prompt_buckets=[16], num_pages=12, decode_chunk=2)
    reqs = [srv.submit(p, max_new_tokens=40) for p in prompts]
    srv.run_until_idle()
    assert srv.preemptions > 0  # chains outgrew the pool mid-decode
    for p, r in zip(prompts, reqs):
        assert r.result() == _engine_reference(params, p, 40), p


def test_mixed_grammar_and_penalties_through_admission(params):
    """Constrained + penalized requests keep their per-slot device state
    correct when their admission and another slot's decode share a
    dispatch (gstate/penalty scatters are row-masked in _mixed_step)."""
    icfg = dataclasses.replace(GREEDY, temperature=1.0)
    srv = PagedInferenceServer(params, CFG, icfg, **SRV_KW)
    alt = PagedInferenceServer(params, CFG, icfg, **SRV_KW,
                               **OTHER_SCHEDULE)
    sp = SamplingParams(seed=7, temperature=0.8, frequency_penalty=0.5)

    def run(s):
        r0 = s.submit(PROMPTS[0], max_new_tokens=16, sampling=sp)
        for _ in range(2):
            s.step()
        r1 = s.submit(LONG, max_new_tokens=8,
                      sampling=SamplingParams(seed=9, presence_penalty=0.3))
        s.run_until_idle()
        return r0.result(), r1.result()

    assert run(srv) == run(alt)


def _draft_setup():
    draft_cfg = dataclasses.replace(CFG, embed_dim=16, num_layers=1,
                                    num_heads=2, num_kv_heads=2,
                                    mlp_dim=32)
    draft_params = transformer.init_params(draft_cfg, jax.random.key(9))
    return draft_params, draft_cfg


REP = [3, 4, 5, 6] * 5 + [3, 4]  # drafts genuinely accept here


def test_mixed_draft_spec_greedy_equals_the_dense_engine(params):
    """THE fusion property: with a draft model configured the step
    stays one fused program, and greedy outputs are token-for-token the
    engine reference's — admissions landing mid-decode, draft prefill
    riding the ragged fused group."""
    draft_params, draft_cfg = _draft_setup()
    kw = dict(spec_drafts=2, draft_params=draft_params,
              draft_cfg=draft_cfg, **SRV_KW)
    prompts = [REP, PROMPTS[0], LONG, list(range(1, 14))]
    mixed = PagedInferenceServer(params, CFG, GREEDY, **kw)
    out_m = _staggered_run(mixed, prompts, 12)
    assert any(r.get("prefill_tokens") and r.get("spec_rows")
               for r in mixed.flight_window()), \
        "no program ran a prefill group beside draft rounds"
    for p, o in zip(prompts, out_m):
        assert o == _engine_reference(params, p, 12), p


def test_mixed_draft_spec_seeded_is_schedule_invariant(params):
    """Seeded sampling through draft-model speculation: the draft
    proposal, accept uniform, and corrective draws are position-keyed
    per request (speculative._row_pos_keys), so the schedule must not
    change speculative sampled outputs either — two schedules agree
    token-for-token at temperature > 0, penalties included.
    Draft length pinned (spec_control=False): length schedules are a
    throughput policy, and at temperature > 0 the bonus-position draw
    legitimately differs across schedules that pick different
    lengths."""
    draft_params, draft_cfg = _draft_setup()
    icfg = dataclasses.replace(GREEDY, temperature=1.0)
    sp = [SamplingParams(seed=300 + i, temperature=0.9, top_p=0.9,
                         presence_penalty=0.3)
          for i in range(4)]
    prompts = [REP, PROMPTS[0], LONG, PROMPTS[1]]

    def run(**kw):
        srv = PagedInferenceServer(
            params, CFG, icfg, spec_drafts=2,
            draft_params=draft_params, draft_cfg=draft_cfg,
            spec_control=False, **SRV_KW, **kw)
        reqs = [srv.submit(p, max_new_tokens=10, sampling=s)
                for p, s in zip(prompts[:2], sp[:2])]
        for _ in range(3):
            srv.step()
        reqs += [srv.submit(p, max_new_tokens=10, sampling=s)
                 for p, s in zip(prompts[2:], sp[2:])]
        srv.run_until_idle()
        return [r.result() for r in reqs]

    assert run() == run(decode_chunk=1, mixed_token_budget=40)


def test_mixed_adaptive_spec_midstream_changes_exact(params):
    """Mid-stream draft-length changes from the controller keep greedy
    outputs exact: a random-init draft model accepts poorly, so an
    aggressive controller really does walk lengths down (and 0-length
    rows ride the speculative window as plain decode) — and every
    token still matches the engine reference."""
    draft_params, draft_cfg = _draft_setup()
    ctl = {"low": 0.45, "high": 0.8, "ewma": 0.5, "cooldown": 1,
           "probe_period": 4}
    kw = dict(spec_drafts=3, draft_params=draft_params,
              draft_cfg=draft_cfg, spec_control=ctl, **SRV_KW)
    prompts = [REP, PROMPTS[0], LONG]
    mixed = PagedInferenceServer(params, CFG, GREEDY, **kw)
    out_m = _staggered_run(mixed, prompts, 14)
    assert mixed.spec_control.length_changes > 0, \
        "controller never changed a draft length; the test is vacuous"
    for p, o in zip(prompts, out_m):
        assert o == _engine_reference(params, p, 14), p


def test_mixed_adaptive_ngram_raises_lengths_exact(params):
    """The controller moves BOTH ways: n-gram drafting on repetitive
    prompts accepts well, so lengths climb from a pinned-low start —
    still token-for-token exact, and committed-per-round really rises
    above plain decode's 1.0."""
    ctl = {"initial": 1, "low": 0.2, "high": 0.5, "ewma": 0.5,
           "cooldown": 2, "probe_period": 8}
    srv = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3, spec_control=ctl, **SRV_KW)
    prompts = [REP, [3, 4, 5, 6] * 6]
    out = _staggered_run(srv, prompts, 16)
    assert srv.spec_control.length_changes > 0
    assert (srv.decode_tokens_committed / max(srv.decode_rounds, 1)) > 1.1
    for p, o in zip(prompts, out):
        assert o == _engine_reference(params, p, 16), p


def test_mixed_draft_spec_grammar_is_schedule_invariant():
    """Grammar masks through the FUSED draft/verify walk: a
    regex-constrained, penalized request sharing the batch with a free
    request — two schedules agree token-for-token, and the constrained
    output is all digits."""
    from cloud_server_tpu.data.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    gcfg = dataclasses.replace(CFG, vocab_size=300)
    gparams = transformer.init_params(gcfg, jax.random.key(2))
    draft_cfg = dataclasses.replace(gcfg, embed_dim=16, num_layers=1,
                                    num_heads=2, num_kv_heads=2,
                                    mlp_dim=32)
    draft_params = transformer.init_params(draft_cfg, jax.random.key(3))
    icfg = InferConfig(max_decode_len=12, temperature=0.0,
                       eos_token_id=tok.eos_id, pad_token_id=0)
    kw = dict(max_slots=4, max_context=128, page_size=8,
              prefill_chunk=16, prompt_buckets=[16, 32], tokenizer=tok,
              spec_drafts=2, draft_params=draft_params,
              draft_cfg=draft_cfg)

    def run(**other):
        srv = PagedInferenceServer(gparams, gcfg, icfg, **kw, **other)
        free = srv.submit(tok.encode("hello"), max_new_tokens=12)
        for _ in range(2):
            srv.step()
        con = srv.submit(tok.encode("n:"), max_new_tokens=12,
                         sampling=SamplingParams(regex=r"[0-9]+", seed=5,
                                                 frequency_penalty=0.3))
        srv.run_until_idle()
        return free.result(), con.result()

    out_m = run()
    assert out_m == run(decode_chunk=1, mixed_token_budget=40)
    digits = tok.decode([t for t in out_m[1] if t != tok.eos_id])
    assert digits and digits.isdigit(), digits


def test_mixed_budget_too_small_rejected(params):
    with pytest.raises(ValueError, match="mixed_token_budget"):
        PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3,
                             mixed_token_budget=2, **SRV_KW)
