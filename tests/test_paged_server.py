"""Paged server: parity with the engine, the client API (streaming,
validation, failure, logprobs), prefix reuse, chunked prefill, in-server
speculative decoding, capacity beyond a contiguous layout."""

import dataclasses

import jax
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)

SRV_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32])


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _engine_reference(params, prompt, n_new, cfg=CFG):
    icfg = dataclasses.replace(GREEDY, max_decode_len=n_new)
    toks = engine.generate(
        params, np.asarray([prompt], np.int32), jax.random.key(1),
        cfg=cfg, infer_cfg=icfg)
    return list(np.asarray(toks)[0])


PROMPTS = [[5, 9, 3], [17, 2, 40, 8, 21], [60], list(range(1, 14))]


@pytest.mark.parametrize("allocation", ["ondemand", "reserve"])
def test_paged_server_matches_engine_greedy(params, allocation):
    srv = PagedInferenceServer(params, CFG, GREEDY, allocation=allocation,
                               **SRV_KW)
    outs = srv.generate(PROMPTS, max_new_tokens=8)
    for prompt, out in zip(PROMPTS, outs):
        assert out == _engine_reference(params, prompt, 8), prompt


def test_paged_server_interleaves(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, max_slots=2,
                               max_context=64, page_size=8,
                               prefill_chunk=16, prompt_buckets=[16])
    r0 = srv.submit(PROMPTS[0], max_new_tokens=12)
    for _ in range(3):
        srv.step()
    r1 = srv.submit(PROMPTS[1], max_new_tokens=6)
    r2 = srv.submit(PROMPTS[2], max_new_tokens=6)
    srv.run_until_idle()
    assert r0.result() == _engine_reference(params, PROMPTS[0], 12)
    assert r1.result() == _engine_reference(params, PROMPTS[1], 6)
    assert r2.result() == _engine_reference(params, PROMPTS[2], 6)


def test_streaming_callback_sees_tokens_in_order(params):
    seen = []
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    req = srv.submit(PROMPTS[0], max_new_tokens=8, stream=seen.append)
    srv.run_until_idle()
    assert seen == req.tokens == _engine_reference(params, PROMPTS[0], 8)


def test_submit_validation(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, max_slots=1,
                               max_context=16, page_size=8,
                               prefill_chunk=8, prompt_buckets=[8])
    with pytest.raises(ValueError, match="empty prompt"):
        srv.submit([])
    with pytest.raises(ValueError, match="exceeds largest bucket"):
        srv.submit(list(range(9)))
    with pytest.raises(ValueError, match="no room to decode"):
        srv.submit(list(range(8)), max_new_tokens=0)
    assert srv.num_pending == 0  # nothing refused was queued


def test_shape_validation_at_init(params):
    with pytest.raises(ValueError, match="multiple of"):
        PagedInferenceServer(params, CFG, GREEDY, max_slots=1,
                             max_context=60, page_size=8)
    with pytest.raises(ValueError, match="allocation"):
        PagedInferenceServer(params, CFG, GREEDY, allocation="eager",
                             **SRV_KW)


def test_scheduler_error_unblocks_clients(params):
    """A fatal step() error must fail waiting requests, not hang them."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    srv.step = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
    # submit BEFORE start: the patched step() raises on the scheduler's
    # first iteration, and a post-crash submit would (correctly) be
    # rejected with "server is stopped" — a race this test isn't about
    reqs = [srv.submit(p, max_new_tokens=4) for p in PROMPTS[:2]]
    srv.start()
    try:
        for req in reqs:
            with pytest.raises(RuntimeError, match="boom"):
                req.result(timeout=60)
    finally:
        srv.stop()


def test_slot_reuse_no_leakage(params):
    """A slot freed by one request must serve the next one exactly:
    one slot, and a second prompt that shares no page with the first."""
    srv = PagedInferenceServer(params, CFG, GREEDY,
                               **{**SRV_KW, "max_slots": 1})
    first = srv.generate([PROMPTS[1]], max_new_tokens=10)[0]
    second = srv.generate([PROMPTS[2]], max_new_tokens=10)[0]
    assert first == _engine_reference(params, PROMPTS[1], 10)
    assert second == _engine_reference(params, PROMPTS[2], 10)


def test_logprobs_recorded(params):
    """Every emitted token carries the log-probability the model assigned
    it: checked for the first token against a hand prefill."""
    import jax.numpy as jnp

    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    req = srv.submit([3, 7, 11], max_new_tokens=6)
    srv.run_until_idle()
    assert len(req.logprobs) == len(req.tokens) == 6
    assert all(lp <= 0.0 for lp in req.logprobs)
    cache = engine.init_cache(CFG, 1, 32)
    logits, _ = engine.prefill(params, jnp.asarray([[3, 7, 11]], jnp.int32),
                               CFG, cache)
    want = float(jax.nn.log_softmax(logits[0])[req.tokens[0]])
    np.testing.assert_allclose(req.logprobs[0], want, rtol=1e-4)


def test_chunked_prefill_long_prompt(params):
    """A prompt spanning several prefill chunks decodes identically."""
    long_prompt = [(i * 7) % 60 + 1 for i in range(30)]  # > prefill_chunk
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    out = srv.generate([long_prompt], max_new_tokens=8)[0]
    assert out == _engine_reference(params, long_prompt, 8)


def test_chunked_prefill_interleaves_decodes(params):
    """While a long admission runs chunk-by-chunk, active slots keep
    producing tokens every scheduler step (bounded decode stall)."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=32)
    for _ in range(3):
        srv.step()
    produced = len(r0.tokens)
    long_prompt = [(i * 5) % 60 + 1 for i in range(30)]
    r1 = srv.submit(long_prompt, max_new_tokens=4)
    srv.step()  # runs ONE chunk of r1's prefill + a decode dispatch
    assert len(r0.tokens) > produced  # r0 was not stalled by r1's prefill
    srv.run_until_idle()
    assert r0.result() == _engine_reference(params, PROMPTS[0], 32)
    assert r1.result() == _engine_reference(params, long_prompt, 4)


def test_prefix_reuse_across_requests(params):
    """Second request sharing a long prefix skips prefill pages and still
    matches the engine exactly."""
    base = [(i * 3) % 60 + 1 for i in range(24)]  # 3 full pages of 8
    p1 = base + [7, 7]
    p2 = base + [9, 1, 4]
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    out1 = srv.generate([p1], max_new_tokens=6)[0]
    hits_before = srv.allocator.prefix_hit_pages
    out2 = srv.generate([p2], max_new_tokens=6)[0]
    assert srv.allocator.prefix_hit_pages - hits_before >= 3
    assert out1 == _engine_reference(params, p1, 6)
    assert out2 == _engine_reference(params, p2, 6)


def test_multi_prefix_families(params):
    """Two distinct prefix families both get reuse (no single-prefix
    limitation)."""
    fam_a = [(i * 3) % 60 + 1 for i in range(16)]
    fam_b = [(i * 5) % 60 + 2 for i in range(16)]
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    for fam in (fam_a, fam_b):
        srv.generate([fam + [11]], max_new_tokens=4)
    hits0 = srv.allocator.prefix_hit_pages
    outs = srv.generate([fam_a + [12, 13], fam_b + [14]], max_new_tokens=4)
    assert srv.allocator.prefix_hit_pages - hits0 >= 4  # 2 pages each
    assert outs[0] == _engine_reference(params, fam_a + [12, 13], 4)
    assert outs[1] == _engine_reference(params, fam_b + [14], 4)


def test_speculative_greedy_parity(params):
    """spec_drafts > 0 must be token-for-token identical at temp 0 —
    including on repetitive prompts where drafts actually accept."""
    rep = [3, 4, 5, 6] * 5 + [3, 4]
    prompts = [rep, PROMPTS[0], PROMPTS[3]]
    plain = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    spec = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3,
                                **SRV_KW)
    out_p = plain.generate(prompts, max_new_tokens=10)
    out_s = spec.generate(prompts, max_new_tokens=10)
    assert out_p == out_s
    for prompt, out in zip(prompts, out_p):
        assert out == _engine_reference(params, prompt, 10)


def test_speculative_actually_accepts(params):
    """On a strongly repetitive greedy decode, n-gram drafts must commit
    >1 token per model round on average — guards the draft-quality path
    (history alignment), which parity tests cannot see (the accept rule
    keeps outputs exact even when every draft misses)."""
    rep = [3, 4, 5, 6] * 6
    srv = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3, **SRV_KW)
    srv.generate([rep], max_new_tokens=24)
    rate = srv.decode_tokens_committed / max(srv.decode_rounds, 1)
    assert rate > 1.3, (srv.decode_tokens_committed, srv.decode_rounds)


def _draft_setup():
    draft_cfg = dataclasses.replace(CFG, embed_dim=16, num_layers=1,
                                    num_heads=2, num_kv_heads=2, mlp_dim=32)
    draft_params = transformer.init_params(draft_cfg, jax.random.key(9))
    return draft_params, draft_cfg


def test_draft_model_spec_greedy_parity(params):
    """In-server DRAFT-MODEL speculation (classic speculative decoding
    through the paged server) is token-for-token exact at temperature 0,
    including across prefix-cache reuse (shared pages carry the draft
    model's kv alongside the target's)."""
    draft_params, draft_cfg = _draft_setup()
    srv = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=2,
                               draft_params=draft_params,
                               draft_cfg=draft_cfg, **SRV_KW)
    prompts = [[3, 4, 5, 6] * 4, PROMPTS[0], PROMPTS[3]]
    outs = srv.generate(prompts, max_new_tokens=10)
    for prompt, out in zip(prompts, outs):
        assert out == _engine_reference(params, prompt, 10), prompt
    # a second request sharing a prefix reuses pages in BOTH pools
    hits0 = srv.allocator.prefix_hit_pages
    out2 = srv.generate([prompts[0] + [9]], max_new_tokens=10)[0]
    assert srv.allocator.prefix_hit_pages > hits0
    assert out2 == _engine_reference(params, prompts[0] + [9], 10)


def test_draft_vocab_mismatch_fails_at_construction(params):
    draft_params, draft_cfg = _draft_setup()
    bad = dataclasses.replace(draft_cfg, vocab_size=CFG.vocab_size + 8)
    with pytest.raises(ValueError, match="vocab_size"):
        PagedInferenceServer(params, CFG, GREEDY, spec_drafts=2,
                             draft_params=draft_params, draft_cfg=bad,
                             **SRV_KW)


def test_pallas_refuses_chunk_wider_than_kernel(params):
    """A prefill chunk wider than the kernel's window cap used to hand
    those dispatches to the XLA reference without a word; a config that
    asks for the kernel now runs the kernel or fails at construction."""
    pallas = dataclasses.replace(CFG, decode_attention_impl="pallas")
    with pytest.raises(ValueError, match="window cap"):
        PagedInferenceServer(params, pallas, GREEDY, max_slots=2,
                             max_context=1024, page_size=8,
                             prefill_chunk=512)
    PagedInferenceServer(params, CFG, GREEDY, max_slots=2,
                         max_context=1024, page_size=8, prefill_chunk=512)


def test_draft_model_spec_sampled_smoke(params):
    draft_params, draft_cfg = _draft_setup()
    icfg = dataclasses.replace(GREEDY, temperature=0.9, top_k=20)
    srv = PagedInferenceServer(params, CFG, icfg, spec_drafts=2,
                               draft_params=draft_params,
                               draft_cfg=draft_cfg, **SRV_KW)
    outs = srv.generate(PROMPTS[:2], max_new_tokens=9)
    assert all(len(o) == 9 for o in outs)


def test_speculative_sampled_distribution_smoke(params):
    """Stochastic spec decoding runs end-to-end and respects budgets."""
    icfg = dataclasses.replace(GREEDY, temperature=0.8, top_k=20)
    srv = PagedInferenceServer(params, CFG, icfg, spec_drafts=2, **SRV_KW)
    outs = srv.generate(PROMPTS[:2], max_new_tokens=9)
    assert all(len(o) == 9 for o in outs)


def test_capacity_beyond_contiguous(params):
    """A pool sized for 4 full-context slots serves 8 concurrent short
    requests — the capacity win paging exists for. (A contiguous
    (slots, max_context) cache of the same bytes has 4 rows and would
    queue them 4 at a time; here all 8 are in flight at once.)"""
    srv = PagedInferenceServer(params, CFG, GREEDY, max_slots=8,
                               max_context=64, page_size=8,
                               num_pages=4 * 8,  # 4 slots' worth of pages
                               prefill_chunk=16, prompt_buckets=[16],
                               decode_chunk=1)
    reqs = [srv.submit([i + 1, i + 2, i + 3], max_new_tokens=6)
            for i in range(8)]
    srv.step()
    assert srv.num_active == 8  # all admitted concurrently
    srv.run_until_idle()
    for i, r in enumerate(reqs):
        prompt = [i + 1, i + 2, i + 3]
        assert r.result() == _engine_reference(params, prompt, 6)


def test_int8_kv_paged(params):
    cfg8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
    srv = PagedInferenceServer(params, cfg8, GREEDY, **SRV_KW)
    outs = srv.generate(PROMPTS[:2], max_new_tokens=8)
    # int8 cache: compare against the int8 contiguous engine (same
    # quantization), not the exact bf16 path
    for prompt, out in zip(PROMPTS[:2], outs):
        assert out == _engine_reference(params, prompt, 8, cfg=cfg8), prompt


def test_eos_and_budget(params):
    icfg = dataclasses.replace(GREEDY, eos_token_id=13)
    srv = PagedInferenceServer(params, CFG, icfg, **SRV_KW)
    ref = _engine_reference(params, PROMPTS[1], 12)
    want = []
    for t in ref:
        if t == 13:
            break
        want.append(t)
    out = srv.generate([PROMPTS[1]], max_new_tokens=12)[0]
    assert out == want


def test_oversized_request_fails_cleanly(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, max_slots=2,
                               max_context=32, page_size=8,
                               num_pages=2, prefill_chunk=8,
                               prompt_buckets=[16])
    r = srv.submit([1, 2, 3], max_new_tokens=20)  # needs 3 of 2 pages
    srv.run_until_idle()
    assert r.finish_reason.startswith("error")
    with pytest.raises(RuntimeError):
        r.result(timeout=1)


def test_latency_stats_recorded(params):
    """Every request carries submit/emit wall-clock times; TTFT and ITL
    percentiles come out of latency_stats()."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    r = srv.submit(PROMPTS[0], max_new_tokens=8)
    srv.run_until_idle()
    st = r.latency_stats()
    assert st is not None
    assert st["ttft"] > 0
    assert st["itl_max"] >= st["itl_p99"] >= st["itl_p50"] >= 0
    assert len(r.emit_times) == len(r.tokens)


def test_pallas_wide_prefill_chunks(params):
    """decode_attention_impl='pallas' with a prefill chunk wider than the
    narrow kernel's cap routes the wide (grid) kernel for admission
    windows and the narrow kernel for decode — outputs stay exact."""
    cfg = dataclasses.replace(CFG, decode_attention_impl="pallas")
    srv = PagedInferenceServer(params, cfg, GREEDY, max_slots=2,
                               max_context=128, page_size=8,
                               prefill_chunk=48, prompt_buckets=[16, 64])
    long_prompt = [(i * 7) % 60 + 1 for i in range(60)]
    out = srv.generate([long_prompt, PROMPTS[0]], max_new_tokens=6)
    assert out[0] == _engine_reference(params, long_prompt, 6)
    assert out[1] == _engine_reference(params, PROMPTS[0], 6)


def test_moe_paged_matches_engine():
    """The paged server serves the MoE family exactly (docs/serving.md
    claims it; window_forward routes through the shared block code) —
    plain and speculative decode both.

    capacity_factor >= E/k makes routing dropless, which is what makes
    bit-parity across batch sizes possible at all: with drops, expert
    capacity is contended BATCH-WIDE, so a token's output would depend
    on co-scheduled (even padding) rows — the engine reference runs
    B=1 while the server batches 4 slots."""
    from cloud_server_tpu.models import moe
    moe_cfg = dataclasses.replace(CFG, num_experts=4,
                                  num_experts_per_token=2,
                                  expert_capacity_factor=2.0)
    moe_params = moe.init_params(moe_cfg, jax.random.key(2))
    srv = PagedInferenceServer(moe_params, moe_cfg, GREEDY, **SRV_KW)
    outs = srv.generate(PROMPTS[:3], max_new_tokens=8)
    for prompt, out in zip(PROMPTS[:3], outs):
        assert out == _engine_reference(moe_params, prompt, 8,
                                        cfg=moe_cfg), prompt
    spec = PagedInferenceServer(moe_params, moe_cfg, GREEDY,
                                spec_drafts=2, **SRV_KW)
    assert spec.generate(PROMPTS[:3], max_new_tokens=8) == outs


def test_lora_merged_paged_matches_engine():
    """A LoRA-merged dense checkpoint (the serving artifact --lora-*
    produces) serves through the paged server with engine parity, and
    the adapters actually change the output (non-zero delta)."""
    from cloud_server_tpu.models.lora import (
        LoRAConfig, export_merged, make_lora_module)
    lcfg = LoRAConfig(rank=4, alpha=8.0, targets=("wq", "wv"))
    module = make_lora_module(lcfg)
    lparams = module.init_params(CFG, jax.random.key(3))
    # zero-init B makes merged == base; perturb it so the merge is real
    lparams["lora"] = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(4), a.shape,
                                    a.dtype) * 0.3,
        lparams["lora"])
    base = jax.tree.map(lambda x: x, lparams["base"])
    merged = export_merged(lparams, lcfg)
    srv = PagedInferenceServer(merged, CFG, GREEDY, **SRV_KW)
    outs = srv.generate(PROMPTS[:2], max_new_tokens=8)
    for prompt, out in zip(PROMPTS[:2], outs):
        assert out == _engine_reference(merged, prompt, 8), prompt
    base_srv = PagedInferenceServer(base, CFG, GREEDY, **SRV_KW)
    assert base_srv.generate(PROMPTS[:2], max_new_tokens=8) != outs


def test_ondemand_concurrency_beyond_reservation(params):
    """On-demand allocation admits every request where full reservation
    serializes them, preempting (youngest-first, radix-cached requeue)
    when chains outgrow the pool — outputs stay exact throughout."""
    prompts = [[(i * 9 + k) % 60 + 1 for k in range(8)] for i in range(6)]
    kw = dict(max_slots=6, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16], num_pages=12, decode_chunk=2)

    # full reservation: each request reserves ceil((8+40+1)/8) = 7 of 12
    # pages -> one slot in flight at a time
    rsv = PagedInferenceServer(params, CFG, GREEDY, allocation="reserve",
                               **kw)
    for p in prompts:
        rsv.submit(p, max_new_tokens=40)
    rsv.step()
    assert rsv.num_active == 1

    # on-demand: all 6 admit concurrently on 2 pages each
    srv = PagedInferenceServer(params, CFG, GREEDY, allocation="ondemand",
                               **kw)
    reqs = [srv.submit(p, max_new_tokens=40) for p in prompts]
    srv.step()
    assert srv.num_active == 6
    srv.run_until_idle()
    assert srv.preemptions > 0  # chains outgrew the pool mid-decode
    for p, r in zip(prompts, reqs):
        assert r.result() == _engine_reference(params, p, 40), p


def test_ondemand_preemption_with_speculation(params):
    """Preemption + continuation under the speculative decode loop."""
    prompts = [[3, 4, 5, 6] * 2 for _ in range(4)]
    srv = PagedInferenceServer(params, CFG, GREEDY, allocation="ondemand",
                               spec_drafts=2, max_slots=4, max_context=64,
                               page_size=8, prefill_chunk=16,
                               prompt_buckets=[16], num_pages=10,
                               decode_chunk=2)
    reqs = [srv.submit(p, max_new_tokens=30) for p in prompts]
    srv.run_until_idle()
    want = _engine_reference(params, prompts[0], 30)
    for r in reqs:
        assert r.result() == want


def test_ondemand_single_oversized_fails_cleanly(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, allocation="ondemand",
                               max_slots=2, max_context=64, page_size=8,
                               num_pages=3, prefill_chunk=8,
                               prompt_buckets=[16])
    r = srv.submit([1, 2, 3], max_new_tokens=40)  # needs 6 of 3 pages
    srv.run_until_idle()
    assert r.finish_reason.startswith("error")
    with pytest.raises(RuntimeError):
        r.result(timeout=1)
    # pool accounting stays consistent after the failure
    assert srv.allocator.available == 3


def test_eviction_under_churn(params):
    """Many distinct prompts through a small pool: cached pages get
    evicted, nothing corrupts, outputs stay exact."""
    srv = PagedInferenceServer(params, CFG, GREEDY, max_slots=2,
                               max_context=64, page_size=8,
                               num_pages=20, prefill_chunk=16,
                               prompt_buckets=[16, 32])
    for i in range(12):  # each leaves 2 cached pages; pool holds 20
        prompt = [(i * 11 + k) % 60 + 1 for k in range(17)]
        out = srv.generate([prompt], max_new_tokens=5)[0]
        assert out == _engine_reference(params, prompt, 5), i
    assert srv.allocator.evictions > 0


def test_admit_job_holds_one_slot(params):
    """An admission job is one slot's: scalars for its slot, lengths and
    captured sample, rows for its tokens, and a cursor that advances by
    whatever width the budget granted its chunk, so two admissions of
    one burst progress, and end, each on its own."""
    long_prompt = list(range(1, 29))  # 2 chunks at prefill_chunk=16
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    reqs = [srv.submit(p, max_new_tokens=6)
            for p in (PROMPTS[0], long_prompt)]
    srv.step()
    short, long = sorted(srv._jobs, key=lambda j: j.rem_len)
    for job, prompt in ((short, PROMPTS[0]), (long, long_prompt)):
        assert isinstance(job.slot, int) and srv._slots[job.slot] is not None
        assert (job.rem_len, job.base_len, job.prompt_len) == (
            len(prompt), 0, len(prompt))
        assert job.rows.shape == job.prompt_row.shape == (len(prompt),)
        assert list(job.rows) == prompt
        assert (job.got, job.done) == (False, 0)
    assert not hasattr(short, "slots") and short.slot != long.slot
    # the fill's plan took a chunk of each; nothing is committed yet
    assert (short.planned, long.planned) == (len(PROMPTS[0]), 16)
    srv.step()
    # the short one is admitted and gone, the long one is half way
    assert srv._jobs == [long] and (long.done, long.planned) == (16, 28)
    assert srv.active[short.slot] and not long.got
    srv.step()
    assert not srv._jobs and long.got and long.done == 28
    assert long.tok == reqs[1].tokens[0]
    srv.run_until_idle()
    for r, p in zip(reqs, (PROMPTS[0], long_prompt)):
        assert r.result() == _engine_reference(params, p, 6)
