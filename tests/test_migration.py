"""Live in-flight request migration (inference/migration.py + the
paged server's export/import threading + router failover/drain wiring).

The load-bearing guarantee: a migrated request's client-visible stream
is byte-identical to the uninterrupted run — the tokens salvaged
before the hand-off plus the continuation, no token lost, none
duplicated. Exactness rests ONLY on the host token state (tokens,
seed_used, position-keyed RNG streams, grammar walk re-derived from
the tokens); the KV page transfer is purely a prefill-cost
optimization, so the crash-path salvage (no KV) is exact too.
"""

import dataclasses
import threading
import time

import jax
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.block_allocator import BlockAllocator
from cloud_server_tpu.inference.faults import FaultPlan, InjectedFault
from cloud_server_tpu.inference.http_server import HttpFrontend
from cloud_server_tpu.inference.migration import (MIGRATION_VERSION,
                                                  MigrationLedger,
                                                  MigrationSnapshot)
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.request_trace import PHASES
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.inference.sampling import SamplingParams
from cloud_server_tpu.inference.request import Request
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
SRV_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32])
LONG = [(i * 7) % 60 + 1 for i in range(30)]
MID = [3, 1, 4, 1, 5, 9, 2, 6]


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _assert_gap_free(tree):
    root = tree["root"]
    phases = [c for c in root["children"] if c["name"] in PHASES]
    assert phases, f"no phase spans in {tree['request_id']}"
    assert phases[0]["start"] == root["start"]
    for a, b in zip(phases, phases[1:]):
        assert a["end"] == b["start"], \
            f"gap between {a['name']} and {b['name']}"
    if root["end"] is not None:
        assert phases[-1]["end"] == root["end"]


def _drive(router, reqs, deadline_s=90.0):
    deadline = time.time() + deadline_s
    while not all(r.done for r in reqs) and time.time() < deadline:
        router.step()
        time.sleep(0.001)
    assert all(r.done for r in reqs), \
        [(r.request_id, len(r.tokens), r.finish_reason) for r in reqs]


# ---------------------------------------------------------------------------
# allocator: import_chain (destination-side page re-admission)
# ---------------------------------------------------------------------------


def _toks(n, base=0):
    return [base + i + 1 for i in range(n)]


def test_import_chain_dedupe_partial_and_famine():
    a = BlockAllocator(8, page_size=4)
    fills = a.import_chain(_toks(12))
    # nothing cached yet: every page in the chain needs a fill
    assert len(fills) == 3
    assert [c for c, _ in fills] == [0, 1, 2]
    pages = [p for _, p in fills]
    assert len(set(pages)) == 3
    st = a.stats()
    assert st.pages_cached == 3
    assert st.pages_free + st.pages_cached + st.pages_active == 8

    # the imported chain is now a cache hit for a matching prompt
    # (13 tokens: lookup always leaves >= 1 token un-shared)
    shared, n_tok = a.lookup_prefix(_toks(13))
    assert len(shared) == 3 and n_tok == 12
    a.release(shared, _toks(12))

    # re-import of the same chain dedupes completely: no fills
    assert a.import_chain(_toks(12)) == []
    # a longer chain sharing the prefix only fills the NEW tail pages
    fills = a.import_chain(_toks(20))
    assert [c for c, _ in fills] == [3, 4]
    assert a.stats().pages_cached == 5

    # famine: once pages run out the import stays partial — the
    # prefix that DID land is still usable, the rest re-prefills
    b = BlockAllocator(2, page_size=4)
    fills = b.import_chain(_toks(16))
    assert len(fills) == 2
    assert b.stats().pages_cached == 2
    assert b.stats().pages_free == 0


# ---------------------------------------------------------------------------
# export: snapshot contents + atomic evacuation
# ---------------------------------------------------------------------------


def test_export_snapshot_fields_and_evacuation(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, tracing=1.0)
    sp = SamplingParams(seed=77, temperature=0.9, top_p=0.9)
    req = srv.submit(LONG, max_new_tokens=24, sampling=sp,
                     deadline_s=45.0)
    while len(req.tokens) < 4:
        srv.step()

    snap = srv.migrate_export(req, reason="drain")
    assert snap.version == MIGRATION_VERSION
    assert snap.request_id == req.request_id
    assert snap.reason == "drain"
    assert list(snap.prompt) == LONG
    assert len(snap.tokens) >= 4
    assert snap.tokens == tuple(req.tokens)
    assert snap.logprobs == tuple(req.logprobs)
    assert len(snap.emit_times) == len(snap.tokens)
    assert snap.seed_used == req.seed_used
    assert snap.sampling is sp
    assert snap.max_new_tokens == 24
    assert snap.remaining_new_tokens() == 24 - len(snap.tokens)
    # the REMAINDER rides along, never the absolute host stamp
    assert 0 < snap.deadline_remaining_s <= 45.0
    assert snap.trace_ctx is not None
    # committed FULL pages only, keyed to their exact token chain
    n = snap.n_kv_pages()
    assert n >= 2
    full = list(LONG) + list(snap.tokens)
    assert list(snap.chain_tokens) == full[:n * srv.page_size]
    assert set(snap.kv_pages) == set(srv.state["pools"])
    for name, arr in snap.kv_pages.items():
        assert arr.shape[1] == n, name
        assert isinstance(arr, np.ndarray)  # host-side, ships anywhere

    # evacuated atomically: gone from the server, handle NOT completed
    # (the caller re-admits elsewhere and mirrors the outcome back)
    assert not req.done
    assert srv.num_active == 0 and srv.num_pending == 0
    st = srv.allocator.stats()
    assert st.pages_active == 0
    assert st.pages_free + st.pages_cached == st.pages_total
    # the source half of the trace closes as a complete, gap-free
    # tree (finish:migrated); the continuation joins the same trace id
    trees = srv.trace_trees()
    src = next(t for t in trees if t["request_id"] == req.request_id)
    assert src["root"]["end"] is not None
    assert "finish_reason" not in src["root"]["tags"]  # NOT completed
    _assert_gap_free(src)

    mstats = srv.migration_stats()
    assert mstats["out_started"] == 1
    assert mstats["out_completed"] == 1
    assert mstats["out_failed"] == 0
    assert mstats["tokens_salvaged"] == len(snap.tokens)
    assert mstats["pages_moved"] == n
    snap_m = srv.metrics_snapshot()
    assert snap_m["cloud_server_migrations_started_total"]["value"] == 1
    assert snap_m["cloud_server_migrations_completed_total"][
        "value"] == 1
    assert snap_m["cloud_server_migrations_failed_total"]["value"] == 0


def test_export_pending_request_is_host_only(params):
    srv = PagedInferenceServer(params, CFG, GREEDY,
                               **dict(SRV_KW, max_slots=2))
    hogs = [srv.submit(LONG, max_new_tokens=16) for _ in range(2)]
    srv.step()
    queued = srv.submit(MID, max_new_tokens=6)
    assert srv.num_pending == 1
    snap = srv.migrate_export(queued)
    assert snap.tokens == ()
    assert snap.n_kv_pages() == 0 and snap.kv_pages is None
    assert srv.num_pending == 0
    srv.run_until_idle()
    assert all(h.done for h in hogs)


# ---------------------------------------------------------------------------
# live export -> import: token-exact resumption, KV actually reused
# ---------------------------------------------------------------------------


def test_live_migration_token_exact_greedy_and_seeded(params):
    lone = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    g_ref = lone.generate([LONG], max_new_tokens=24)[0]
    sp = SamplingParams(seed=123, temperature=0.8, top_p=0.9)
    s_ref_req = lone.submit(MID, max_new_tokens=48, sampling=sp)
    lone.run_until_idle()
    s_ref = list(s_ref_req.tokens)

    r0 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    r1 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    g_stream, s_stream = [], []
    g = r0.submit(LONG, max_new_tokens=24, stream=g_stream.append)
    s = r0.submit(MID, max_new_tokens=48, sampling=sp,
                  stream=s_stream.append)
    while len(g.tokens) < 5 or len(s.tokens) < 5:
        r0.step()

    gs = r0.migrate_export(g)
    ss = r0.migrate_export(s)
    assert gs.n_kv_pages() >= 2
    before_hits = r1.allocator.stats().prefix_hit_pages
    g2 = r1.migrate_import(gs, stream=g_stream.append)
    s2 = r1.migrate_import(ss, stream=s_stream.append)
    # the continuation handle resumes with the salvaged stream intact
    assert list(g2.tokens) == list(gs.tokens)
    r1.run_until_idle()

    assert g2.done and g2.finish_reason == "length"
    assert s2.done and s2.finish_reason == "length"
    # EXACT vs the uninterrupted run — greedy and seeded sampling
    assert list(g2.tokens) == g_ref
    assert list(s2.tokens) == s_ref
    assert len(g2.logprobs) == 24
    # client stream: zero loss, zero duplication across the hand-off
    assert g_stream == g_ref
    assert s_stream == s_ref
    # the imported pages were REUSED by the continuation's admission
    # (prefix hits on the destination cover the transferred chain)
    gained = r1.allocator.stats().prefix_hit_pages - before_hits
    assert gained >= gs.n_kv_pages()
    # destination flight records attribute the migrated admissions
    assert any(rec.get("migrated_in") for rec in r1.flight_window())
    st0, st1 = r0.migration_stats(), r1.migration_stats()
    assert st0["out_completed"] == 2 and st0["out_failed"] == 0
    assert st1["in_completed"] == 2 and st1["in_failed"] == 0
    assert st1["pages_moved"] == 0  # import counts ride the exporter


# ---------------------------------------------------------------------------
# import/export guardrails and injected faults
# ---------------------------------------------------------------------------


def test_migrate_import_rejections_and_injected_faults(params):
    fp = FaultPlan()
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, faults=fp)
    req = srv.submit(MID, max_new_tokens=6)
    srv.step()

    # injected export fault surfaces to the caller; the request is
    # untouched and finishes normally on this server
    fp.arm("migrate_export", count=1)
    with pytest.raises(InjectedFault):
        srv.migrate_export(req)
    srv.run_until_idle()
    assert req.done and req.finish_reason == "length"

    # a finished request is not exportable
    with pytest.raises(RuntimeError, match="not live"):
        srv.migrate_export(req)

    # crash-path salvage works from the bare handle (host-only)
    snap = srv.migrate_salvage(req)
    assert snap.tokens == tuple(req.tokens)
    assert snap.n_kv_pages() == 0

    # exhausted decode budget: nothing to resume
    with pytest.raises(ValueError, match="budget"):
        srv.migrate_import(snap)
    # version mismatch: refuse, don't guess
    bad = dataclasses.replace(snap, version=MIGRATION_VERSION + 1,
                              max_new_tokens=12)
    with pytest.raises(ValueError, match="version"):
        srv.migrate_import(bad)
    # injected import fault
    good = dataclasses.replace(snap, max_new_tokens=12)
    fp.arm("migrate_import", count=1)
    with pytest.raises(InjectedFault):
        srv.migrate_import(good)

    mstats = srv.migration_stats()
    # two failed exports: the injected fault AND the not-live refusal
    assert mstats["out_failed"] == 2
    assert mstats["out_completed"] == 1  # the salvage
    assert mstats["in_failed"] == 3
    assert mstats["in_completed"] == 0
    assert srv.metrics_snapshot()[
        "cloud_server_migrations_failed_total"]["value"] == 5


def test_nonmigratable_mid_stream_failure_keeps_old_contract(params):
    """A replica whose failure path can't salvage (no migrate_salvage,
    or salvage itself raises) falls back to today's fail-fast
    contract: the mid-stream request fails, is NOT retried."""
    class _Stub:
        ready = True
        num_active = num_pending = 0

        def submit(self, prompt, **kw):
            raise AssertionError("must not be resubmitted")

    router = ReplicatedRouter([_Stub(), _Stub()])
    hook = router._make_fail_hook(0, [1, 2], {}, frozenset(), None)
    req = Request(prompt=[1, 2], max_new_tokens=4)
    req.finish_reason = "error: boom"
    req.tokens = [7, 8]          # mid-stream
    assert hook(req) is False    # old contract: fail-fast stands
    assert router.migration_stats()["out_started"] == 0

    # a real server whose export keeps failing: the router counts the
    # failed salvage and falls back the same way
    fp = FaultPlan()
    fp.arm("migrate_export", count=0)      # every export raises
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, faults=fp)
    r = srv.submit(MID, max_new_tokens=6)
    srv.step()
    with pytest.raises(InjectedFault):
        srv.migrate_export(r)
    assert srv.migration_stats()["out_failed"] == 1
    srv.run_until_idle()
    assert r.done and r.finish_reason == "length"


# ---------------------------------------------------------------------------
# zero-cost when idle: the unconfigured path stays byte-identical
# ---------------------------------------------------------------------------


def test_migration_armed_idle_keeps_dispatch_counts(params, monkeypatch):
    """Clone of the overlap dispatch/sync-count guard with migration
    fault sites armed far in the future: the happy path must issue
    exactly the same dispatches and device_gets — migration adds ZERO
    syncs until an export actually runs."""
    from cloud_server_tpu.inference import paged_server as ps
    fp = FaultPlan({"faults": [
        {"site": "migrate_export", "after": 10**6},
        {"site": "migrate_import", "after": 10**6}]})
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, faults=fp)
    calls = {"dispatch": 0, "get": 0}
    origs = {n: getattr(ps, n) for n in
             ("_mixed_step", "_decode_rounds", "_spec_rounds")}
    orig_get = jax.device_get

    def wrap(name):
        def w(*a, **k):
            calls["dispatch"] += 1
            return origs[name](*a, **k)
        return w

    for n in origs:
        monkeypatch.setattr(ps, n, wrap(n))
    monkeypatch.setattr(jax, "device_get",
                        lambda x: (calls.__setitem__(
                            "get", calls["get"] + 1), orig_get(x))[1])

    warm = srv.submit([5, 9, 3, 1], max_new_tokens=24)
    srv.step()  # FILL: a plan, launched
    assert calls == {"dispatch": 1, "get": 0}
    assert srv._inflight is not None
    long = srv.submit(LONG, max_new_tokens=4)
    steps = 0
    while srv._jobs or srv.num_pending:
        before = dict(calls)
        srv.step()
        steps += 1
        assert calls["dispatch"] - before["dispatch"] == 1
        assert calls["get"] - before["get"] == 1
        assert steps < 50
    assert steps >= 2
    for n, f in origs.items():
        monkeypatch.setattr(ps, n, f)
    monkeypatch.setattr(jax, "device_get", orig_get)
    srv.run_until_idle()
    assert warm.done and long.done
    assert srv.migration_stats()["out_started"] == 0


# ---------------------------------------------------------------------------
# router drain(migrate=True): zero-loss evacuation
# ---------------------------------------------------------------------------


def test_router_drain_migrate_evacuates_all(params):
    prompts = [LONG, MID, [7, 7, 2, 11, 30]]
    lone = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    # a budget the drain's own commit of the dispatch in flight (up to
    # eight rounds) cannot use up: nothing ends before it is evacuated
    refs = [lone.generate([p], max_new_tokens=40)[0] for p in prompts]

    r0 = PagedInferenceServer(params, CFG, GREEDY,
                              **dict(SRV_KW, max_slots=2), tracing=1.0)
    r1 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                              tracing=1.0)
    router = ReplicatedRouter([r0, r1])
    # keep replica 1 busier so all three land on replica 0
    fillers = [r1.submit([5, 9, 3], max_new_tokens=16)
               for _ in range(3)]
    streams = [[] for _ in prompts]
    reqs = [router.submit(p, max_new_tokens=40, stream=st.append)
            for p, st in zip(prompts, streams)]
    while len(reqs[0].tokens) < 2 or len(reqs[1].tokens) < 2:
        router.step()
    # two in slots mid-stream, one still queued: the drain must
    # evacuate BOTH kinds with zero loss
    assert r0.num_active == 2 and r0.num_pending == 1

    assert router.drain(0) is True
    assert r0.num_active == 0 and r0.num_pending == 0
    assert not r0.ready
    _drive(router, reqs + fillers)

    for r, ref, st in zip(reqs, refs, streams):
        assert r.finish_reason == "length"
        assert list(r.tokens) == ref
        assert st == ref
    mstats = router.migration_stats()
    assert mstats["out_started"] == 3
    assert mstats["out_completed"] == 3
    assert mstats["out_failed"] == 0
    assert mstats["in_completed"] == 3
    assert mstats["success_rate"] == 1.0

    # /stats surfaces the fleet-merged migration block
    payload = HttpFrontend(router)._stats_json(0)
    assert payload["migration"]["out_completed"] == 3
    assert payload["migration"]["success_rate"] == 1.0

    # every drained request's continuation tree carries the migrate
    # span with drain provenance; finished trees stay gap-free
    trees = router.trace_trees()
    spans = [c for t in trees for c in t["root"]["children"]
             if c["name"] == "migrate"]
    assert len(spans) == 3
    assert all(sp["tags"]["reason"] == "drain" for sp in spans)
    for t in trees:
        if t["root"]["end"] is not None:
            _assert_gap_free(t)

    # the drained replica can come back and serve again
    r0.resume()
    assert r0.ready
    back = router.submit(MID, max_new_tokens=4)
    _drive(router, [back])
    assert back.finish_reason == "length"


# ---------------------------------------------------------------------------
# chaos: seeded fault schedule, every request finishes exactly
# ---------------------------------------------------------------------------

CHAOS_PROMPTS = [LONG, MID, [7, 7, 2, 11], list(range(1, 14))]
CHAOS_SP = [None, SamplingParams(seed=5, temperature=0.9),
            None, SamplingParams(seed=11, temperature=0.7, top_p=0.8)]


def _chaos_refs(params, prompts, sps, max_new):
    lone = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    refs = []
    for p, sp in zip(prompts, sps):
        r = lone.submit(p, max_new_tokens=max_new, sampling=sp)
        lone.run_until_idle()
        refs.append(list(r.tokens))
    return refs


def test_chaos_one_replica_kill_no_token_loss(params):
    """Tier-1-sized chaos: a dispatch kill takes out replica 0 while
    every request is mid-stream. All requests finish with the exact
    uninterrupted outputs, streams carry no loss or duplication, and
    the finished traces stay gap-free."""
    refs = _chaos_refs(params, CHAOS_PROMPTS, CHAOS_SP, 12)
    fp = FaultPlan()
    r0 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                              faults=fp, tracing=1.0)
    r1 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                              tracing=1.0)
    router = ReplicatedRouter([r0, r1], breaker_threshold=2)
    streams = [[] for _ in CHAOS_PROMPTS]
    reqs = [router.submit(p, max_new_tokens=12, sampling=sp,
                          stream=st.append)
            for p, sp, st in zip(CHAOS_PROMPTS, CHAOS_SP, streams)]
    while min(len(r.tokens) for r in reqs) < 1:
        router.step()
    fp.arm("dispatch", count=1)  # kill replica 0 mid-stream
    _drive(router, reqs)

    for r, ref, st in zip(reqs, refs, streams):
        assert r.finish_reason == "length"
        assert list(r.tokens) == ref, "token mismatch after migration"
        assert st == ref, "stream lost or duplicated tokens"
    mstats = router.migration_stats()
    assert mstats["out_failed"] == 0
    assert mstats["out_started"] >= 1
    assert mstats["in_completed"] == mstats["out_started"]
    for t in router.trace_trees():
        if t["root"]["end"] is not None:
            _assert_gap_free(t)


@pytest.mark.slow
def test_chaos_soak_three_replicas(params):
    """Soak: seeded schedule over a 3-replica fleet — replica 0 dies
    mid-stream, then replica 1 dies AFTER absorbing migrations (so
    some requests migrate TWICE), while replica 2 rides out a
    transient allocation famine. Every request still finishes with
    the exact uninterrupted output, one gap-free trace chain each."""
    prompts = [[(i * k + 3) % 60 + 1 for i in range(4 + k)]
               for k in range(8)]
    sps = [None if k % 2 == 0 else
           SamplingParams(seed=100 + k, temperature=0.85, top_p=0.9)
           for k in range(8)]
    refs = _chaos_refs(params, prompts, sps, 24)

    fp0, fp1, fp2 = FaultPlan(), FaultPlan(), FaultPlan()
    fp2.arm("alloc_famine", count=2)
    servers = [PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                                    faults=fp, tracing=1.0)
               for fp in (fp0, fp1, fp2)]
    router = ReplicatedRouter(servers, breaker_threshold=2)
    streams = [[] for _ in prompts]
    reqs = [router.submit(p, max_new_tokens=24, sampling=sp,
                          stream=st.append)
            for p, sp, st in zip(prompts, sps, streams)]
    while min(len(r.tokens) for r in reqs) < 1:
        router.step()
    fp0.arm("dispatch", count=1)  # first casualty
    # wait until the fleet has absorbed replica 0's migrations, then
    # kill replica 1 too: any continuation it absorbed hops a SECOND
    # time, salvaged from the continuation handle's longer stream
    deadline = time.time() + 60
    while time.time() < deadline:
        router.step()
        time.sleep(0.001)
        if router.migration_stats()["in_completed"] >= 1:
            break
    fp1.arm("dispatch", count=1)  # second casualty
    _drive(router, reqs, deadline_s=180.0)

    for r, ref, st in zip(reqs, refs, streams):
        assert r.finish_reason == "length"
        assert list(r.tokens) == ref
        assert st == ref
    mstats = router.migration_stats()
    assert mstats["out_failed"] == 0
    assert mstats["in_completed"] == mstats["out_started"]
    assert mstats["out_started"] >= 2
    trees = router.trace_trees()
    for t in trees:
        if t["root"]["end"] is not None:
            _assert_gap_free(t)
    # each request's hop chain shares ONE trace id
    for r in reqs:
        chain = [t for t in trees
                 if t["request_id"] == r.request_id
                 or t["root"]["tags"].get("migrate_of") == r.request_id
                 or t["root"]["tags"].get("retry_of") == r.request_id]
        assert len({t["trace_id"] for t in chain}) == 1


# ---------------------------------------------------------------------------
# exactness under speculation (slow: extra compile)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_migration_exact_with_speculation(params):
    """Self-speculative decoding: greedy outputs are exact at ANY
    draft schedule, so a mid-stream hand-off between speculating
    servers must not move a single token."""
    kw = dict(SRV_KW, max_context=128, prompt_buckets=[16, 64])
    lone = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=2,
                                **kw)
    rep = [3, 4, 5, 6] * 5 + [3, 4]
    ref = lone.generate([rep], max_new_tokens=32)[0]

    r0 = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=2, **kw)
    r1 = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=2, **kw)
    stream = []
    req = r0.submit(rep, max_new_tokens=32, stream=stream.append)
    while len(req.tokens) < 5:
        r0.step()
    snap = r0.migrate_export(req)
    assert snap.n_kv_pages() >= 2
    cont = r1.migrate_import(snap, stream=stream.append)
    r1.run_until_idle()
    assert cont.done and cont.finish_reason == "length"
    assert list(cont.tokens) == ref
    assert stream == ref


# ---------------------------------------------------------------------------
# exactness under grammar constraints (slow: separate vocab/tokenizer)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_migration_exact_with_grammar():
    """Regex-constrained decoding: the destination re-derives the
    grammar walker state deterministically from the salvaged tokens,
    so the migrated stream is exact AND still matches the pattern."""
    from cloud_server_tpu.data.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    cfg = dataclasses.replace(CFG, vocab_size=300)
    icfg = InferConfig(max_decode_len=16, temperature=0.0,
                       eos_token_id=tok.eos_id, pad_token_id=0)
    kw = dict(max_slots=4, max_context=128, page_size=8,
              prefill_chunk=16, prompt_buckets=[16, 32], tokenizer=tok)
    params = transformer.init_params(cfg, jax.random.key(0))
    prompt = tok.encode("The year is ")
    sp = SamplingParams(regex=r"[0-9]{30,40}")

    lone = PagedInferenceServer(params, cfg, icfg, **kw)
    ref_req = lone.submit(prompt, max_new_tokens=48, sampling=sp)
    lone.run_until_idle()
    ref = list(ref_req.tokens)
    import re as _re
    body = ref[:-1] if ref and ref[-1] == tok.eos_id else ref
    assert _re.fullmatch(r"[0-9]{30,40}", tok.decode(body))

    r0 = PagedInferenceServer(params, cfg, icfg, **kw)
    r1 = PagedInferenceServer(params, cfg, icfg, **kw)
    stream = []
    req = r0.submit(prompt, max_new_tokens=48, sampling=sp,
                    stream=stream.append)
    while len(req.tokens) < 3:
        r0.step()
    snap = r0.migrate_export(req)
    cont = r1.migrate_import(snap, stream=stream.append)
    r1.run_until_idle()
    assert cont.done
    assert list(cont.tokens) == ref
    assert stream == ref


# ---- pure-host units: snapshot math, ledger accounting, fleet merge
# (no server, no jax dispatch — these run in milliseconds) ----


def _snap(**over):
    base = dict(
        version=MIGRATION_VERSION, request_id="r-1", reason="drain",
        prompt=(1, 2, 3), tokens=(7, 8), logprobs=(0.0, 0.0),
        emit_times=(0.0, 0.0), seed_used=17, sampling=None,
        adapter=None, tenant=None, slo_class=None, max_new_tokens=8,
        deadline_remaining_s=None, trace_ctx=None, chain_tokens=(),
        kv_pages=None)
    base.update(over)
    return MigrationSnapshot(**base)


def test_snapshot_budget_prompt_and_page_math():
    s = _snap()
    assert s.remaining_new_tokens() == 6
    assert s.full_prompt() == (1, 2, 3, 7, 8)
    # budget clamps at zero even if the stream somehow overran it
    assert _snap(tokens=tuple(range(8))).remaining_new_tokens() == 0
    assert _snap(tokens=tuple(range(11))).remaining_new_tokens() == 0
    # page count: salvage (None) and an empty pool dict are both zero;
    # otherwise pages ride axis 1 of every pool array
    assert _snap().n_kv_pages() == 0
    assert _snap(kv_pages={}).n_kv_pages() == 0
    pages = {"k0": np.zeros((2, 3, 8, 4)), "v0": np.zeros((2, 3, 8, 4))}
    assert _snap(kv_pages=pages).n_kv_pages() == 3


def test_snapshot_frozen_and_versioned():
    assert MIGRATION_VERSION == 1
    s = _snap()
    with pytest.raises(dataclasses.FrozenInstanceError):
        s.tokens = (9,)
    # replace() is the sanctioned way to build variants (the rejection
    # tests use it to forge a future version)
    s2 = dataclasses.replace(s, version=MIGRATION_VERSION + 1)
    assert s2.version == MIGRATION_VERSION + 1
    assert s2.tokens == s.tokens and s.version == MIGRATION_VERSION


def test_ledger_stats_totals():
    led = MigrationLedger()
    led.record_export_start()
    led.record_export_done(n_tokens=5, n_pages=2)
    led.record_export_start()
    led.record_export_failed()
    led.record_import_start()
    led.record_import_done()
    led.record_import_start()
    led.record_import_failed()
    st = led.stats()
    assert st["out_started"] == 2 and st["out_completed"] == 1
    assert st["out_failed"] == 1
    assert st["in_started"] == 2 and st["in_completed"] == 1
    assert st["in_failed"] == 1
    # the metric-family totals count BOTH halves
    assert st["started"] == 4 and st["completed"] == 2
    assert st["failed"] == 2
    assert st["tokens_salvaged"] == 5 and st["pages_moved"] == 2


def test_ledger_flight_deltas_consumed_once():
    led = MigrationLedger()
    assert led.drain_flight_deltas() == (0, 0)
    led.record_export_done(n_tokens=1, n_pages=0)
    led.record_import_done()
    led.record_import_done()
    # one flight-recorder read takes the deltas...
    assert led.drain_flight_deltas() == (2, 1)
    # ...and the next iteration starts from zero (cumulative stats
    # keep the totals)
    assert led.drain_flight_deltas() == (0, 0)
    assert led.stats()["in_completed"] == 2


def test_ledger_totals_exact_under_concurrency():
    led = MigrationLedger()
    n = 500

    def work():
        for _ in range(n):
            led.record_export_start()
            led.record_export_done(n_tokens=3, n_pages=1)
            led.record_import_done()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = led.stats()
    assert st["out_started"] == 4 * n == st["out_completed"]
    assert st["tokens_salvaged"] == 12 * n
    assert st["pages_moved"] == 4 * n
    fin, fout = led.drain_flight_deltas()
    assert (fin, fout) == (4 * n, 4 * n)


def test_router_migration_stats_skips_nonmigratable_replicas():
    class _Migratable:
        def submit(self, prompt, **kw):  # router probes the signature
            raise AssertionError("stats-only stub")

        def __init__(self, **kv):
            self._st = {k: 0 for k in (
                "out_started", "out_completed", "out_failed",
                "in_started", "in_completed", "in_failed", "started",
                "completed", "failed", "tokens_salvaged",
                "pages_moved")}
            self._st.update(kv)

        def migration_stats(self):
            return dict(self._st)

    class _Legacy:  # third-party backend without the method
        def submit(self, prompt, **kw):
            raise AssertionError("stats-only stub")

    router = ReplicatedRouter([
        _Migratable(out_started=4, out_completed=3, in_completed=2,
                    tokens_salvaged=11, pages_moved=5),
        _Legacy(),
        _Migratable(out_started=1, in_completed=2, in_failed=1),
    ])
    st = router.migration_stats()
    assert st["out_started"] == 5 and st["out_completed"] == 3
    assert st["in_completed"] == 4 and st["in_failed"] == 1
    assert st["tokens_salvaged"] == 11 and st["pages_moved"] == 5
    # ratio recomputes from the merged sums (never averaged)
    assert st["success_rate"] == pytest.approx(4 / 5)
    # a fleet that never exported divides by max(.., 1), not zero
    idle = ReplicatedRouter([_Legacy()]).migration_stats()
    assert idle["out_started"] == 0
    assert idle["success_rate"] == 0.0
