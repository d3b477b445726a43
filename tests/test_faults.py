"""Failure-domain layer (inference/faults.py + router failover):
deterministic fault injection, request deadlines, overload brownout,
circuit breakers, and the zero-token retry rule."""

import json
import threading
import time

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.faults import (BrownoutShedError,
                                               FaultPlan, InjectedFault,
                                               OverloadDetector,
                                               resolve_brownout,
                                               resolve_fault_plan)
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.request_trace import PHASES
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.inference.request import QueueFullError, Request
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
SRV_KW = dict(max_slots=2, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 64])
PROMPT = [5, 9, 3]


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


# ---------------------------------------------------------------------------
# FaultPlan unit
# ---------------------------------------------------------------------------


def test_fault_plan_windows_and_stats():
    plan = FaultPlan({"faults": [
        {"site": "dispatch", "after": 2, "count": 2}]})
    fired = [plan.fire("dispatch") is not None for _ in range(6)]
    # skips the first 2 hits, fires on the next 2, then exhausted
    assert fired == [False, False, True, True, False, False]
    st = plan.stats()
    assert st["hits"]["dispatch"] == 6
    assert st["fired"]["dispatch"] == 2
    assert st["fired"]["wedge"] == 0


def test_fault_plan_unlimited_and_runtime_arm():
    plan = FaultPlan()
    assert plan.fire("submit_reject") is None  # nothing armed
    plan.arm("submit_reject", count=0)        # <= 0: unlimited
    assert all(plan.fire("submit_reject") is not None
               for _ in range(5))
    # arm() windows count from the CURRENT hit count
    plan.arm("dispatch", after=1, count=1)
    assert plan.fire("dispatch") is None
    assert plan.fire("dispatch") is not None


def test_fault_plan_seeded_probability_reproduces():
    spec = {"seed": 7, "faults": [
        {"site": "dispatch", "count": 0, "p": 0.5}]}
    runs = []
    for _ in range(2):
        plan = FaultPlan(spec)
        runs.append([plan.fire("dispatch") is not None
                     for _ in range(40)])
    assert runs[0] == runs[1]          # same seed -> same firings
    assert any(runs[0]) and not all(runs[0])  # p really applied


def test_fault_plan_rejects_junk():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan({"faults": [{"site": "nope"}]})
    with pytest.raises(ValueError, match="unknown fault-plan keys"):
        FaultPlan({"bogus": 1})
    with pytest.raises(ValueError, match="p"):
        FaultPlan({"faults": [{"site": "dispatch", "p": 2.0}]})
    with pytest.raises(ValueError, match="after"):
        FaultPlan({"faults": [{"site": "dispatch", "after": -1}]})
    with pytest.raises(InjectedFault):
        plan = FaultPlan({"faults": [{"site": "dispatch"}]})
        plan.check("dispatch")


def test_resolve_fault_plan_forms(tmp_path):
    assert resolve_fault_plan(None, "") is None
    assert resolve_fault_plan(False, '{"faults": []}') is None
    spec = {"faults": [{"site": "dispatch"}]}
    assert resolve_fault_plan(json.dumps(spec)).fire("dispatch")
    path = tmp_path / "plan.json"
    path.write_text(json.dumps(spec))
    assert resolve_fault_plan(str(path)).fire("dispatch")
    ready = FaultPlan(spec)
    assert resolve_fault_plan(ready) is ready
    # InferConfig fallback string
    assert resolve_fault_plan(None, json.dumps(spec)).fire("dispatch")


# ---------------------------------------------------------------------------
# OverloadDetector unit
# ---------------------------------------------------------------------------


def _clock(start=100.0):
    state = {"t": start}

    def read():
        return state["t"]

    return state, read


def test_overload_levels_and_hysteresis():
    state, clock = _clock()
    det = OverloadDetector(
        {"pending_age_s": 1.0, "budget_utilization": 0.9,
         "host_gap_frac": 0.5, "alpha": 1.0, "hold_s": 5.0},
        clock=clock)
    assert det.observe() == 0
    # one signal over threshold -> level 1
    assert det.observe(budget_utilization=0.95) == 1
    # two signals -> level 2
    assert det.observe(budget_utilization=0.95,
                       pending_age_s=3.0) == 2
    # recovery: the level HOLDS for hold_s (hysteresis), then drops
    state["t"] += 1.0
    assert det.observe() == 2
    state["t"] += 5.0
    assert det.observe() == 0


def test_overload_shed_sets_and_counters():
    state, clock = _clock()
    det = OverloadDetector(
        {"budget_utilization": 0.5, "alpha": 1.0, "hold_s": 60.0},
        clock=clock)
    det.observe(budget_utilization=0.9)
    assert det.level() == 1
    assert det.shed("best_effort") is True
    assert det.shed("batch") is False       # level 1 sheds only be
    assert det.shed("interactive") is False
    det.observe(budget_utilization=0.9, pending_age_s=10.0)
    assert det.shed("batch") is True        # level 2 sheds batch too
    assert det.stats()["shed_total"] == {"best_effort": 1, "batch": 1}


def test_overload_level_decays_when_scheduler_goes_quiet():
    """A latched shed level must not refuse traffic forever once busy
    iterations (the observe() source) stop happening."""
    state, clock = _clock()
    det = OverloadDetector({"budget_utilization": 0.5, "alpha": 1.0,
                            "hold_s": 2.0}, clock=clock)
    det.observe(budget_utilization=1.0)
    assert det.level() == 1
    state["t"] += 3.0  # no observes for > hold_s: not overloaded
    assert det.level() == 0
    assert det.shed("best_effort") is False


def test_overload_retry_hint_jitter_bounds():
    det = OverloadDetector({"budget_utilization": 0.5, "alpha": 1.0,
                            "retry_after_s": 2.0, "jitter_frac": 0.5,
                            "hold_s": 60.0, "seed": 3})
    det.observe(budget_utilization=1.0)
    hints = [det.retry_hint() for _ in range(32)]
    assert all(2.0 <= h <= 3.0 for h in hints)  # base..base*(1+frac)
    assert len(set(hints)) > 1                  # jitter really applied
    # seeded: a same-seed detector reproduces the hint sequence
    det2 = OverloadDetector({"budget_utilization": 0.5, "alpha": 1.0,
                             "retry_after_s": 2.0, "jitter_frac": 0.5,
                             "hold_s": 60.0, "seed": 3})
    det2.observe(budget_utilization=1.0)
    assert [det2.retry_hint() for _ in range(32)] == hints


def test_brownout_config_validation():
    with pytest.raises(ValueError, match="unknown brownout"):
        OverloadDetector({"bogus": 1})
    with pytest.raises(ValueError, match="alpha"):
        OverloadDetector({"alpha": 0.0})
    assert resolve_brownout(None, "") is None
    assert resolve_brownout(False, '{"alpha": 0.5}') is None
    assert isinstance(resolve_brownout({"alpha": 0.5}),
                      OverloadDetector)


# ---------------------------------------------------------------------------
# Injection on live servers
# ---------------------------------------------------------------------------


def test_submit_reject_fires_once_then_recovers(params):
    fp = FaultPlan({"faults": [{"site": "submit_reject", "count": 1}]})
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, faults=fp)
    with pytest.raises(InjectedFault):
        srv.submit(PROMPT)
    out = srv.generate([PROMPT], max_new_tokens=4)
    assert len(out[0]) == 4
    snap = srv.metrics_snapshot()
    key = 'cloud_server_faults_injected_total{site="submit_reject"}'
    assert snap[key]["value"] == 1
    assert srv.fault_stats()["fired"]["submit_reject"] == 1


def test_alloc_famine_defers_admission(params):
    fp = FaultPlan()
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, faults=fp)
    warm = srv.submit(PROMPT, max_new_tokens=8)
    srv.step()
    assert srv.num_active == 1
    late = srv.submit([7, 2, 4], max_new_tokens=4)
    fp.arm("alloc_famine", count=1)
    srv.step()
    # the injected famine deferred the admission (nothing failed)
    assert late in list(srv._pending)
    assert late.finish_reason is None
    srv.step()  # famine was transient: admits normally now
    assert late not in list(srv._pending)
    srv.run_until_idle()
    assert warm.done and late.done
    assert len(late.tokens) == 4


def test_iteration_stall_injects_latency(params):
    fp = FaultPlan({"faults": [
        {"site": "iteration_stall", "count": 1, "stall_ms": 60}]})
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, faults=fp)
    t0 = time.perf_counter()
    srv.step()
    stalled = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.step()
    clean = time.perf_counter() - t0
    assert stalled >= 0.06
    assert clean < 0.06


def test_dispatch_fault_crashes_scheduler_and_fails_all(params):
    fp = FaultPlan()
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                               faults=fp).start()
    try:
        ok = srv.submit(PROMPT, max_new_tokens=4)
        assert ok.result(timeout=60) is not None
        fp.arm("dispatch", count=1)
        doomed = srv.submit(PROMPT, max_new_tokens=8)
        assert doomed._done.wait(timeout=60)
        assert doomed.finish_reason.startswith("error: InjectedFault")
        with pytest.raises(RuntimeError):
            doomed.result()
        # serve_forever died: the server refuses new work
        with pytest.raises(RuntimeError, match="stopped"):
            srv.submit(PROMPT)
    finally:
        srv.stop()


def test_wedge_blocks_scheduler_until_stop(params):
    fp = FaultPlan({"faults": [{"site": "wedge", "count": 1}]})
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                               faults=fp, decode_chunk=1).start()
    req = srv.submit(PROMPT, max_new_tokens=8)
    time.sleep(0.3)  # the scheduler is wedged inside step()
    assert req.tokens == [] and not req.done
    srv.stop()  # releases the wedge; leftovers are failed, not hung
    assert req.done
    assert srv._thread is None


def test_stop_waits_out_a_long_step(params):
    """stop() racing a step that outlasts the teardown's lock (on the
    chip: a step that compiles a new dispatch shape, 15 to 30 s) returns
    only when the scheduler thread has left it: the teardown is
    serialized, and pools the caller then drops are not written back by
    a late `self.state = ...` (the benchmark's check found them alive)."""
    fp = FaultPlan()
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                               faults=fp).start()
    assert srv._scheduler_join_timeout_s >= 120.0  # a compile and more
    srv._teardown_lock_timeout_s = 0.05
    req = srv.submit(PROMPT, max_new_tokens=8)
    assert req.result(timeout=60) is not None
    fp.arm("iteration_stall", count=1, stall_ms=600)
    late = srv.submit(PROMPT, max_new_tokens=8)
    time.sleep(0.2)  # the scheduler is inside the stalled step
    thread = srv._thread
    srv.stop()
    assert not thread.is_alive()
    assert srv.unserialized_teardowns == 0
    assert late.done
    srv.state = None
    time.sleep(0.7)  # past the stall's end
    assert srv.state is None


def test_unserialized_teardown_counter(params):
    """_fail_all against a WEDGED scheduler (step lock never released):
    the bounded acquire times out, teardown proceeds unserialized, and
    the event is counted instead of silent."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    req = srv.submit(PROMPT, max_new_tokens=8)
    srv.step()
    assert srv.num_active == 1
    srv._teardown_lock_timeout_s = 0.05
    assert srv._step_lock.acquire(timeout=5)  # wedge the scheduler
    try:
        srv._fail_all(RuntimeError("boom"))
    finally:
        srv._step_lock.release()
    assert srv.unserialized_teardowns == 1
    assert req.done and req.finish_reason.startswith("error")
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_unserialized_teardown_total"]["value"] == 1


# ---------------------------------------------------------------------------
# Request deadlines
# ---------------------------------------------------------------------------


def test_deadline_expires_pending_and_active(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    free0 = srv.allocator.stats().pages_free
    # pending expiry: never admitted
    queued = srv.submit(PROMPT, max_new_tokens=4, deadline_s=0.01)
    time.sleep(0.03)
    srv.step()
    assert queued.done and queued.finish_reason == "deadline"
    assert queued.tokens == []
    # active expiry: partial tokens survive, slot + pages release
    run = srv.submit(PROMPT, max_new_tokens=8, deadline_s=0.2)
    deadline = time.time() + 30
    while not run.tokens and time.time() < deadline:
        srv.step()
    assert run.tokens
    time.sleep(0.25)
    srv.step()
    assert run.done and run.finish_reason == "deadline"
    assert srv.num_active == 0
    stats = srv.allocator.stats()
    assert stats.pages_free + stats.pages_cached >= free0
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_deadline_expired_total"]["value"] == 2
    with pytest.raises(ValueError, match="deadline_s"):
        srv.submit(PROMPT, deadline_s=0.0)


def test_qos_class_default_deadline(params):
    qos = {"deadline_s": {"batch": 0.01},
           "tenants": {"bulk": {"priority": "batch"},
                       "fast": {"priority": "interactive"}}}
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW, qos=qos)
    bulk = srv.submit(PROMPT, max_new_tokens=4, tenant="bulk")
    fast = srv.submit(PROMPT, max_new_tokens=4, tenant="fast")
    assert bulk.deadline is not None
    assert fast.deadline is None  # class declares none
    # explicit deadline_s overrides the class default
    explicit = srv.submit(PROMPT, max_new_tokens=4, tenant="bulk",
                          deadline_s=30.0)
    assert explicit.deadline - explicit.submit_time > 1.0
    time.sleep(0.03)
    srv.run_until_idle()
    assert bulk.finish_reason == "deadline"
    assert fast.finish_reason == "length"
    assert explicit.finish_reason == "length"


def test_qos_deadline_config_validation():
    from cloud_server_tpu.inference.qos import TenantRegistry
    with pytest.raises(ValueError, match="unknown priority classes"):
        TenantRegistry({"deadline_s": {"nope": 1.0}})
    with pytest.raises(ValueError, match="must be > 0"):
        TenantRegistry({"deadline_s": {"batch": 0.0}})
    reg = TenantRegistry({"deadline_s": 5.0})
    assert reg.default_deadline(None) == 5.0


# ---------------------------------------------------------------------------
# Overload brownout on a live server
# ---------------------------------------------------------------------------


def test_brownout_sheds_low_classes_not_interactive(params):
    qos = {"tenants": {"inter": {"priority": "interactive"},
                       "bulk": {"priority": "batch"},
                       "scraper": {"priority": "best_effort"}}}
    # every busy iteration crosses both thresholds -> level 2
    brown = {"pending_age_s": 1e-9, "budget_utilization": 1e-9,
             "host_gap_frac": 10.0, "alpha": 1.0, "hold_s": 60.0,
             "retry_after_s": 0.5, "jitter_frac": 0.5}
    srv = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                               qos=qos, brownout=brown)
    keep = srv.submit(PROMPT, max_new_tokens=8, tenant="inter")
    queued = srv.submit(PROMPT, max_new_tokens=8, tenant="inter")
    queued2 = srv.submit(PROMPT, max_new_tokens=8, tenant="inter")
    srv.step()  # the fill: the head of the queue has waited
    assert srv.brownout_stats()["level"] == 1
    srv.step()  # a committed program: its budget's use is a signal too
    assert srv.brownout_stats()["level"] == 2
    with pytest.raises(BrownoutShedError) as ei:
        srv.submit(PROMPT, tenant="scraper")
    assert isinstance(ei.value, QueueFullError)  # HTTP 429 path
    assert ei.value.retry_after_s > 0
    assert ei.value.priority_class == "best_effort"
    with pytest.raises(BrownoutShedError):
        srv.submit(PROMPT, tenant="bulk")
    # interactive still admits while lower classes shed
    vip = srv.submit(PROMPT, max_new_tokens=4, tenant="inter")
    srv.run_until_idle()
    assert vip.done and keep.done and queued.done and queued2.done
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_brownout_level"]["value"] == 2
    assert snap[
        'cloud_server_brownout_shed_total{class="best_effort"}'][
            "value"] == 1
    assert snap[
        'cloud_server_brownout_shed_total{class="batch"}']["value"] == 1
    # flight records carry the level
    assert any(r.get("brownout_level") == 2
               for r in srv.flight_window())


def test_brownout_requires_qos(params):
    with pytest.raises(ValueError, match="QoS"):
        PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                             brownout={"alpha": 0.5})


# ---------------------------------------------------------------------------
# Router failover e2e (the acceptance scenario)
# ---------------------------------------------------------------------------


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


def test_router_failover_e2e(params):
    """Injected dispatch failure on replica 0 mid-flood: the breaker
    opens, the zero-token request retries and completes on replica 1
    with EXACT greedy output, the partially-streamed request is LIVE-
    MIGRATED (host state salvaged from the handle, resumed on replica
    1 at the exact next token — no token lost, none duplicated on its
    stream), and the trace trees stay gap-free across both hops."""
    long_prompt = [(k * 5) % 60 + 1 for k in range(40)]
    mid_prompt = [(k * 7) % 60 + 1 for k in range(8)]
    lone = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW)
    want = lone.generate([long_prompt], max_new_tokens=6)[0]
    want_a = lone.generate([mid_prompt], max_new_tokens=20)[0]

    fp = FaultPlan()
    r0 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                              faults=fp, tracing=1.0)
    r1 = PagedInferenceServer(params, CFG, GREEDY, **SRV_KW,
                              tracing=1.0)
    router = ReplicatedRouter([r0, r1], breaker_threshold=2,
                              breaker_reset_s=60.0)
    streamed = []
    # a: lands on replica 0 (least loaded, rotation 0) and streams
    # a couple of tokens -> MIGRATED after the crash
    a = router.submit(mid_prompt,
                      max_new_tokens=20, stream=streamed.append)
    while len(a.tokens) < 2:
        router.step()
    # keep replica 1 busier so b also lands on replica 0
    fillers = [r1.submit(PROMPT, max_new_tokens=20) for _ in range(2)]
    b = router.submit(long_prompt, max_new_tokens=6)
    assert b in list(r0._pending)
    router.step()  # b starts admission (40-token prompt, 16/chunk)
    assert b.tokens == []
    fp.arm("dispatch", count=1)  # next replica-0 dispatch raises
    deadline = time.time() + 60
    while not (b.done and a.done) and time.time() < deadline:
        router.step()
        time.sleep(0.001)
    # partially-streamed: live-migrated to replica 1 and completed
    # with the EXACT uninterrupted greedy stream — the tokens salvaged
    # before the crash plus the continuation, no loss, no duplication
    assert a.done and a.finish_reason == "length"
    assert a.tokens == want_a
    assert streamed == want_a
    # zero-token: retried and completed on replica 1, exact greedy
    assert b.done and b.finish_reason == "length"
    assert b.tokens == want
    # breaker opened on replica 0 (>= 2 consecutive failures)
    states = router.breaker_states()
    assert states[0]["state"] == "open"
    assert states[1]["state"] == "closed"
    snap = router.metrics_snapshot()
    assert snap["cloud_server_router_retries_total"]["value"] == 1
    assert snap["cloud_server_router_retry_success_total"][
        "value"] == 1
    assert snap["cloud_server_router_migrations_total"]["value"] == 1
    assert snap["cloud_server_router_migration_success_total"][
        "value"] == 1
    assert snap["cloud_server_migration_ms"]["count"] == 1
    mstats = router.migration_stats()
    assert mstats["out_completed"] == 1
    assert mstats["in_completed"] == 1
    assert mstats["success_rate"] == 1.0
    assert mstats["tokens_salvaged"] >= 2
    assert snap["cloud_server_router_breaker_open_total"]["value"] == 1
    assert snap['cloud_server_router_breaker_state{replica="0"}'][
        "value"] == 2
    # trace integrity across the hop: b's original tree and its retry
    # tree share ONE trace id; the retry tree carries a router_retry
    # span; every finished tree stays gap-free
    trees = router.trace_trees()
    b_trees = [t for t in trees
               if t["request_id"] == b.request_id
               or t["root"]["tags"].get("retry_of") == b.request_id]
    assert len(b_trees) == 2
    assert len({t["trace_id"] for t in b_trees}) == 1
    retry_tree = next(t for t in b_trees
                      if t["root"]["tags"].get("retry_of"))
    span_names = [c["name"] for c in retry_tree["root"]["children"]]
    assert "router_retry" in span_names
    # a's migration: one trace id across both replicas, the
    # continuation tree carries the `migrate` span with the hand-off
    # provenance
    a_trees = [t for t in trees
               if t["request_id"] == a.request_id
               or t["root"]["tags"].get("migrate_of") == a.request_id]
    assert len(a_trees) == 2
    assert len({t["trace_id"] for t in a_trees}) == 1
    mig_tree = next(t for t in a_trees
                    if t["root"]["tags"].get("migrate_of"))
    mig_spans = [c for c in mig_tree["root"]["children"]
                 if c["name"] == "migrate"]
    assert mig_spans
    assert mig_spans[0]["tags"]["reason"] == "failover"
    assert mig_spans[0]["tags"]["tokens_salvaged"] >= 2
    for t in trees:
        if t["root"]["end"] is not None:
            _assert_gap_free(t)
    for f in fillers:
        assert f.done


class _StubReplica:
    """Minimal router-compatible replica for hook-level tests."""

    def __init__(self):
        self.got = []
        self.ready = True
        self.num_active = 0

    @property
    def num_pending(self):
        return len(self.got)

    def submit(self, prompt, **kw):
        self.got.append((prompt, kw))
        return prompt


def _fail_hook(router, req, replica=0):
    """The closure a router submit would have planted on `req`."""
    return router._make_fail_hook(replica, req.prompt, {},
                                  frozenset(), None)(req)


def test_router_retry_stops_past_deadline():
    """The fail hook refuses to retry a request whose deadline has
    already passed — retrying cannot produce an in-deadline answer."""
    stub = _StubReplica()
    router = ReplicatedRouter([_StubReplica(), stub])
    req = Request(prompt=[1], max_new_tokens=4)
    req.finish_reason = "error: boom"
    req.deadline = time.perf_counter() - 1.0
    assert _fail_hook(router, req) is False
    assert stub.got == []
    # same request WITH headroom: the router takes ownership and the
    # retry hand-off reaches the healthy replica
    req2 = Request(prompt=[2], max_new_tokens=4)
    req2.finish_reason = "error: boom"
    req2.deadline = time.perf_counter() + 30.0
    assert _fail_hook(router, req2) is True
    assert req2._done.wait(timeout=10)
    retried = [g for r in router.replicas for g in r.got]
    assert [2] in [p for p, _ in retried]
    # the stub's submit returns a bare list (no completion surface),
    # so the hand-off completed the original with its standing error
    assert req2.finish_reason.startswith("error")


def test_router_retry_refuses_partial_stream():
    router = ReplicatedRouter([_StubReplica(), _StubReplica()])
    req = Request(prompt=[1], max_new_tokens=4)
    req.finish_reason = "error: boom"
    req.tokens = [11]  # one token already streamed
    assert _fail_hook(router, req) is False


def test_router_ignores_request_caused_errors():
    """An error the REQUEST caused (it can never fit the page pool)
    is neither retried nor counted against the replica's breaker —
    it would fail identically everywhere."""
    router = ReplicatedRouter([_StubReplica(), _StubReplica()],
                              breaker_threshold=1)
    req = Request(prompt=[1], max_new_tokens=4)
    req.finish_reason = ("error: request needs more pages than the "
                        "pool can ever provide")
    req._request_fault = True
    assert _fail_hook(router, req) is False
    assert router.breaker_states()[0]["state"] == "closed"
    assert router.breaker_states()[0]["consecutive_failures"] == 0


def test_impossible_request_marked_request_fault(params):
    """The paged server's pool-can-never-fit failure carries the
    _request_fault marker the router's no-retry rule keys on (and
    completes OUTSIDE the state lock — the ABBA-deadlock fix)."""
    srv = PagedInferenceServer(params, CFG, GREEDY, max_slots=2,
                               max_context=64, page_size=8,
                               prefill_chunk=16, prompt_buckets=[16, 64],
                               num_pages=4)
    doomed = srv.submit([(k * 3) % 60 + 1 for k in range(40)],
                        max_new_tokens=4)
    srv.step()
    assert doomed.done
    assert doomed.finish_reason.startswith("error: request needs")
    assert doomed._request_fault is True


# ---------------------------------------------------------------------------
# HTTP layer: retriable error bodies + the X-Deadline-S header
# ---------------------------------------------------------------------------


class _FakeBackend:
    """Stub serving backend for HTTP-shape tests: streams
    `emit_before_fail` tokens, then fails the request."""

    def __init__(self, emit_before_fail):
        self.emit_before_fail = emit_before_fail
        self.deadlines = []
        self.num_active = 0
        self.num_pending = 0
        self.ready = True

    def submit(self, tokens, max_new_tokens=None, stream=None,
               sampling=None, deadline_s=None, **kw):
        self.deadlines.append(deadline_s)
        req = Request(prompt=list(tokens),
                      max_new_tokens=max_new_tokens or 4,
                      stream=stream, submit_time=time.perf_counter())

        def run():
            for _ in range(self.emit_before_fail):
                req.tokens.append(7)
                req.emit_times.append(time.perf_counter())
                if stream is not None:
                    stream(7)
            req.finish_reason = "error: replica exploded"
            req._done.set()

        threading.Thread(target=run, daemon=True).start()
        return req


def _post_generate(front, body, headers=None):
    import urllib.request as urq
    host, port = front.address
    r = urq.Request(f"http://{host}:{port}/generate",
                    data=json.dumps(body).encode(),
                    headers=headers or {})
    try:
        with urq.urlopen(r, timeout=30) as resp:
            return resp.status, resp.read().decode()
    except urq.HTTPError as exc:
        return exc.code, exc.read().decode()


def test_http_stream_failure_retriable_flags():
    from cloud_server_tpu.inference.http_server import HttpFrontend
    # one token streamed before the failure: retriable MUST be false
    srv = _FakeBackend(emit_before_fail=1)
    front = HttpFrontend(srv).start()
    try:
        status, text = _post_generate(front, {"tokens": [1, 2]})
        lines = [json.loads(ln) for ln in text.strip().splitlines()]
        assert status == 200  # headers were sent before the failure
        assert lines[0] == {"token": 7}
        assert lines[-1]["error"].startswith("error")
        assert lines[-1]["retriable"] is False
    finally:
        front.stop()
    # zero tokens streamed: safe for the client to resubmit
    srv = _FakeBackend(emit_before_fail=0)
    front = HttpFrontend(srv).start()
    try:
        _, text = _post_generate(front, {"tokens": [1, 2]})
        last = json.loads(text.strip().splitlines()[-1])
        assert last["retriable"] is True
    finally:
        front.stop()


def test_http_deadline_header_threads_and_validates():
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = _FakeBackend(emit_before_fail=0)
    front = HttpFrontend(srv).start()
    try:
        _post_generate(front, {"tokens": [1]},
                       headers={"X-Deadline-S": "2.5"})
        assert srv.deadlines[-1] == 2.5
        # absent header -> backend sees no deadline kwarg
        _post_generate(front, {"tokens": [1]})
        assert srv.deadlines[-1] is None
        status, text = _post_generate(
            front, {"tokens": [1]}, headers={"X-Deadline-S": "junk"})
        assert status == 400
        assert "X-Deadline-S" in json.loads(text)["error"]
        status, _ = _post_generate(
            front, {"tokens": [1]}, headers={"X-Deadline-S": "-1"})
        assert status == 400
        # NaN compares False both ways — it must not slip through as
        # a silent never-expiring deadline
        status, _ = _post_generate(
            front, {"tokens": [1]}, headers={"X-Deadline-S": "nan"})
        assert status == 400
        status, _ = _post_generate(
            front, {"tokens": [1]}, headers={"X-Deadline-S": "inf"})
        assert status == 400
    finally:
        front.stop()
