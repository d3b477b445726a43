"""Anomaly watchdog + tail-based trace retention + forensic bundles:
rule hysteresis (activation edge, hold window, warm-up suppression,
wedged lazy grading) with injected clocks and hand-computed
thresholds, the tail-retention predicate clause by clause (exactly
once under duplicate finishes, bounded eviction), fleet stat merging,
and the live-server surface (auto-captured bundles, /debug/bundle,
/stats blocks, unconfigured parity)."""

import json
import urllib.request

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.anomaly import (
    RULES, AnomalyWatchdog, merge_anomaly_stats, resolve_anomaly)
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.request_trace import (
    TAIL_REASONS, RequestTrace, TraceRecorder, resolve_recorder)
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
PAGED_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
                prompt_buckets=[16, 48])


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


# ---------------------------------------------------------------------------
# watchdog: config resolution + validation
# ---------------------------------------------------------------------------


def test_resolve_anomaly_paths(tmp_path):
    assert resolve_anomaly(None, "") is None
    assert resolve_anomaly(False, '{"warmup": 1}') is None  # force-off
    wd = AnomalyWatchdog({"warmup": 7})
    assert resolve_anomaly(wd, "") is wd
    assert resolve_anomaly({"warmup": 7}, "").warmup == 7
    assert resolve_anomaly('{"warmup": 7}', "").warmup == 7
    # config-string fallback (the InferConfig.anomaly_config chain)
    assert resolve_anomaly(None, '{"warmup": 7}').warmup == 7
    p = tmp_path / "anomaly.json"
    p.write_text('{"hold_s": 2.5}')
    assert resolve_anomaly(str(p), "").hold_s == 2.5


def test_watchdog_config_validation():
    for bad in ({"bogus_key": 1},
                {"rules": {"bogus_rule": {}}},
                {"rules": {"host_gap": {"bogus_th": 1.0}}},
                {"disable": ["bogus_rule"]},
                {"hold_s": -1.0},
                {"check_every": 0},
                {"event_capacity": 0},
                {"alpha_fast": 0.0},
                {"alpha_slow": 1.5}):
        with pytest.raises(ValueError):
            AnomalyWatchdog(bad)
    wd = AnomalyWatchdog({"disable": ["cache_collapse"],
                          "rules": {"host_gap": {"factor": 5.0}}})
    assert wd._enabled["cache_collapse"] is False
    assert wd._th["host_gap"]["factor"] == 5.0
    # defaults of OTHER rules untouched by a partial override
    assert wd._th["host_gap"]["min_frac"] == 0.2
    assert wd._th["wedged"]["stall_s"] == 10.0


# ---------------------------------------------------------------------------
# watchdog: rule hysteresis with injected clocks (test_slo.py style)
# ---------------------------------------------------------------------------


def _quiet_iters(wd, n, *, start=0.0, dt=0.01, gap=0.05):
    """Feed n healthy iterations (tiny host gap) starting at `start`."""
    for i in range(n):
        wd.observe_iteration(now=start + i * dt, host_gap_frac=gap)
    return start + n * dt


def test_host_gap_activation_edge_and_hold():
    """host_gap: fires on the first iteration whose fast-EWMA exceeds
    factor x slow baseline (and min_frac), counts the WINDOW once, and
    deactivates only after hold_s of continuous recovery."""
    wd = AnomalyWatchdog({"warmup": 4, "check_every": 1, "hold_s": 5.0,
                          "alpha_fast": 1.0, "alpha_slow": 0.001})
    t = _quiet_iters(wd, 10)  # baseline slow EWMA ~0.05
    assert wd.active(t) == ()
    # regression: fast jumps to 0.9 (alpha_fast=1.0 -> fast == sample),
    # slow barely moves -> fast > 2.0 * slow and > min_frac 0.2
    fired = wd.observe_iteration(now=t, host_gap_frac=0.9)
    assert fired == ("host_gap",)
    assert wd.fired_total["host_gap"] == 1
    assert wd.active(t) == ("host_gap",)
    # still firing: no re-activation, the one window stays open
    assert wd.observe_iteration(now=t + 1.0, host_gap_frac=0.9) == ()
    assert wd.fired_total["host_gap"] == 1
    # recovery shorter than hold_s: window held open (hysteresis)
    wd.observe_iteration(now=t + 2.0, host_gap_frac=0.01)
    assert wd.active(t + 2.0) == ("host_gap",)
    # hold_s of continuous recovery: deactivates, end stamped
    wd.observe_iteration(now=t + 7.1, host_gap_frac=0.01)
    assert wd.active(t + 7.1) == ()
    (ev,) = wd.events()
    assert ev["rule"] == "host_gap"
    assert ev["end"] == t + 7.1
    assert ev["details"]["fast"] == pytest.approx(0.9)
    # a fresh regression opens a SECOND window (new event, count 2)
    wd.observe_iteration(now=t + 8.0, host_gap_frac=0.9)
    assert wd.fired_total["host_gap"] == 2
    assert len(wd.events()) == 2


def test_warmup_suppresses_cold_ewma():
    """The same regression inside the warm-up never fires: cold EWMAs
    prime to the first sample, so ratios are meaningless early."""
    wd = AnomalyWatchdog({"warmup": 32, "check_every": 1,
                          "alpha_fast": 1.0, "alpha_slow": 0.001})
    _quiet_iters(wd, 10)
    assert wd.observe_iteration(now=0.2, host_gap_frac=0.9) == ()
    assert wd.fired_total["host_gap"] == 0


def test_latency_shift_on_request_finish():
    """latency_shift via observe_request: a TTFT spike 3x above its
    slow baseline fires once; values under min_s never do."""
    wd = AnomalyWatchdog({"warmup": 4, "hold_s": 5.0,
                          "alpha_fast": 1.0, "alpha_slow": 0.001})
    for i in range(8):  # healthy baseline ~0.1 s
        wd.observe_request(now=float(i), ttft_s=0.1, itl_s=0.01)
    fired = wd.observe_request(now=10.0, ttft_s=0.9)
    assert fired == ("latency_shift",)
    (ev,) = wd.events()
    assert ev["details"]["metric"] == "ttft"
    # sub-min_s shifts are noise by definition: a 10x jump that stays
    # under 0.05 s absolute must not fire
    wd2 = AnomalyWatchdog({"warmup": 2, "alpha_fast": 1.0,
                           "alpha_slow": 0.001})
    for i in range(6):
        wd2.observe_request(now=float(i), ttft_s=0.001)
    assert wd2.observe_request(now=9.0, ttft_s=0.04) == ()


def test_deadline_spike_window_prunes():
    """deadline_spike: >= count expiries inside window_s fires; the
    same expiries spread past the window never do."""
    cfg = {"warmup": 0, "hold_s": 0.0,
           "rules": {"deadline_spike": {"count": 3, "window_s": 10.0}}}
    wd = AnomalyWatchdog(cfg)
    assert wd.observe_request(now=100.0, finish_reason="deadline") == ()
    assert wd.observe_request(now=101.0, finish_reason="deadline") == ()
    assert wd.observe_request(
        now=102.0, finish_reason="deadline") == ("deadline_spike",)
    # spread past the window: the prune drops the old timestamps
    wd2 = AnomalyWatchdog(cfg)
    for t in (100.0, 111.0, 122.0):
        assert wd2.observe_request(now=t, finish_reason="deadline") == ()
    # non-deadline finishes never count
    wd3 = AnomalyWatchdog(cfg)
    for t in (100.0, 100.1, 100.2, 100.3):
        assert wd3.observe_request(now=t, finish_reason="length") == ()


def test_preempt_and_breaker_flap_windows():
    wd = AnomalyWatchdog({"warmup": 0, "hold_s": 0.0, "check_every": 1,
                          "rules": {"preempt_spike":
                                    {"count": 4, "window_s": 10.0},
                                    "breaker_flap":
                                    {"flaps": 2, "window_s": 10.0}}})
    assert wd.observe_iteration(now=100.0, preempt_delta=3) == ()
    assert wd.observe_iteration(
        now=101.0, preempt_delta=1) == ("preempt_spike",)
    # windowed sum prunes: 11 s later only the newest delta remains
    wd.observe_iteration(now=112.0, preempt_delta=1)
    assert wd._preempt_sum == 1
    # breaker_flap counts level CHANGES, not levels: 0->1->0 inside
    # the window is two flaps
    wd2 = AnomalyWatchdog({"warmup": 0, "hold_s": 0.0, "check_every": 1,
                           "rules": {"breaker_flap":
                                     {"flaps": 2, "window_s": 10.0}}})
    wd2.observe_iteration(now=100.0, overload_level=0)  # primes level
    wd2.observe_iteration(now=101.0, overload_level=1)
    assert wd2.observe_iteration(
        now=102.0, overload_level=0) == ("breaker_flap",)
    # a steady elevated level is NOT flapping
    wd3 = AnomalyWatchdog({"warmup": 0, "check_every": 1,
                           "rules": {"breaker_flap":
                                     {"flaps": 2, "window_s": 10.0}}})
    for t in (100.0, 101.0, 102.0, 103.0):
        assert wd3.observe_iteration(now=t, overload_level=2) == ()


def test_wedged_lazy_grading_and_immediate_close():
    """wedged is graded on the READ path (a wedged scheduler cannot
    grade itself) and closes the moment an iteration is observed —
    no hold (the stall IS over)."""
    wd = AnomalyWatchdog({"warmup": 0, "check_every": 1, "hold_s": 99.0,
                          "rules": {"wedged": {"stall_s": 10.0}}})
    wd.observe_iteration(now=100.0, pending=3)
    assert wd.active(105.0) == ()          # not stalled yet
    assert wd.active(111.0) == ("wedged",)  # 11 s silent, work pending
    assert wd.fired_total["wedged"] == 1
    assert wd.active_count(112.0) == 1
    # the next observed iteration closes it immediately despite hold_s
    wd.observe_iteration(now=113.0, pending=3)
    assert wd.active(113.0) == ()
    (ev,) = wd.events()
    assert ev["end"] == 113.0
    # idle stall (nothing pending) is NOT wedged
    wd2 = AnomalyWatchdog({"warmup": 0, "check_every": 1,
                           "rules": {"wedged": {"stall_s": 10.0}}})
    wd2.observe_iteration(now=100.0, pending=0)
    assert wd2.active(200.0) == ()


def test_disable_and_event_ring_bounds():
    wd = AnomalyWatchdog({"warmup": 0, "check_every": 1, "hold_s": 0.0,
                          "event_capacity": 3,
                          "disable": ["host_gap"],
                          "rules": {"preempt_spike":
                                    {"count": 1, "window_s": 0.5}}})
    # disabled rule never fires even on a blatant regression
    _quiet_iters(wd, 5, gap=0.01)
    assert wd.observe_iteration(now=1.0, host_gap_frac=0.99) == ()
    # five disjoint preempt-spike windows -> ring keeps newest 3
    for i in range(5):
        t = 10.0 + i * 2.0
        assert wd.observe_iteration(
            now=t, preempt_delta=1) == ("preempt_spike",)
        wd.observe_iteration(now=t + 1.0)  # window closes (hold 0)
    assert wd.fired_total["preempt_spike"] == 5
    assert len(wd.events()) == 3
    assert wd.events(1)[0]["start"] == 18.0
    assert wd.events(0) == []  # n <= 0 means none, the /stats rule
    st = wd.stats()
    assert set(st) == {"active", "fired_total", "signals", "events"}
    assert set(st["fired_total"]) == set(RULES)


def test_merge_anomaly_stats():
    assert merge_anomaly_stats([]) is None
    assert merge_anomaly_stats([None, None]) is None
    a = {"active": ["host_gap"], "fired_total": {"host_gap": 2},
         "events": [{"rule": "host_gap", "start": 5.0}]}
    b = {"active": ["wedged"], "fired_total": {"host_gap": 1,
                                               "wedged": 1},
         "events": [{"rule": "wedged", "start": 3.0,
                     "replica": 7}]}  # pre-tagged: existing tag wins
    m = merge_anomaly_stats([a, None, b])
    assert m["active"] == ["host_gap", "wedged"]
    assert m["fired_total"] == {"host_gap": 3, "wedged": 1}
    assert [e["start"] for e in m["events"]] == [3.0, 5.0]  # by start
    assert m["events"][0]["replica"] == 7
    assert m["events"][1]["replica"] == 0


# ---------------------------------------------------------------------------
# tail-based trace retention: the predicate, clause by clause
# ---------------------------------------------------------------------------


class _Req:
    def __init__(self, rid, finish_reason="length", preempts=0):
        self.request_id = rid
        self.trace = None
        self.submit_time = 0.0
        self.tenant = None
        self.finish_reason = finish_reason
        self.tokens = []
        self.emit_times = []
        self._events = ([("submit", 0.0)]
                        + [("preempt_requeue", 0.1 * (i + 1))
                           for i in range(preempts)]
                        + [(f"finish:{finish_reason}", 1.0)])

    def timeline(self):
        return list(self._events)


def _finish_one(rec, req, **kw):
    assert rec.begin(req) is None  # head-unsampled at rate 0
    assert req.trace is None and req.tail_trace is not None
    rec.finish(req, **kw)


def test_tail_predicate_reasons():
    """Each TAIL_REASONS clause retains; a clean finish drops."""
    rec = TraceRecorder(sample_rate=0.0, tail_capacity=16)
    cases = [
        (_Req("r-err", finish_reason="error:boom"), {}, "failed"),
        (_Req("r-dead", finish_reason="deadline"), {}, "deadline"),
        (_Req("r-can", finish_reason="cancelled"), {}, "cancelled"),
        (_Req("r-mig", finish_reason="migrated"), {}, "migrated"),
        (_Req("r-slo"), {"slo_violated": True}, "slo"),
        (_Req("r-pre", preempts=2), {}, "preempt"),
        (_Req("r-ano"), {"in_anomaly": True}, "anomaly"),
    ]
    for req, kw, want in cases:
        _finish_one(rec, req, **kw)
        tree = rec.lookup(req.request_id)
        assert tree is not None, want
        assert tree["root"]["tags"]["tail_retained"] == want
    assert {w for _, _, w in cases} == set(TAIL_REASONS)  # full cover
    assert sum(rec.tail_retained.values()) == len(cases)
    # clean finish: graded and dropped (also: one preempt < min of 2)
    for req in (_Req("r-ok"), _Req("r-pre1", preempts=1)):
        _finish_one(rec, req)
        assert rec.lookup(req.request_id) is None
    assert sum(rec.tail_retained.values()) == len(cases)
    assert len(rec.tail_trees()) == len(cases)
    assert rec.tail_trees(0) == [] and rec.tail_trees(-1) == []
    st = rec.tail_stats()
    assert st["capacity"] == 16 and st["retained"] == len(cases)


def test_tail_predicate_priority_and_router_tags():
    """The FIRST matching clause names the retention (terminal reason
    beats router tags beats slo), and the failover/handoff tags the
    router stamps on provisional trees retain as `migrated`."""
    rec = TraceRecorder(sample_rate=0.0, tail_capacity=16)
    req = _Req("r-both", finish_reason="deadline")
    rec.begin(req)
    req.tail_trace.annotate(retry_of="r-orig")
    rec.finish(req, slo_violated=True)
    assert rec.lookup("r-both")["root"]["tags"]["tail_retained"] \
        == "deadline"
    for tag in ("handoff_of", "migrate_of", "retry_of", "migrated_out"):
        r = _Req(f"r-{tag}")
        rec.begin(r)
        r.tail_trace.annotate(**{tag: "r-orig"})
        rec.finish(r)
        assert rec.lookup(r.request_id)["root"]["tags"][
            "tail_retained"] == "migrated"


def test_tail_exactly_once_and_eviction():
    rec = TraceRecorder(sample_rate=0.0, tail_capacity=2)
    req = _Req("r-dup", finish_reason="deadline")
    rec.begin(req)
    rec.finish(req)
    rec.finish(req)  # racing duplicate finish: retained once
    assert rec.tail_retained["deadline"] == 1
    assert len(rec.tail_trees()) == 1
    for i in range(3):
        _finish_one(rec, _Req(f"r-{i}", finish_reason="cancelled"))
    assert rec.tail_evicted_total == 2  # bounded ring: oldest out
    assert rec.lookup("r-dup") is None
    assert rec.lookup("r-2") is not None
    assert rec.tail_stats()["retained"] == 2


def test_tail_constructor_and_resolver():
    with pytest.raises(ValueError):
        TraceRecorder(tail_capacity=-1)
    with pytest.raises(ValueError):
        TraceRecorder(tail_capacity=4, tail_preempt_min=0)
    # tail-only mode: rate 0 still builds a recorder when a tail ring
    # is configured — the "broken requests always inspectable" mode
    rec = resolve_recorder(None, 0.0, tail_capacity=8)
    assert rec is not None and rec.tail_capacity == 8
    assert resolve_recorder(None, 0.0, tail_capacity=0) is None
    assert resolve_recorder(False, 1.0, tail_capacity=8) is None
    # tail off: unsampled requests get NO provisional trace at all
    rec2 = TraceRecorder(sample_rate=0.0, tail_capacity=0)
    req = _Req("r-no-tail", finish_reason="deadline")
    assert rec2.begin(req) is None
    assert getattr(req, "tail_trace", None) is None
    rec2.finish(req)
    assert rec2.lookup("r-no-tail") is None


def test_continuation_ctx_prefers_head_then_tail():
    from cloud_server_tpu.inference.request_trace import (
        any_trace, continuation_ctx)
    req = _Req("r-ctx")
    assert any_trace(req) is None and continuation_ctx(req) is None
    req.tail_trace = RequestTrace("r-ctx", "ab" * 16, None)
    assert any_trace(req) is req.tail_trace
    tid, psid, sampled = continuation_ctx(req)
    assert (tid, psid) == (req.tail_trace.trace_id,
                           req.tail_trace.root_span_id)
    assert sampled is False  # continuation stays head-unsampled
    req.trace = RequestTrace("r-ctx", "cd" * 16, None)
    assert any_trace(req) is req.trace
    assert continuation_ctx(req)[2] is True


# ---------------------------------------------------------------------------
# live servers: watchdog fires, bundle auto-captures, HTTP surface
# ---------------------------------------------------------------------------

# deadline_spike at count 1 with zero warm-up: ONE deadline-expired
# finish is the whole incident — deterministic to provoke in-test
_TRIGGER_CFG = {"warmup": 0, "check_every": 1, "hold_s": 0.0,
                "rules": {"deadline_spike":
                          {"count": 1, "window_s": 3600.0}}}
_FORENSIC_ICFG = InferConfig(
    max_decode_len=8, temperature=0.0, eos_token_id=-1, pad_token_id=0,
    trace_tail_capacity=8, bundle_on_anomaly=True)


def _run_deadline_incident(srv):
    ok = srv.submit([5, 9, 3], max_new_tokens=6)
    dead = srv.submit([7, 7, 2], max_new_tokens=64, deadline_s=1e-4)
    srv.run_until_idle()
    assert ok.done and dead.finish_reason == "deadline"
    return ok, dead


def test_watchdog_fires_and_bundle_autocaptures(params):
    srv = PagedInferenceServer(params, CFG, _FORENSIC_ICFG,
                               tracing=0.0, anomaly=_TRIGGER_CFG,
                               **PAGED_KW)
    ok, dead = _run_deadline_incident(srv)
    # the watchdog latched the incident...
    astats = srv.anomaly_stats()
    assert astats["fired_total"]["deadline_spike"] == 1
    assert astats["events"][0]["rule"] == "deadline_spike"
    # ...the expired request's tree tail-retained despite 0% head
    # sampling (a clean request finishing INSIDE the still-open window
    # may legitimately retain as "anomaly" — forensic context)...
    assert srv.trace_trees() == []
    trees = {t["request_id"]: t for t in srv.tail_trace_trees()}
    assert trees[dead.request_id]["root"]["tags"][
        "tail_retained"] == "deadline"
    assert srv.tail_trace_stats()["retained_total"]["deadline"] == 1
    # ...and ONE bundle auto-captured on the activation edge, carrying
    # the evidence
    (bundle,) = srv.debug_bundles()
    assert bundle["schema"] == "cloud_server.debug_bundle/v1"
    assert bundle["trigger"] == "anomaly:deadline_spike"
    assert bundle["anomaly"]["fired_total"]["deadline_spike"] == 1
    # captured ON the edge: the triggering request's own retention
    # lands just after, so the ring block is present but may predate it
    assert set(bundle["tail_retention"]) == {
        "capacity", "retained", "retained_total", "evicted_total"}
    assert isinstance(bundle["flight"], list)
    assert "cache" in bundle
    assert isinstance(bundle["metrics"], dict)
    # metric families mirror the same counts
    snap = srv.metrics_snapshot()
    assert snap[
        'cloud_server_anomalies_total{rule="deadline_spike"}'][
            "value"] == 1
    assert snap["cloud_server_trace_tail_retained_total"]["value"] \
        == len(trees)
    assert snap["cloud_server_anomaly_bundles_total"]["value"] == 1
    # a manual bundle works regardless of auto-capture
    assert srv.debug_bundle()["trigger"] == "manual"


def test_unconfigured_parity(params):
    """Without anomaly/tail config the full surface reads empty and
    the metric families still exist at zero (stable catalog)."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=4)
    srv.run_until_idle()
    assert srv.anomaly_stats() is None
    assert srv.anomaly_events() == []
    assert srv.tail_trace_trees() == []
    assert srv.tail_trace_stats() is None
    assert srv.debug_bundles() == []
    snap = srv.metrics_snapshot()
    for rule in RULES:
        assert snap[
            f'cloud_server_anomaly_active{{rule="{rule}"}}'][
                "value"] == 0.0
    assert snap["cloud_server_trace_tail_retained_total"]["value"] == 0
    assert snap["cloud_server_anomaly_bundles_total"]["value"] == 0


def test_router_merges_fleet_forensics(params):
    """Behind the router: anomaly stats merge with events tagged by
    TRUE replica index (even when only one replica has a watchdog),
    tail trees and bundles are replica-tagged, and the fleet bundle
    carries the router-only breaker/role blocks."""
    plain = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    armed = PagedInferenceServer(params, CFG, _FORENSIC_ICFG,
                                 tracing=0.0, anomaly=_TRIGGER_CFG,
                                 **PAGED_KW)
    router = ReplicatedRouter([plain, armed])
    ok = router.submit([5, 9, 3], max_new_tokens=4)
    dead = armed.submit([7, 7, 2], max_new_tokens=64, deadline_s=1e-4)
    while not (ok.done and dead.done):
        router.step()
    m = router.anomaly_stats()
    assert m["fired_total"]["deadline_spike"] == 1
    assert m["events"][0]["replica"] == 1  # true index, not filtered
    assert router.anomaly_events()[0]["replica"] == 1
    trees = {t["request_id"]: t for t in router.tail_trace_trees()}
    tree = trees[dead.request_id]
    assert tree["root"]["tags"]["replica"] == 1
    assert tree["root"]["tags"]["tail_retained"] == "deadline"
    assert router.tail_trace_stats()["retained_total"]["deadline"] == 1
    (b,) = router.debug_bundles()
    assert b["replica"] == 1
    fleet = router.debug_bundle()
    assert fleet["schema"] == "cloud_server.debug_bundle/v1"
    assert "breakers" in fleet and "roles" in fleet
    assert fleet["anomaly"]["fired_total"]["deadline_spike"] == 1


def test_http_bundle_and_stats_blocks(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, _FORENSIC_ICFG,
                               tracing=0.0, anomaly=_TRIGGER_CFG,
                               **PAGED_KW).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address
        _run_deadline_incident(srv)

        def get(path):
            with urllib.request.urlopen(
                    f"http://{host}:{port}{path}", timeout=30) as resp:
                return json.loads(resp.read())

        stats = get("/stats?n=8")
        assert stats["anomaly"]["fired_total"]["deadline_spike"] == 1
        assert stats["tail_retention"]["retained_total"][
            "deadline"] == 1
        # fresh bundle vs the auto-captured ring
        bundle = get("/debug/bundle?n=4")
        assert bundle["schema"] == "cloud_server.debug_bundle/v1"
        assert bundle["trigger"] == "manual"
        ring = get("/debug/bundle?ring=4")
        assert len(ring["bundles"]) == 1
        assert ring["bundles"][0]["trigger"] \
            == "anomaly:deadline_spike"
        # /traces carries the tail-retained tree + the anomaly marker
        # track (instant events in the Perfetto export)
        traces = get("/traces?n=16")
        names = {ev.get("name") for ev in traces["traceEvents"]}
        assert "anomaly:deadline_spike" in names
    finally:
        front.stop()
        srv.stop()


def test_http_bundle_404_without_support(params):
    """A backend without debug_bundle (e.g. a bare object) returns
    404, matching the other optional endpoints' contract."""
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address
        # unconfigured server still serves a (mostly-empty) bundle —
        # the endpoint exists whenever the backend does
        with urllib.request.urlopen(
                f"http://{host}:{port}/debug/bundle", timeout=30) as r:
            assert json.loads(r.read())["anomaly"] is None
    finally:
        front.stop()
        srv.stop()
