"""Serving observability: metrics registry semantics (bucket edges,
merge, rendering), request-lifecycle timestamp monotonicity across
finish/cancel/preempt paths, router snapshot merging,
the flight recorder, docs-catalog drift, and the dispatch-count
regression guard (instrumentation must add zero dispatches/syncs)."""

import io
import json
import pathlib
import re
import time
import urllib.request

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import transformer
from cloud_server_tpu.utils.logging import JsonLogger
from cloud_server_tpu.utils.serving_metrics import (
    FlightRecorder, Histogram, MetricsRegistry, histogram_percentile,
    merge_snapshots, render_prometheus)

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
# registry primitives
# ---------------------------------------------------------------------------


def test_histogram_bucket_edges():
    """`le` semantics: a value exactly on an edge lands in that bucket;
    above the top edge lands in the overflow bucket."""
    h = Histogram("cloud_server_x_seconds", "", buckets=(0.001, 0.01, 1.0))
    for v in (0.0005, 0.001, 0.0011, 0.01, 0.5, 1.0, 2.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["counts"] == [2, 2, 2, 1]  # per-bucket, overflow last
    assert snap["count"] == 7
    assert snap["sum"] == pytest.approx(sum(
        (0.0005, 0.001, 0.0011, 0.01, 0.5, 1.0, 2.0)))
    with pytest.raises(ValueError):
        Histogram("cloud_server_bad", "", buckets=(1.0, 0.5))  # unsorted


def test_histogram_merge_and_mismatch():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    for r, vals in ((r1, (0.002, 0.2)), (r2, (0.002, 5.0, 200.0))):
        h = r.histogram("lat_seconds", "h")
        for v in vals:
            h.observe(v)
        r.counter("things_total", "c").inc(2)
        r.gauge("depth", "g").set(3)
    merged = merge_snapshots([r1.snapshot(), r2.snapshot()])
    h = merged["cloud_server_lat_seconds"]
    assert h["count"] == 5 and h["sum"] == pytest.approx(205.204)
    assert merged["cloud_server_things_total"]["value"] == 4
    assert merged["cloud_server_depth"]["value"] == 6
    bad = MetricsRegistry()
    bad.histogram("lat_seconds", "h", buckets=(1.0, 2.0)).observe(1.5)
    with pytest.raises(ValueError):
        merge_snapshots([r1.snapshot(), bad.snapshot()])


def test_histogram_percentile_interpolation():
    h = Histogram("cloud_server_p", "", buckets=(1.0, 2.0, 4.0))
    for v in [0.5] * 50 + [3.0] * 50:  # half in (0,1], half in (2,4]
        h.observe(v)
    snap = h.snapshot()
    assert histogram_percentile(snap, 0.25) == pytest.approx(0.5)
    assert histogram_percentile(snap, 0.75) == pytest.approx(3.0)
    assert histogram_percentile(snap, 1.0) == pytest.approx(4.0)
    assert histogram_percentile({"count": 0, "counts": [], "buckets": [],
                                 "sum": 0.0}, 0.5) == 0.0


def test_registry_namespace_and_type_conflict():
    r = MetricsRegistry()
    c = r.counter("foo_total", "f")
    assert c.name == "cloud_server_foo_total"
    assert r.counter("cloud_server_foo_total") is c  # get-or-create
    with pytest.raises(ValueError):
        r.gauge("foo_total")  # same name, different type


def test_render_prometheus_wellformed():
    r = MetricsRegistry()
    r.counter("a_total", "A").inc(3)
    r.gauge("b", "B").set(1.5)
    h = r.histogram("c_seconds", "C", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(10.0)
    text = render_prometheus(r.snapshot())
    _assert_exposition_wellformed(text)
    lines = text.splitlines()
    assert 'cloud_server_c_seconds_bucket{le="0.1"} 1' in lines
    assert 'cloud_server_c_seconds_bucket{le="+Inf"} 2' in lines
    assert "cloud_server_c_seconds_count 2" in lines


def test_render_prometheus_groups_families_contiguously():
    """The exposition format wants every series of a family in one
    group. A raw key sort interleaves (`foo_bar` sorts between `foo`
    and `foo{...}` because "_" < "{"), so the renderer must group by
    FAMILY — and do so regardless of snapshot dict ordering."""
    snap = {  # adversarial order AND adversarial names
        'cloud_server_foo{tenant="a"}':
            {"type": "gauge", "help": "F", "value": 1.0},
        "cloud_server_foo_bar":
            {"type": "gauge", "help": "FB", "value": 2.0},
        "cloud_server_foo":
            {"type": "gauge", "help": "F", "value": 3.0},
    }
    text = render_prometheus(snap)
    _assert_exposition_wellformed(text)
    fams = [ln.split("{")[0].rsplit(" ", 1)[0].strip()
            for ln in text.splitlines()
            if ln and not ln.startswith("#")]
    prev, seen = None, set()
    for f in fams:
        if f != prev:
            assert f not in seen, f"family {f} split by another family"
            seen.add(f)
            prev = f


def _assert_exposition_wellformed(text: str) -> None:
    """Every series has exactly one HELP and one TYPE line and no
    sample name repeats (histogram buckets aside, which must be
    cumulative and end at +Inf == _count)."""
    helps, types, samples = set(), set(), []
    for ln in text.splitlines():
        if ln.startswith("# HELP "):
            name = ln.split()[2]
            assert name not in helps, f"duplicate HELP for {name}"
            helps.add(name)
        elif ln.startswith("# TYPE "):
            name = ln.split()[2]
            assert name not in types, f"duplicate TYPE for {name}"
            types.add(name)
        elif ln:
            samples.append(ln)
    assert helps == types
    seen = set()
    for ln in samples:
        series = ln.rsplit(" ", 1)[0]
        assert series not in seen, f"duplicate sample {series}"
        seen.add(series)
        base = series.split("{")[0]
        base = re.sub(r"_(bucket|sum|count)$", "", base) \
            if base.endswith(("_bucket", "_sum", "_count")) else base
        assert base in types or series.split("{")[0] in types, series


def test_flight_recorder_ring():
    fr = FlightRecorder(4)
    for i in range(10):
        fr.record(x=i)
    assert len(fr) == 4 and fr.iterations == 10
    assert [rec["x"] for rec in fr.window()] == [6, 7, 8, 9]
    assert [rec["x"] for rec in fr.window(2)] == [8, 9]
    assert [rec["iteration"] for rec in fr.window(2)] == [9, 10]
    with pytest.raises(ValueError):
        FlightRecorder(0)


# ---------------------------------------------------------------------------
# lifecycle monotonicity (finish/cancel/preempt)
# ---------------------------------------------------------------------------


def _check_monotonic(req, *, expect=()):
    ev = req.timeline()
    names = [n for n, _ in ev]
    times = [t for _, t in ev]
    assert times == sorted(times), f"non-monotonic timeline: {ev}"
    assert names[0] == "submit"
    assert sum(n.startswith("finish:") for n in names) == 1
    assert names[-1].startswith("finish:")
    for name in expect:
        assert any(n == name or n.startswith(name) for n in names), \
            f"missing {name} in {names}"
    if "first_token" in names:
        i_admit = names.index("admit")
        i_ft = names.index("first_token")
        assert i_admit < i_ft
        assert req.submit_time <= times[i_admit] <= times[i_ft]


def test_lifecycle_monotonic_finish(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    reqs = [srv.submit([5, 9, 3], max_new_tokens=4),
            srv.submit([7, 7, 2, 1], max_new_tokens=4)]
    srv.run_until_idle()
    for r in reqs:
        _check_monotonic(r, expect=("admit", "first_token",
                                    "finish:length"))
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_ttft_seconds"]["count"] == 2
    assert snap["cloud_server_queue_wait_seconds"]["count"] == 2
    assert snap["cloud_server_e2e_seconds"]["count"] == 2
    # 4 tokens per request -> 3 inter-token gaps each
    assert snap["cloud_server_itl_seconds"]["count"] == 6
    assert snap["cloud_server_requests_finished_total"]["value"] == 2


def test_lifecycle_monotonic_cancel(params):
    srv = PagedInferenceServer(params, CFG, GREEDY,
                               **{**PAGED_KW, "max_slots": 1})
    active = srv.submit([5, 9, 3], max_new_tokens=8)
    queued = srv.submit([8, 1, 1], max_new_tokens=8)
    queued.cancel()  # still pending: finishes immediately
    _check_monotonic(queued, expect=("finish:cancelled",))
    assert "admit" not in [n for n, _ in queued.timeline()]
    srv.step()
    active.cancel()  # holds a slot: reaped by the next step's sweep
    srv.run_until_idle()
    _check_monotonic(active, expect=("admit", "finish:cancelled"))
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_requests_cancelled_total"]["value"] == 2
    assert snap["cloud_server_e2e_seconds"]["count"] == 2


def test_lifecycle_monotonic_preempt_requeue(params):
    """On-demand page famine preempts the youngest slot; its request's
    timeline shows requeue + re-admission, still monotonic, and the
    requeue counter matches the server's preemption count."""
    prompts = [[(i * 9 + k) % 60 + 1 for k in range(8)] for i in range(6)]
    srv = PagedInferenceServer(
        params, CFG, GREEDY, allocation="ondemand", max_slots=6,
        max_context=64, page_size=8, prefill_chunk=16,
        prompt_buckets=[16], num_pages=12, decode_chunk=2)
    reqs = [srv.submit(p, max_new_tokens=40) for p in prompts]
    srv.run_until_idle()
    assert srv.preemptions > 0
    preempted = [r for r in reqs
                 if any(n == "preempt_requeue" for n, _ in r.timeline())]
    assert preempted
    for r in preempted:
        _check_monotonic(r, expect=("admit", "preempt_requeue",
                                    "finish:length"))
        names = [n for n, _ in r.timeline()]
        # requeued requests are re-admitted: admit appears again after
        # the preempt_requeue event
        assert names.index("preempt_requeue") < len(names) - 1 - \
            names[::-1].index("admit")
    snap = srv.metrics_snapshot()
    assert (snap["cloud_server_preempt_requeues_total"]["value"]
            == srv.preemptions)
    # queue-wait observed once per request (first admission only)
    assert snap["cloud_server_queue_wait_seconds"]["count"] == len(reqs)


# ---------------------------------------------------------------------------
# dispatch-count regression: instrumentation adds no dispatches/syncs
# ---------------------------------------------------------------------------


_TRACING_SLO_KW = {
    "tracing": 1.0,
    "slo": {"windows_s": [10, 60],
            "classes": {"default": {"objective": 0.99, "ttft_s": 30.0,
                                    "itl_s": 30.0, "queue_wait_s": 30.0,
                                    "e2e_s": 120.0}}}}


_QOS_CACHE_KW = {"qos": {"tenants": {"a": {}, "b": {}}}}

# failure-domain clone: a FaultPlan armed but never firing (after is
# astronomically far) plus a live brownout detector — the THREADING
# must add zero dispatches/syncs even when enabled. (The unconfigured
# case — no FaultPlan at all — is the `plain` clone, unchanged.)
_FAULTS_BROWNOUT_KW = {
    "faults": {"seed": 0,
               "faults": [{"site": "dispatch", "after": 10 ** 9}]},
    "brownout": {"alpha": 0.3},
    "qos": {"tenants": {"a": {}, "b": {}}}}

# anomaly+tail clone: an armed-but-quiet watchdog (every rule enabled
# with astronomically far thresholds, graded every iteration past a
# zero warmup — the hardest observe path) plus tail-based trace
# retention at 0% head sampling. The watchdog feed and the provisional
# tail trees must add zero dispatches/syncs. (Unconfigured — no
# watchdog, no tail ring — is the `plain` clone, unchanged.)


def _anomaly_tail_kw():
    from cloud_server_tpu.inference.request_trace import TraceRecorder
    return {
        "tracing": TraceRecorder(sample_rate=0.0, tail_capacity=64),
        "anomaly": {"warmup": 0, "check_every": 1,
                    "rules": {"latency_shift": {"factor": 1e9},
                              "cache_collapse": {"min_baseline": 2.0},
                              "breaker_flap": {"flaps": 10 ** 9},
                              "deadline_spike": {"count": 10 ** 9},
                              "preempt_spike": {"count": 10 ** 9},
                              "host_gap": {"factor": 1e9},
                              "wedged": {"stall_s": 1e9}}}}


@pytest.mark.parametrize("extra_kw",
                         [{}, _TRACING_SLO_KW, _QOS_CACHE_KW,
                          _FAULTS_BROWNOUT_KW, _anomaly_tail_kw()],
                         ids=["plain", "tracing_slo", "qos_cache",
                              "faults_brownout", "anomaly_tail"])
def test_mixed_step_dispatch_and_sync_count(params, monkeypatch,
                                            extra_kw):
    """The instrumented mixed-scheduler iteration still issues exactly
    ONE fused dispatch and ONE host sync per step while admissions are
    in flight — the telemetry observes timestamps the scheduler already
    had, it never adds device work. The `tracing_slo` clone runs the
    SAME invariant with per-request tracing at 100% head sampling AND
    SLO tracking enabled: span recording and burn-rate accounting are
    host-side list/int work on already-owned timestamps, zero
    dispatches or syncs. The `qos_cache` clone runs it with a
    multi-tenant registry live, so the per-tenant CACHE attribution
    path (cache_telemetry record hooks inside every allocator
    lookup/alloc/release) is pinned to zero added dispatches/syncs
    too.

    Under the (default) async scheduler a steady-state step issues
    exactly ONE fused dispatch — `_mixed_step` while the planned frame
    has prefill work, else the decode/spec program on the
    kind-transition step — and ONE device_get (the previous launch's
    commit), so the counter wraps all three dispatch entry points."""
    from cloud_server_tpu.inference import paged_server as ps
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW, **extra_kw)
    warm = srv.submit([5, 9, 3, 1], max_new_tokens=24)
    srv.step()  # warm decode running before the long prompt lands
    assert srv.num_active == 1

    calls = {"dispatch": 0, "mixed": 0, "get": 0}
    origs = {n: getattr(ps, n) for n in
             ("_mixed_step", "_decode_rounds", "_spec_rounds")}
    orig_get = jax.device_get

    def wrap(name):
        def w(*a, **k):
            calls["dispatch"] += 1
            if name == "_mixed_step":
                calls["mixed"] += 1
            return origs[name](*a, **k)
        return w

    def get_wrap(x):
        calls["get"] += 1
        return orig_get(x)

    for n in origs:
        monkeypatch.setattr(ps, n, wrap(n))
    monkeypatch.setattr(jax, "device_get", get_wrap)

    long = srv.submit([(k * 7) % 60 + 1 for k in range(40)],
                      max_new_tokens=4)
    churn_steps = 0
    while srv._jobs or srv.num_pending:
        before = dict(calls)
        srv.step()
        churn_steps += 1
        assert calls["dispatch"] - before["dispatch"] == 1, \
            "mixed iteration must stay ONE fused dispatch"
        assert calls["get"] - before["get"] == 1, \
            "mixed iteration must stay ONE host sync"
        assert churn_steps < 50
    # 40-token remainder over 16-token chunks: admission spans >1 fused
    # iteration, so the invariant was tested under real churn — and
    # the fused program really carried the prefill half
    assert churn_steps >= 2
    assert calls["mixed"] >= 2
    for n, f in origs.items():
        monkeypatch.setattr(ps, n, f)
    monkeypatch.setattr(jax, "device_get", orig_get)
    srv.run_until_idle()
    assert warm.done and long.done
    assert srv.metrics_snapshot()[
        "cloud_server_requests_finished_total"]["value"] == 2
    if "slo" in extra_kw:  # the clone really ran with both live
        assert len(srv.trace_trees()) == 2
        assert srv.slo_report()["classes"]["default"]["metrics"][
            "e2e"]["lifetime"]["total"] == 2
    if "anomaly" in extra_kw:  # armed, observed every iteration, quiet
        astats = srv.anomaly_stats()
        # host_gap EWMA is folded on every observed iteration, so its
        # presence proves the watchdog feed really ran in the loop
        assert "host_gap" in astats["signals"]
        assert astats["active"] == []
        assert sum(astats["fired_total"].values()) == 0
        # tail ring live but empty: both requests finished cleanly, so
        # their provisional trees were graded and dropped
        tstats = srv.tail_trace_stats()
        assert tstats["capacity"] == 64
        assert tstats["retained"] == 0
        assert srv.tail_trace_trees() == []
        assert srv.trace_trees() == []  # 0% head sampling held
    if "qos" in extra_kw:  # the cache-attribution path really ran
        cs = srv.cache_stats()
        assert cs["tenants"]  # walks were recorded per tenant
        assert (cs["pool"]["pages_free"] + cs["pool"]["pages_cached"]
                + cs["pool"]["pages_active"]
                == cs["pool"]["pages_total"])


# ---------------------------------------------------------------------------
# flight recorder on a live server
# ---------------------------------------------------------------------------


def test_flight_recorder_records_mixed_iterations(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, flight_recorder_size=3, **PAGED_KW)
    for i in range(3):
        srv.submit([5 + i, 9, 3], max_new_tokens=4)
    srv.run_until_idle()
    window = srv.flight_window()
    assert 0 < len(window) <= 3  # ring bounded by flight_recorder_size
    assert srv.flight.iterations >= len(window)
    for rec in window:
        assert "scheduler" not in rec
        assert rec["budget_tokens"] == srv.mixed_token_budget
        if rec.get("fill"):  # it launched; the next record is its program's
            assert rec["tokens_scheduled"] == 0
            continue
        assert rec["tokens_scheduled"] > 0
        assert 0 < rec["budget_utilization"] <= 1.0
        assert rec["budget_tokens"] == srv.mixed_token_budget
        assert 0 < rec["compaction_ratio"] <= 1.0
        assert rec["duration_ms"] >= 0


# ---------------------------------------------------------------------------
# router snapshot merging
# ---------------------------------------------------------------------------


def test_router_snapshot_merge(params):
    replicas = [PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
                for _ in range(2)]
    router = ReplicatedRouter(replicas)
    reqs = [router.submit([5 + i, 9, 3], max_new_tokens=4)
            for i in range(4)]
    router.run_until_idle()
    assert all(r.done for r in reqs)
    # least-loaded placement spread the 4 submits over both replicas
    per_replica = [rep.metrics_snapshot()[
        "cloud_server_requests_finished_total"]["value"]
        for rep in replicas]
    assert all(v > 0 for v in per_replica)
    merged = router.metrics_snapshot()
    assert merged["cloud_server_requests_finished_total"]["value"] == 4
    assert merged["cloud_server_ttft_seconds"]["count"] == 4
    # fleet histogram counts = sum of replica bucket counts
    rep_counts = [rep.metrics_snapshot()["cloud_server_ttft_seconds"]
                  for rep in replicas]
    want = [a + b for a, b in zip(rep_counts[0]["counts"],
                                  rep_counts[1]["counts"])]
    assert merged["cloud_server_ttft_seconds"]["counts"] == want
    text = render_prometheus(merged)
    _assert_exposition_wellformed(text)


def test_router_flight_window(params):
    replicas = [PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
                for _ in range(2)]
    router = ReplicatedRouter(replicas)
    for i in range(4):
        router.submit([5 + i, 9, 3], max_new_tokens=3)
    router.run_until_idle()
    window = router.flight_window(8)
    assert window
    assert {rec["replica"] for rec in window} == {0, 1}
    ts = [rec["ts"] for rec in window]
    assert ts == sorted(ts)


# ---------------------------------------------------------------------------
# HTTP surface: /metrics well-formedness, access log, /debug/trace
# ---------------------------------------------------------------------------


@pytest.fixture()
def frontend(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW).start()
    log_stream = io.StringIO()
    front = HttpFrontend(srv, access_log=JsonLogger(
        stream=log_stream)).start()
    yield front, srv, log_stream
    front.stop()
    srv.stop()


def _get(front, path: str):
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=60) as resp:
        return resp.read().decode()


def test_metrics_exposition_wellformed_over_http(frontend):
    front, srv, _ = frontend
    srv.submit([5, 9, 3], max_new_tokens=3)
    srv.run_until_idle()
    text = _get(front, "/metrics")
    _assert_exposition_wellformed(text)
    assert "cloud_server_ttft_seconds_bucket" in text
    assert "cloud_server_pages_free" in text
    # KV-cache & memory families (cache_telemetry.py) ride the same
    # exposition: eager-registered histograms + allocator counters
    assert "cloud_server_cache_chain_depth_pages_bucket" in text
    assert "cloud_server_pool_evictable_frac_bucket" in text
    assert "cloud_server_prefix_hit_tokens_total" in text
    # /debug/cache is well-formed JSON over the same backend
    cache = json.loads(_get(front, "/debug/cache"))
    assert set(cache) >= {"pool", "prefix", "tenants", "top_prefixes",
                          "recent_evictions", "eviction_matrix"}


def test_access_log_records(frontend):
    front, _, log_stream = frontend
    _get(front, "/healthz")
    _get(front, "/metrics")
    # the client's read completes when the body arrives, which is
    # BEFORE the handler's finally-block writes the access record —
    # poll (bounded) instead of racing the server thread
    deadline = time.perf_counter() + 5.0
    while True:
        records = [json.loads(ln) for ln in
                   log_stream.getvalue().splitlines() if ln]
        access = [r for r in records if r.get("event") == "access"]
        if {r["path"] for r in access} >= {"/healthz", "/metrics"}:
            break
        assert time.perf_counter() < deadline, (
            "access records never appeared: "
            f"{sorted(r['path'] for r in access)}")
        time.sleep(0.01)
    for r in access:
        assert r["method"] == "GET" and r["status"] == 200
        assert r["duration_ms"] >= 0 and r["request_id"]


def test_debug_trace_endpoint(frontend, tmp_path):
    front, srv, _ = frontend
    host, port = front.address
    logdir = str(tmp_path / "trace")
    req = urllib.request.Request(
        f"http://{host}:{port}/debug/trace",
        data=json.dumps({"steps": 2, "logdir": logdir}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        out = json.loads(resp.read())
    assert out["ok"] is True and out["logdir"] == logdir
    srv.submit([5, 9, 3], max_new_tokens=4)
    srv.run_until_idle()  # >= 2 iterations: capture opened and closed
    assert not srv.tracer.active
    assert list(pathlib.Path(logdir).rglob("*")), \
        "trace capture wrote nothing"
    # the tracer is reusable once the previous window closed
    srv.request_trace(1, str(tmp_path / "trace2"))
    srv.submit([5, 9], max_new_tokens=2)
    srv.run_until_idle()
    assert not srv.tracer.active


# ---------------------------------------------------------------------------
# docs catalog drift check
# ---------------------------------------------------------------------------


def test_metric_catalog_matches_docs(params):
    """Every metric name registered at runtime appears in
    docs/observability.md's catalog tables, and vice versa — the
    catalog cannot rot in either direction. Tenant-labeled series
    (multi-tenant QoS) are cataloged by their FAMILY name, so the
    label suffix is stripped before comparing; the server runs with
    a QoS config so the per-tenant families register."""
    doc = (pathlib.Path(__file__).resolve().parents[1]
           / "docs" / "observability.md").read_text()
    catalog = set(re.findall(r"^\|\s*`(cloud_server_[a-z0-9_]+)`", doc,
                             re.M))
    # qos + slo so the per-tenant AND per-class labeled families
    # register (labeled series are cataloged by family name)
    paged = PagedInferenceServer(params, CFG, GREEDY,
                                 qos={"tenants": {"a": {}}},
                                 slo=_TRACING_SLO_KW["slo"], **PAGED_KW)
    # behind a router so the cloud_server_router_* families (failover/
    # retry/breaker counters + breaker-state gauges) register too
    from cloud_server_tpu.inference.router import ReplicatedRouter
    router = ReplicatedRouter([paged])
    # an autoscaler over the router (its cloud_server_autoscaler_*
    # families register eagerly into the router registry) and a replay
    # driver (cloud_server_scenario_*) — the scenario-harness families
    # are part of the catalog contract too
    from cloud_server_tpu.scenarios import ReplayDriver, SLOBurnAutoscaler
    SLOBurnAutoscaler(router, spawn=lambda role: None)
    driver = ReplayDriver(router, [])
    runtime = {name.split("{")[0] for name in
               set(router.metrics_snapshot())
               | set(driver.metrics_snapshot())}
    missing_from_docs = runtime - catalog
    stale_in_docs = catalog - runtime
    assert not missing_from_docs, (
        f"registered at runtime but absent from docs/observability.md: "
        f"{sorted(missing_from_docs)}")
    assert not stale_in_docs, (
        f"documented but never registered at runtime: "
        f"{sorted(stale_in_docs)}")
