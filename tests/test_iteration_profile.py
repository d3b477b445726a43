"""Iteration-phase profiler: phase-clock semantics, flight-record
phase splits (host_ms + device_wait_ms == duration_ms), the overhead
guard (the profiling-enabled mixed iteration stays ONE dispatch / ONE
sync, with a bounded CONSTANT number of profiler clock reads), the
/debug/scheduler_trace Perfetto export and its cross-link to request
span trees by iteration index, idle-iteration visibility, and the
fleet merge of the per-phase histograms."""

import json
import urllib.error
import urllib.request

import jax
import pytest
from serial_order import waits

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import iteration_profile as ip
from cloud_server_tpu.inference.iteration_profile import (
    OVERLAP_PHASES, PHASES, IterationProfiler, derive_gap_fields,
    profile_summary, resolve_profiler, scheduler_chrome_trace)
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
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
# phase-clock semantics (no server, injected clock)
# ---------------------------------------------------------------------------


def test_profiler_phases_accumulate_and_partition(monkeypatch):
    """enter(phase) is a boundary: the time since the previous one goes
    to the phase that was open; a phase entered again ACCUMULATES;
    entering the open phase reads no clock; the per-phase sum equals
    the span from t0 to end() exactly (no time dropped or
    double-counted)."""
    ticks = iter([10.0, 10.5, 11.0, 14.0, 14.25, 15.25, 15.5])
    monkeypatch.setattr(ip, "perf_counter", lambda: next(ticks))
    p = IterationProfiler()
    assert p.begin() == 10.0 and p.t0 == 10.0
    assert p.enter("sweep") == 10.0  # already open: no boundary
    p.enter("build")                # sweep 0.5 s
    p.enter("device")               # build 0.5 s
    p.enter("build")                # device 3.0 s
    assert p.enter("build") == 14.0
    p.enter("device")               # 0.25 s more build (accumulates)
    p.enter("commit")               # 1.0 s more device
    last = p.end()                  # commit 0.25 s
    phases = p.phases_ms()
    assert list(phases) == ["sweep", "build", "device", "commit"]
    assert phases["build"] == pytest.approx(750.0)
    assert phases["device"] == pytest.approx(4000.0)
    assert sum(phases.values()) == pytest.approx((last - p.t0) * 1e3)
    # begin() resets for the next iteration, open in its first phase
    ticks2 = iter([20.0, 21.0])
    monkeypatch.setattr(ip, "perf_counter", lambda: next(ticks2))
    p.begin()
    p.end()
    assert p.phases_ms() == {"sweep": pytest.approx(1000.0)}


def test_profiler_boundaries_are_trace_events():
    """Handed an annotation, every open phase is one `sched/<phase>`
    event carrying the iteration's index, inside one `sched/iteration`
    that takes the index only when the step records (`end`); `close`
    ends a step that recorded nothing, and a `begin` after a step that
    raised closes what it left open."""
    log = []

    class Span:
        def __init__(self, name, **stats):
            self.name, self.stats = name, dict(stats)

        def __enter__(self):
            log.append(("open", self.name, dict(self.stats)))

        def __exit__(self, *exc):
            log.append(("close", self.name, dict(self.stats)))

        def set_metadata(self, **stats):
            self.stats.update(stats)

    p = IterationProfiler(Span)
    p.begin(7)
    p.enter("admission")
    p.enter("admission")
    p.enter("device")
    p.end()
    assert log == [
        ("open", "sched/iteration", {}),
        ("open", "sched/sweep", {"iteration": 7}),
        ("close", "sched/sweep", {"iteration": 7}),
        ("open", "sched/admission", {"iteration": 7}),
        ("close", "sched/admission", {"iteration": 7}),
        ("open", "sched/device", {"iteration": 7}),
        ("close", "sched/device", {"iteration": 7}),
        ("close", "sched/iteration", {"iteration": 7})]
    assert set(ip._PHASE_EVENTS) == set(PHASES)
    del log[:]
    p.begin(8)
    p.close()   # an idle step
    p.close()   # nothing left open: a no-op
    assert [e[:2] for e in log] == [
        ("open", "sched/iteration"), ("open", "sched/sweep"),
        ("close", "sched/sweep"), ("close", "sched/iteration")]
    assert log[-1][2] == {}
    del log[:]
    p.begin(8)
    p.enter("build")        # ... and the step raises here
    p.begin(8)
    assert [e[:2] for e in log[-4:]] == [
        ("close", "sched/build"), ("close", "sched/iteration"),
        ("open", "sched/iteration"), ("open", "sched/sweep")]


def test_between_ms_is_the_time_from_an_end_to_the_next_begin(monkeypatch):
    """`begin()` after a busy step's `end()` keeps the difference of
    the two reads they already make; after a `close()` (an idle step,
    a wait for work, a step that raised) and on the first step there is
    none. No read of its own: the ticks below are all there are."""
    ticks = iter([10.0, 10.5,          # busy step: begin, end
                  10.75, 11.0,         # busy: between 0.25 s
                  11.5,                # idle step (close): between 0.5 s
                  12.0, 12.5,          # busy after an idle one: none
                  13.0, 13.25])        # busy after a wait for work: none
    monkeypatch.setattr(ip, "perf_counter", lambda: next(ticks))
    p = IterationProfiler()
    p.begin()
    assert p.between_ms is None        # the first step
    p.end()
    p.begin()
    assert p.between_ms == pytest.approx(250.0)
    p.end()
    p.begin()
    assert p.between_ms == pytest.approx(500.0)
    p.close()                          # recorded nothing
    p.begin()
    assert p.between_ms is None
    p.end()
    p.close()                          # the loop goes to wait for work
    p.begin()
    assert p.between_ms is None
    p.end()
    assert next(ticks, None) is None   # every tick was a begin or an end


def test_lap_reads_the_clock_and_moves_no_boundary(monkeypatch):
    ticks = iter([1.0, 2.0, 3.0, 4.0])
    monkeypatch.setattr(ip, "perf_counter", lambda: next(ticks))
    p = IterationProfiler()
    p.begin()
    assert (p.lap(), p.lap()) == (2.0, 3.0)
    p.end()
    assert p.phases_ms() == {"sweep": pytest.approx(3000.0)}


def test_derive_gap_fields():
    d = derive_gap_fields({"sweep": 1.0, "admission": 2.0, "device": 6.0,
                           "commit": 1.0}, 10.0)
    assert d["overlap_ms"] == pytest.approx(3.0)
    assert d["host_ms"] == pytest.approx(1.0)
    assert d["device_wait_ms"] == pytest.approx(6.0)
    assert d["host_gap_frac"] == pytest.approx(0.1)
    assert derive_gap_fields({}, 0.0)["host_gap_frac"] == 0.0


def test_derive_gap_fields_overlapped_hides_plan_and_deliver():
    """An overlapped iteration's planning ran under the program before
    the commit and its delivery under the one launched after it: both
    are `overlap_ms`; the serialized tail is commit, launch, epilogue."""
    phases = {"sweep": 0.5, "admission": 1.5, "build": 3.0, "device": 30.0,
              "commit": 2.0, "launch": 4.0, "deliver": 6.0, "epilogue": 1.0}
    d = derive_gap_fields(phases, 48.0)
    assert d["overlap_ms"] == pytest.approx(11.0)
    assert d["host_ms"] == pytest.approx(7.0)
    assert d["device_wait_ms"] == pytest.approx(30.0)
    assert d["host_ms"] + d["device_wait_ms"] + d["overlap_ms"] \
        == pytest.approx(48.0)
    assert d["host_gap_frac"] == pytest.approx(7.0 / 48.0)


def test_resolve_profiler_forms():
    assert resolve_profiler(False) is None
    assert resolve_profiler("off") is None
    assert resolve_profiler(None, cfg_enabled=False) is None
    assert isinstance(resolve_profiler(None, cfg_enabled=True),
                      IterationProfiler)
    assert isinstance(resolve_profiler(True, cfg_enabled=False),
                      IterationProfiler)
    ready = IterationProfiler()
    assert resolve_profiler(ready) is ready
    with pytest.raises(ValueError):
        resolve_profiler(3)


def test_config_knob_validates():
    assert InferConfig(iteration_profile=False).iteration_profile is False
    assert InferConfig().iteration_profile is True


# ---------------------------------------------------------------------------
# flight-record phase split on live servers
# ---------------------------------------------------------------------------


def _churn(srv, n_first=2, long_len=40):
    """A small mixed-churn run: warm decodes, then a long prompt whose
    chunked admission spans several iterations."""
    first = [srv.submit([5 + i, 9, 3], max_new_tokens=8)
             for i in range(n_first)]
    srv.step()
    long = srv.submit([(k * 7) % 60 + 1 for k in range(long_len)],
                      max_new_tokens=4)
    srv.run_until_idle()
    return first + [long]


def test_flight_records_carry_phase_split(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    reqs = _churn(srv)
    assert all(r.done for r in reqs)
    window = srv.flight_window()
    assert window
    # every record is a fill step's or that of a step that committed
    ov = [rec for rec in window if rec.get("overlap")]
    assert ov and all(rec.get("fill") for rec in window if rec not in ov)
    for rec in window:
        phases = rec["phases_ms"]
        assert set(phases) <= set(PHASES)
        assert all(v >= 0.0 for v in phases.values())
        assert rec["t_start"] > 0.0 and "epilogue" in phases
    for rec in ov:
        phases = rec["phases_ms"]
        # the acceptance identity: the phase split PARTITIONS the
        # iteration — the host's tail, the device wait and the host
        # work hidden under a program reassemble duration exactly
        assert (rec["host_ms"] + rec["device_wait_ms"]
                + rec["overlap_ms"]) == pytest.approx(
            rec["duration_ms"], rel=1e-9, abs=1e-6)
        assert 0.0 <= rec["host_gap_frac"] <= 1.0
        # a step that committed crossed every boundary
        assert "device" in phases
        assert "inflight_depth" not in rec
        assert rec["overlap_launch_lead_ms"] >= 0.0
    # per-phase histograms observed once per committing iteration
    snap = srv.metrics_snapshot()
    dev = snap['cloud_server_iter_phase_ms{phase="device"}']
    assert dev["type"] == "histogram"
    assert dev["count"] == len(ov)
    summary = srv.iteration_profile_stats()
    assert set(summary["phases"]) <= set(PHASES) | {"overlap"}
    assert 0.0 <= summary["host_gap_frac"] <= 1.0


def test_the_fill_steps_record(params):
    """A step with nothing in flight plans and launches and commits
    nothing. Its record says `fill` and holds what the step itself did:
    its phases (no `device`, no `commit`), the launch and what the plan
    staged, page flow and pool state; no token split, no gap fields, no
    launch flag: those are the record's of the step that commits the
    program, one step later."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=4)
    srv.step()
    rec, = srv.flight_window()
    assert rec["fill"] is True and rec["iteration"] == 1
    assert set(rec["phases_ms"]) == {"sweep", "admission", "build",
                                     "launch", "deliver", "epilogue"}
    assert rec["duration_ms"] == pytest.approx(
        sum(rec["phases_ms"].values()), rel=1e-9, abs=1e-6)
    assert rec["launch_h2d"] == 1 and rec["plan_h2d"] == 2
    assert rec["stage_ms"] >= 0.0 and rec["t_launch"] >= rec["t_start"]
    assert rec["pages_allocated"] >= 1 and rec["preemptions"] == 0
    assert rec["tokens_scheduled"] == 0
    assert rec["budget_utilization"] == 0.0
    assert rec["joined"] is False and rec["grouped"] is False
    for absent in ("overlap", "host_ms", "device_wait_ms", "overlap_ms",
                   "host_gap_frac", "n_live", "decode_rounds",
                   "prefill_tokens", "launch_ahead", "launch_waits",
                   "host_late", "between_ms"):
        assert absent not in rec, absent
    srv.step()
    nxt = srv.flight_window()[-1]
    # the program the fill launched: committed, and recorded, here
    assert nxt["overlap"] and nxt["launch_waits"] == "fill"
    assert nxt["prefill_tokens"] == 3 and "fill" not in nxt
    assert nxt["between_ms"] >= 0.0
    # the fill's phases are host time in the histograms, unfolded
    snap = srv.metrics_snapshot()
    assert snap['cloud_server_iter_phase_ms{phase="build"}']["count"] == 1
    assert snap['cloud_server_iter_phase_ms{phase="overlap"}']["count"] == 1
    srv.run_until_idle()


def test_overlapped_records_carry_the_delivery(params):
    """A step that committed and launched has a `deliver` phase, the
    count of stream calls and completions it made, and the identity
    with `deliver` among the hidden phases, not in the host tail."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    streamed = []
    reqs = [srv.submit([5 + i, 9, 3], max_new_tokens=8,
                       stream=streamed.append) for i in range(2)]
    srv.run_until_idle()
    assert all(r.done for r in reqs)
    window = srv.flight_window()
    ov = [rec for rec in window if rec.get("overlap")]
    assert ov
    for rec in ov:
        ph = rec["phases_ms"]
        assert ph["deliver"] >= 0.0 and rec["delivered"] > 0
        assert rec["overlap_ms"] == pytest.approx(
            sum(ph.get(p, 0.0) for p in OVERLAP_PHASES),
            rel=1e-9, abs=1e-9)
        assert rec["host_ms"] == pytest.approx(
            sum(ph.get(p, 0.0) for p in ("commit", "launch", "epilogue")),
            rel=1e-9, abs=1e-9)
        assert (rec["host_ms"] + rec["device_wait_ms"]
                + rec["overlap_ms"]) == pytest.approx(
            rec["duration_ms"], rel=1e-9, abs=1e-6)
    # every stream call and every completion is counted once, in the
    # record of the step that made it (the sequential fill step too)
    assert sum(rec.get("delivered", 0) for rec in window) \
        == len(streamed) + len(reqs)
    assert "deliver" in OVERLAP_PHASES and "deliver" in PHASES


def test_records_tile_the_clock_with_between_ms(params):
    """`between_ms + duration_ms` is the scheduler's period: the first
    record has no `between_ms`, every later one of an unbroken run of
    busy steps starts where the one before closed, and a record after
    an idle step has none again. `between_ms` is outside the identity
    `host_ms + device_wait_ms + overlap_ms == duration_ms`."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    assert all(r.done for r in _churn(srv))
    window = srv.flight_window()
    assert len(window) > 3 and "between_ms" not in window[0]
    for prev, rec in zip(window, window[1:]):
        assert rec["between_ms"] > 0.0
        assert rec["t_start"] - rec["between_ms"] * 1e-3 == pytest.approx(
            prev["t_start"] + prev["duration_ms"] * 1e-3, abs=1e-9)
        assert (rec["host_ms"] + rec["device_wait_ms"]
                + rec.get("overlap_ms", 0.0)) == pytest.approx(
            rec["duration_ms"], rel=1e-9, abs=1e-6)
    span = (window[-1]["t_start"] + window[-1]["duration_ms"] * 1e-3
            - window[0]["t_start"]) * 1e3
    assert sum(r.get("between_ms", 0.0) + r["duration_ms"]
               for r in window) - window[0].get("between_ms", 0.0) \
        == pytest.approx(span, abs=1e-6)
    n = len(window)
    srv.step()                      # idle: no record, no closing stamp
    srv.submit([7, 9, 3], max_new_tokens=3)
    srv.run_until_idle()
    after = srv.flight_window()[n:]
    assert after and "between_ms" not in after[0]
    assert all("between_ms" in rec for rec in after[1:])


def test_launched_ahead_steps_tile_the_clock(params):
    """A step that puts the next program on the queue before it commits
    the one in flight crosses the same boundaries in another order
    (launch, then device): its phases still partition it, and
    `between_ms + duration_ms` over an unbroken run of such steps is the
    run's length within 0.1%, as `PERF.md` section 5 reads the period.
    The Perfetto iteration track carries the dispatch's `launch_ahead`,
    and `launch_waits` where it waited."""
    srv = PagedInferenceServer(params, CFG, GREEDY, decode_chunk=1, **PAGED_KW)
    reqs = [srv.submit([5 + i, 9, 3], max_new_tokens=30) for i in range(2)]
    srv.run_until_idle()
    assert all(r.done for r in reqs)
    window = srv.flight_window()
    run = [r for r in window if r.get("launch_ahead")]
    assert len(run) >= 20
    i0 = window.index(run[0])
    assert window[i0:i0 + len(run)] == run          # unbroken
    for rec in run:
        assert "between_ms" in rec and "launch_waits" not in rec
        assert sum(rec["phases_ms"].values()) == pytest.approx(
            rec["duration_ms"], rel=1e-9, abs=1e-6)
        assert (rec["host_ms"] + rec["device_wait_ms"]
                + rec["overlap_ms"]) == pytest.approx(
            rec["duration_ms"], rel=1e-9, abs=1e-6)
        assert isinstance(rec["host_late"], bool)
    # every step of the run but its last launched the next record's
    # dispatch ahead: `launch` is among its phases, beside `device`
    assert all({"launch", "device", "commit", "deliver"}
               <= set(rec["phases_ms"]) for rec in run[:-1])
    span = (run[-1]["t_start"] + run[-1]["duration_ms"] * 1e-3
            - run[0]["t_start"] + run[0]["between_ms"] * 1e-3) * 1e3
    assert sum(r["between_ms"] + r["duration_ms"] for r in run) \
        == pytest.approx(span, rel=1e-3)
    waited = [r for r in window if r.get("overlap")
              and not r["launch_ahead"]]
    assert waited and {r["launch_waits"] for r in waited} == {"fill"}
    iters = {e["args"]["iteration"]: e["args"]
             for e in scheduler_chrome_trace(window)["traceEvents"]
             if e["ph"] == "X" and e["tid"] == 0}
    for rec in window:
        args = iters[rec["iteration"]]
        for k in ("launch_ahead", "launch_waits"):
            assert (k in args) == (k in rec)
            assert args.get(k) == rec.get(k)


def test_records_split_build_and_say_who_set_the_pace(params):
    """`stage_ms` and `plan_h2d` sit on the record of the step that
    planned (beside its `build` and `launch_h2d`), `host_late` on the
    record of the step that committed the program: a bool on every
    overlapped record, true where the program was known ready before
    the step came for it."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    first = [srv.submit([5 + i, 9, 3], max_new_tokens=8) for i in range(2)]
    srv.step()
    srv.step()
    assert srv._inflight is not None
    # the program is ready before the next step is taken
    jax.block_until_ready(srv._inflight.futures)
    srv.step()
    late = srv.flight_window()[-1]
    assert late["overlap"] and late["host_late"] is True
    long = srv.submit([(k * 7) % 60 + 1 for k in range(40)],
                      max_new_tokens=4)
    srv.run_until_idle()
    assert all(r.done for r in first + [long])
    window = srv.flight_window()
    for rec in window:
        assert isinstance(rec["plan_h2d"], int) and rec["plan_h2d"] >= 0
        if rec.get("overlap"):
            assert isinstance(rec["host_late"], bool)
        else:
            assert "host_late" not in rec
        # a step that staged a plan timed the block, inside `build`;
        # one that planned nothing staged nothing
        assert ("stage_ms" in rec) == (rec["plan_h2d"] > 0)
        if "stage_ms" in rec:
            assert 0.0 < rec["stage_ms"] <= rec["phases_ms"]["build"]
        # a launch follows a plan, never the other way round
        assert rec["launch_h2d"] <= min(rec["plan_h2d"], 1)
    assert any(rec["plan_h2d"] for rec in window)
    # with the profiler off the counter stays and the time goes
    off = PagedInferenceServer(params, CFG, GREEDY, iteration_profile=False, **PAGED_KW)
    assert all(r.done for r in _churn(off))
    for rec in off.flight_window():
        assert "stage_ms" not in rec and "between_ms" not in rec
        assert rec["plan_h2d"] >= 0
        assert isinstance(rec.get("host_late", False), bool)


@pytest.mark.parametrize("order", ["ahead", "waits"])
def test_records_say_which_programs_walked_the_layers_once(params, order):
    """`joined`: true on the records of mixed steps whose program took
    the one walk (one plain decode round beside a prefill group), false
    on decode-only programs and on steps of several rounds, and on
    every record, whichever way its step was launched."""
    def churn(**kw):
        srv = waits(PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW,
                                         **kw), order == "waits")
        assert all(r.done for r in _churn(srv))
        return [r for r in srv.flight_window() if not r.get("fill")]

    window = churn(decode_chunk=1)
    assert all(isinstance(rec["joined"], bool) for rec in window)
    mixed = [rec for rec in window
             if rec.get("prefill_tokens") and rec["decode_rounds"]]
    assert mixed and all(rec["joined"] for rec in mixed)
    alone = [rec for rec in window if rec not in mixed]
    assert alone and not any(rec["joined"] for rec in alone)
    # the default eight rounds a step keep the two walks while the
    # requests have tokens enough left to run more than one
    for rec in churn():
        assert rec["joined"] == (rec.get("prefill_tokens", 0) > 0
                                 and rec["decode_rounds"] == 1)


def test_profiler_disabled_keeps_old_shape(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, iteration_profile=False,
                               **PAGED_KW)
    # same churn shape as the enabled test: the profiler changes no
    # dispatch shapes, so the jit cache is shared either way
    reqs = _churn(srv)
    assert all(r.done for r in reqs)
    for rec in srv.flight_window():
        assert "phases_ms" not in rec and "host_gap_frac" not in rec
        assert rec["duration_ms"] >= 0.0
    assert not [k for k in srv.metrics_snapshot() if "iter_phase" in k]
    assert srv.iteration_profile_stats() is None


# ---------------------------------------------------------------------------
# overhead guard: one dispatch, one sync, bounded constant clock reads
# ---------------------------------------------------------------------------


def test_profiled_mixed_step_dispatch_sync_and_clock_counts(
        params, monkeypatch):
    """The profiling-enabled clone of the dispatch/device_get-count
    regression test, plus the profiler's own budget: phase stamping
    performs a bounded CONSTANT number of perf_counter reads per
    pipelined iteration (begin + one mark per boundary — the count
    must not scale with slots, jobs, or tokens).

    Under the async scheduler a steady-state step issues exactly ONE
    fused dispatch — `_mixed_step` while the planned frame has prefill
    work, else the decode/spec program — and ONE device_get (the
    previous launch's commit)."""
    from cloud_server_tpu.inference import paged_server as ps
    srv = PagedInferenceServer(params, CFG, GREEDY, iteration_profile=True, **PAGED_KW)
    warm = srv.submit([5, 9, 3, 1], max_new_tokens=24)
    srv.step()
    assert srv.num_active == 1

    calls = {"dispatch": 0, "get": 0, "clock": 0}
    origs = {n: getattr(ps, n) for n in
             ("_mixed_step", "_decode_rounds", "_spec_rounds")}
    orig_get = jax.device_get
    orig_clock = ip.perf_counter

    def wrap(name):
        def w(*a, **k):
            calls["dispatch"] += 1
            return origs[name](*a, **k)
        return w

    def get_wrap(x):
        calls["get"] += 1
        return orig_get(x)

    def clock_wrap():
        calls["clock"] += 1
        return orig_clock()

    for n in origs:
        monkeypatch.setattr(ps, n, wrap(n))
    monkeypatch.setattr(jax, "device_get", get_wrap)
    # counts ONLY the profiler's reads: the module binds perf_counter
    # as a module global, so every begin/mark goes through this
    monkeypatch.setattr(ip, "perf_counter", clock_wrap)

    long = srv.submit([(k * 7) % 60 + 1 for k in range(40)],
                      max_new_tokens=4)
    churn_steps = 0
    clock_per_step = set()
    while srv._jobs or srv.num_pending:
        before = dict(calls)
        srv.step()
        churn_steps += 1
        assert calls["dispatch"] - before["dispatch"] == 1, \
            "profiled pipelined iteration must stay ONE fused dispatch"
        assert calls["get"] - before["get"] == 1, \
            "profiled pipelined iteration must stay ONE host sync"
        clock_per_step.add(calls["clock"] - before["clock"])
        assert churn_steps < 50
    assert churn_steps >= 2  # real churn: admission spanned iterations
    # bounded constant: begin + the boundaries into admission, build,
    # device, commit, launch, deliver and epilogue + end = 9, and the
    # two laps around the plan's staging block (`stage_ms`) = 11;
    # `between_ms` reads no clock of its own
    assert len(clock_per_step) == 1, (
        f"profiler clock reads varied across mixed iterations: "
        f"{clock_per_step}")
    assert clock_per_step.pop() <= 11
    for n, f in origs.items():
        monkeypatch.setattr(ps, n, f)
    monkeypatch.setattr(jax, "device_get", orig_get)
    monkeypatch.setattr(ip, "perf_counter", orig_clock)
    srv.run_until_idle()
    assert warm.done and long.done


# ---------------------------------------------------------------------------
# idle-iteration visibility
# ---------------------------------------------------------------------------


def test_idle_vs_busy_visibility(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    for _ in range(3):
        srv.step()
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_idle_iterations_total"]["value"] == 3
    assert snap["cloud_server_last_busy_ts"]["value"] == 0.0
    srv.submit([5, 9, 3], max_new_tokens=3)
    srv.run_until_idle()
    snap = srv.metrics_snapshot()
    assert snap["cloud_server_last_busy_ts"]["value"] > 0.0
    # the gauge matches the newest flight record's wall-clock stamp
    assert snap["cloud_server_last_busy_ts"]["value"] == \
        srv.flight_window()[-1]["ts"]
    busy_before = srv.flight.iterations
    srv.step()  # idle again: counter moves, gauge freezes
    snap2 = srv.metrics_snapshot()
    assert snap2["cloud_server_idle_iterations_total"]["value"] == 4
    assert snap2["cloud_server_last_busy_ts"]["value"] == \
        snap["cloud_server_last_busy_ts"]["value"]
    assert srv.flight.iterations == busy_before


# ---------------------------------------------------------------------------
# scheduler Perfetto export + cross-link to request span trees
# ---------------------------------------------------------------------------


def test_scheduler_chrome_trace_wellformed(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    reqs = _churn(srv)
    assert all(r.done for r in reqs)
    window = srv.flight_window()
    trace = scheduler_chrome_trace(window)
    assert json.loads(json.dumps(trace)) == trace  # JSON-serializable
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert metas, "process/thread name metadata missing"
    inflight_tid = len(PHASES) + 1
    iters = [e for e in xs if e["tid"] == 0]
    phases = [e for e in xs if 0 < e["tid"] < inflight_tid]
    inflight = [e for e in xs if e["tid"] == inflight_tid]
    assert len(iters) == len(window)
    # iteration indices agree with flight_window()
    assert [e["args"]["iteration"] for e in iters] == \
        [rec["iteration"] for rec in window]
    # the iteration track's args carry the record's scalars, the parts
    # of the hidden host work among them
    for e, rec in zip(iters, window):
        for k in ("between_ms", "stage_ms", "plan_h2d", "host_late",
                  "launch_h2d"):
            assert (k in e["args"]) == (k in rec)
            if k in rec:
                assert e["args"][k] == rec[k]
        assert "inflight_depth" not in e["args"]
    assert {"between_ms", "stage_ms", "plan_h2d", "host_late"} \
        <= set().union(*(e["args"] for e in iters))
    assert "inflight_depth" not in ip._ITER_ARG_KEYS
    by_iter = {e["args"]["iteration"]: e for e in iters}
    for e in phases:
        assert e["name"] in PHASES
        it = by_iter[e["args"]["iteration"]]
        # phase events nest within their iteration's bounds (µs; the
        # 1 µs slack absorbs float accumulation on a large timebase)
        assert e["ts"] >= it["ts"] - 1.0
        assert e["ts"] + e["dur"] <= it["ts"] + it["dur"] + 1.0
    # every recorded phase of every record rendered
    want = sum(len([v for v in rec["phases_ms"].values() if v > 0])
               for rec in window)
    assert len(phases) == want
    # async-scheduler round trip: overlapped iterations render their
    # committed dispatch as a CONCURRENT inflight slice — launched
    # inside the PREVIOUS record's window, ending at this record's
    # residual device wait — so the slice must START before its
    # committing iteration begins and OVERLAP that iteration's bounds
    # (the old export wrongly assumed disjoint iteration windows)
    assert inflight, "overlapped run rendered no inflight slices"
    for e in inflight:
        it = by_iter[e["args"]["iteration"]]
        assert e["ts"] < it["ts"]                      # launched earlier
        assert e["ts"] + e["dur"] > it["ts"]           # spans into it
        assert e["ts"] + e["dur"] <= it["ts"] + it["dur"] + 1.0
        launched_in = e["args"]["launched_in_iteration"]
        prev = by_iter.get(launched_in)
        if prev is not None:  # still in the retained window
            assert prev["ts"] <= e["ts"] <= prev["ts"] + prev["dur"] + 1.0


def test_scheduler_trace_skips_unprofiled_records(params):
    srv = PagedInferenceServer(params, CFG, GREEDY,
                               iteration_profile=False, **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=3)
    srv.run_until_idle()
    trace = scheduler_chrome_trace(srv.flight_window())
    assert trace["traceEvents"] == []


def test_cross_link_span_to_iteration_roundtrip(params):
    """The two-way answer: a traced request's decode_segment span
    carries an iteration index; the flight record with that index
    frames the span exactly (same t0/now pair), and the Perfetto
    export's iteration event agrees."""
    srv = PagedInferenceServer(params, CFG, GREEDY, tracing=1.0, **PAGED_KW)
    reqs = _churn(srv)
    assert all(r.done for r in reqs)
    window = srv.flight_window()
    by_iter = {rec["iteration"]: rec for rec in window}
    trees = srv.trace_trees()
    assert len(trees) == len(reqs)
    segs = [s for t in trees for ph in t["root"]["children"]
            for s in ph.get("children", ())
            if s["name"] in ("decode_segment", "prefill_chunk")]
    assert segs, "no iteration-granular spans recorded"
    linked = 0
    for s in segs:
        idx = s["tags"]["iteration"]
        rec = by_iter.get(idx)
        if rec is None:
            continue  # evicted from the ring — index still valid
        linked += 1
        # the span shares the iteration's (t0, now) frame
        assert s["start"] == pytest.approx(rec["t_start"], abs=1e-9)
        assert s["end"] == pytest.approx(
            rec["t_start"] + rec["duration_ms"] * 1e-3, abs=1e-6)
    assert linked, "no span linked to a retained flight record"
    # and the reverse hop through the Perfetto export
    trace = scheduler_chrome_trace(window)
    iter_ev = {e["args"]["iteration"]: e
               for e in trace["traceEvents"]
               if e["ph"] == "X" and e["tid"] == 0}
    s = next(s for s in segs if s["tags"]["iteration"] in iter_ev)
    e = iter_ev[s["tags"]["iteration"]]
    assert e["ts"] == pytest.approx(s["start"] * 1e6, rel=1e-12)


# ---------------------------------------------------------------------------
# /stats + /debug/scheduler_trace over HTTP
# ---------------------------------------------------------------------------


@pytest.fixture()
def frontend(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW).start()
    front = HttpFrontend(srv).start()
    yield front, srv
    front.stop()
    srv.stop()


def _get(front, path: str):
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}{path}",
                                timeout=30) as resp:
        return resp.status, json.loads(resp.read())


def test_http_stats_and_scheduler_trace(frontend):
    front, srv = frontend
    req = srv.submit([5, 9, 3], max_new_tokens=4)
    srv.run_until_idle()
    assert req.done
    status, stats = _get(front, "/stats?n=8")
    assert status == 200
    prof = stats["iteration_profile"]
    assert 0.0 <= prof["host_gap_frac"] <= 1.0
    assert "device" in prof["phases"]
    assert "p99_ms" in prof["phases"]["device"]
    for rec in stats["flight_recorder"]:
        assert "phases_ms" in rec
    status, trace = _get(front, "/debug/scheduler_trace?n=8")
    assert status == 200
    assert any(e["ph"] == "X" and e["name"] in PHASES
               for e in trace["traceEvents"])
    # n junk -> 400; n=0 -> empty, never "everything"
    try:
        urllib.request.urlopen(
            "http://%s:%d/debug/scheduler_trace?n=x" % front.address,
            timeout=30)
        assert False, "expected HTTP 400"
    except urllib.error.HTTPError as exc:
        assert exc.code == 400
    status, empty = _get(front, "/debug/scheduler_trace?n=0")
    assert status == 200 and empty["traceEvents"] == []


# ---------------------------------------------------------------------------
# fleet merge
# ---------------------------------------------------------------------------


def test_router_merges_phase_histograms(params):
    replicas = [PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
                for _ in range(2)]
    router = ReplicatedRouter(replicas)
    for i in range(4):
        router.submit([5 + i, 9, 3], max_new_tokens=3)
    router.run_until_idle()
    key = 'cloud_server_iter_phase_ms{phase="device"}'
    per_rep = [rep.metrics_snapshot()[key] for rep in replicas]
    assert all(e["count"] > 0 for e in per_rep), \
        "placement should spread over both replicas"
    merged = router.metrics_snapshot()[key]
    assert merged["count"] == sum(e["count"] for e in per_rep)
    assert merged["counts"] == [
        a + b for a, b in zip(per_rep[0]["counts"], per_rep[1]["counts"])]
    # the fleet summary recomputes the ratio from merged sums
    fleet = profile_summary(router.metrics_snapshot())
    host = sum(v["count"] for k, v in fleet["phases"].items())
    assert host > 0 and 0.0 <= fleet["host_gap_frac"] <= 1.0
    # router flight windows tag replicas, so the Perfetto export
    # renders one process per replica
    trace = scheduler_chrome_trace(router.flight_window(16))
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {0, 1}
