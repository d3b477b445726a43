"""Scenario harness: seeded workload determinism, the replay driver's
timing contract, the discrete-event simulator (incl. the live
calibration check), and the SLO-burn-rate autoscaler (stub-router
policy tests + the live drain-under-autoscaler race)."""

import threading
import time

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import transformer
from cloud_server_tpu.scenarios import (
    AutoscalerConfig, CostModel, Event, FleetSim, LengthMixture,
    MMPPArrivals, PoissonArrivals, ReplayDriver, Scenario, SessionShape,
    SimReplica, SLOBurnAutoscaler, TenantMix, TraceArrivals, stream_bytes)

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
PAGED_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
                prompt_buckets=[16, 48])

# sim-vs-live attainment agreement bar — the value documented in
# docs/scenarios.md ("Calibration"); change them together
CALIBRATION_TOL = 0.35


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _mini_scenario(seed=0, duration=1.0, rate=20.0, turns=1.0,
                   prefix=0, think=0.0):
    return Scenario(
        arrivals=PoissonArrivals(rate), duration_s=duration,
        prompt_len=LengthMixture([(1.0, ("uniform", 4, 12))]),
        output_len=LengthMixture.point(4),
        tenants=TenantMix({"inter": 1.0, "bulk": 1.0}),
        session=SessionShape(turns_mean=turns, think_s_mean=think,
                             prefix_len=prefix),
        vocab=60, seed=seed)


# ---------------------------------------------------------------------------
# workload generation
# ---------------------------------------------------------------------------


def test_stream_bytes_deterministic():
    """The determinism contract: identical config + seed produce a
    BYTE-identical event stream; a different seed does not."""
    a = _mini_scenario(seed=7, turns=2.5, prefix=6, think=0.1).generate()
    b = _mini_scenario(seed=7, turns=2.5, prefix=6, think=0.1).generate()
    assert a and stream_bytes(a) == stream_bytes(b)
    c = _mini_scenario(seed=8, turns=2.5, prefix=6, think=0.1).generate()
    assert stream_bytes(a) != stream_bytes(c)


def test_multi_turn_sessions_share_tenant_prefix():
    sc = _mini_scenario(seed=3, duration=2.0, turns=3.0, prefix=6,
                        think=0.2)
    events = sc.generate()
    assert any(e.turn > 0 for e in events)  # multi-turn really sampled
    by_tenant = {}
    for e in events:
        assert e.prefix_len == 6
        assert e.prompt[:6] == sc.tenant_prefix(e.tenant)
        by_tenant.setdefault(e.tenant, set()).add(e.prompt[:6])
    # every session of a tenant opens with the SAME system prefix (the
    # radix-cache workload), and distinct tenants get distinct ones
    assert all(len(v) == 1 for v in by_tenant.values())
    assert len(set(frozenset(v) for v in by_tenant.values())) == 2
    # follow-up turns carry positive think time, turn 0 never does
    assert all(e.think_s > 0 for e in events if e.turn > 0)
    assert all(e.think_s == 0 for e in events if e.turn == 0)


def test_arrival_processes():
    import random
    rng = random.Random(0)
    times = PoissonArrivals(50.0).times(rng, 1.0)
    assert times == sorted(times) and all(0 <= t < 1.0 for t in times)
    # MMPP: the burst phase really bursts (low 1 rps, high 50 rps)
    mmpp = MMPPArrivals([(1.0, 1.0), (50.0, 1.0), (1.0, 1.0)])
    times = mmpp.times(random.Random(0), 3.0)
    burst = sum(1 for t in times if 1.0 <= t < 2.0)
    quiet = len(times) - burst
    assert burst > 5 * max(1, quiet)
    # trace replay: exact recorded gaps, cycled past the trace end
    tr = TraceArrivals([0.5, 0.25]).times(random.Random(0), 2.0)
    assert tr == pytest.approx([0.5, 0.75, 1.25, 1.5])
    with pytest.raises(ValueError):
        TraceArrivals([0.0, 0.0])


def test_length_mixture_bounds():
    import random
    rng = random.Random(0)
    mix = LengthMixture([(0.5, ("lognormal", 3.0, 0.8, 40)),
                         (0.3, ("uniform", 2, 9)),
                         (0.2, ("point", 7))])
    samples = [mix.sample(rng) for _ in range(500)]
    assert all(1 <= s <= 40 for s in samples)
    assert LengthMixture.point(0).sample(rng) == 1  # floor at 1
    # tenant mix is insertion-order independent (sorted internally)
    sa = TenantMix({"a": 1.0, "b": 3.0})
    sb = TenantMix({"b": 3.0, "a": 1.0})
    ra, rb = random.Random(1), random.Random(1)
    assert ([sa.sample(ra) for _ in range(50)]
            == [sb.sample(rb) for _ in range(50)])


# ---------------------------------------------------------------------------
# replay driver (virtual time, stub target)
# ---------------------------------------------------------------------------


class _StubHandle:
    def __init__(self):
        self.done = False
        self.finish_reason = ""


class _StubTarget:
    def __init__(self, reject_after=None):
        self.submitted = []
        self.reject_after = reject_after

    def submit(self, prompt, **kw):
        if (self.reject_after is not None
                and len(self.submitted) >= self.reject_after):
            raise RuntimeError("backpressure")
        h = _StubHandle()
        self.submitted.append((prompt, kw, h))
        return h


def test_replay_timing_contract():
    """Turn 0 fires at its nominal time; turn k fires think_s after
    turn k-1 ACTUALLY completed — never off the nominal schedule."""
    events = [
        Event(time_s=1.0, session=0, turn=0, tenant="a",
              prompt=(1, 2), max_new_tokens=4),
        Event(time_s=1.1, session=0, turn=1, tenant="a",
              prompt=(3,), max_new_tokens=4, think_s=0.5),
    ]
    tgt = _StubTarget()
    drv = ReplayDriver(tgt, events, submit_kw={"deadline_s": 9.0})
    assert drv.tick(0.99) == 0 and not tgt.submitted
    assert drv.tick(1.0) == 1          # turn 0 due
    assert drv.tick(5.0) == 0          # turn 1 waits on completion
    tgt.submitted[0][2].done = True    # turn 0 completes, seen at t=5
    assert drv.tick(5.0) == 0          # think time starts NOW
    assert drv.tick(5.49) == 0
    assert drv.tick(5.5) == 1          # 5.0 + think_s
    assert drv.exhausted and not drv.done
    tgt.submitted[1][2].done = True
    assert drv.done
    # submit_kw + per-event fields both reached the target
    _, kw, _ = tgt.submitted[0]
    assert kw == {"deadline_s": 9.0, "max_new_tokens": 4, "tenant": "a"}
    res = drv.result()
    assert res == {"fired": 2, "completed": 2, "failed": 0,
                   "failures": [], "rejected": 0, "outstanding": 0}


def test_replay_counts_rejections_and_metrics():
    events = _mini_scenario(seed=1).generate()
    tgt = _StubTarget(reject_after=3)
    drv = ReplayDriver(tgt, events)
    drv.tick(1e9)
    assert len(drv.rejected) == len(events) - 3
    snap = drv.metrics_snapshot()
    assert snap["cloud_server_scenario_events_fired_total"]["value"] == 3
    assert (snap["cloud_server_scenario_events_rejected_total"]["value"]
            == len(events) - 3)
    assert (snap["cloud_server_scenario_sessions_total"]["value"]
            == len({e.session for e in events}))
    assert "cloud_server_scenario_replay_lag_ms" in snap
    assert drv.result()["rejected"] == len(events) - 3


# ---------------------------------------------------------------------------
# discrete-event simulator
# ---------------------------------------------------------------------------


def test_cost_model_fit():
    cm = CostModel.fit([{"tokens_scheduled": 10, "duration_ms": 3.0},
                        {"tokens_scheduled": 30, "duration_ms": 5.0},
                        {"tokens_scheduled": 50, "duration_ms": 7.0}])
    assert cm.per_token_ms == pytest.approx(0.1)
    assert cm.fixed_ms == pytest.approx(2.0)
    assert cm.iteration_ms(100) == pytest.approx(12.0)
    # degenerate windows fall back instead of exploding
    assert CostModel.fit([]).fixed_ms == CostModel().fixed_ms
    flat = CostModel.fit([{"tokens_scheduled": 8, "duration_ms": 4.0},
                          {"tokens_scheduled": 8, "duration_ms": 6.0}])
    assert flat.per_token_ms == 0.0 and flat.fixed_ms == pytest.approx(5.0)


def test_sim_replica_drr_prefix_and_preemption():
    """The simulated scheduler keeps the live stack's shapes: weighted
    admission order, the radix prefix-cache skip, and page-pool
    preemption of the youngest admission."""
    r = SimReplica(max_slots=1, budget=64, chunk=16, page_size=8,
                   class_weights={"interactive": 4.0, "batch": 1.0})
    from cloud_server_tpu.scenarios.simulator import _SimReq
    ev = lambda sid, tenant, pfx=0, plen=8, out=2: Event(  # noqa: E731
        time_s=0.0, session=sid, turn=0, tenant=tenant,
        prompt=tuple(range(1, plen + 1)), max_new_tokens=out,
        prefix_len=pfx)
    b = _SimReq(ev(0, "bulk"), "batch", 0.0)
    i = _SimReq(ev(1, "inter"), "interactive", 0.0)
    r.submit(b, 0.0)
    r.submit(i, 0.0)
    r.step(CostModel())
    # one slot, both pending: the heavier class is admitted first
    assert r.active and r.active[0] is i
    # radix model: a second session sharing the tenant prefix skips it
    r2 = SimReplica(max_slots=4, budget=64, chunk=64, page_size=8)
    s1 = _SimReq(ev(0, "inter", pfx=6, plen=8), "default", 0.0)
    s2 = _SimReq(ev(1, "inter", pfx=6, plen=8), "default", 0.0)
    r2.submit(s1, 0.0)
    r2.submit(s2, 0.0)
    r2._admit(0.0)
    assert s1.prefill_left == 8        # first session pays the prefix
    assert s2.prefill_left == 2        # radix skip: only the body left
    # page pressure: pool of 1 page with 2 active preempts the youngest
    r3 = SimReplica(max_slots=4, budget=64, chunk=64, page_size=8,
                    pages=1)
    a1 = _SimReq(ev(0, None), "default", 0.0)
    a2 = _SimReq(ev(1, None), "default", 0.0)
    r3.submit(a1, 0.0)
    r3.submit(a2, 0.0)
    r3.step(CostModel())
    assert r3.preemptions >= 1 and a2.preempted >= 1


def test_fleet_sim_serves_every_event():
    sc = _mini_scenario(seed=2, duration=2.0, rate=30.0, turns=2.0,
                        prefix=4, think=0.05)
    events = sc.generate()
    slo = {"windows_s": [2, 10],
           "classes": {"default": {"objective": 0.9, "ttft_s": 1.0,
                                   "e2e_s": 5.0}}}
    sim = FleetSim([SimReplica(max_slots=4, budget=64, chunk=16,
                               page_size=8) for _ in range(2)],
                   cost=CostModel(fixed_ms=2.0, per_token_ms=0.1),
                   slo=slo)
    rep = sim.run(events)
    assert rep["finished"] == len(events)
    assert rep["iterations"] > 0 and rep["sim_duration_s"] > 0
    lt = rep["slo"]["classes"]["default"]["metrics"]["e2e"]["lifetime"]
    assert lt["total"] == len(events)


def test_sim_calibration_against_live(params):
    """The ISSUE's calibration bar: fit the cost model from a LIVE
    run's flight records, simulate the same event stream with the
    same SLO config, and require per-(class, metric) lifetime
    attainment within CALIBRATION_TOL (documented in
    docs/scenarios.md) plus agreement on which class waits longer."""
    qos = {"quantum": 16,
           "tenants": {"inter": {"weight": 4.0,
                                 "priority": "interactive"},
                       "bulk": {"weight": 1.0, "priority": "batch"}}}
    slo = {"windows_s": [2, 10],
           "classes": {"interactive": {"objective": 0.9, "ttft_s": 0.5,
                                       "queue_wait_s": 0.4,
                                       "e2e_s": 2.0},
                       "batch": {"objective": 0.5, "ttft_s": 0.5,
                                 "queue_wait_s": 0.4, "e2e_s": 2.0}}}
    # warm the (process-wide) jit cache on a throwaway server so
    # compile time enters neither the fit window nor the SLO counts
    warm = PagedInferenceServer(params, CFG, GREEDY, qos=qos, slo=slo,
                                **PAGED_KW)
    w = warm.submit([5, 9, 3, 1], max_new_tokens=4, tenant="inter")
    warm.run_until_idle()
    assert w.done
    warm.stop()
    srv = PagedInferenceServer(params, CFG, GREEDY, qos=qos, slo=slo,
                               **PAGED_KW)
    n_warm = len(srv.flight_window())
    sc = _mini_scenario(seed=5, duration=0.8, rate=40.0)
    events = sc.generate()
    assert len(events) >= 10
    drv = ReplayDriver(srv, events)
    res = drv.run(step=srv.step, timeout_s=120.0)
    srv.run_until_idle()
    assert res["fired"] == len(events)
    assert res["failed"] == 0 and res["rejected"] == 0
    live = srv.slo_report()
    cost = CostModel.fit(srv.flight_window()[n_warm:])
    assert cost.fixed_ms > 0
    srv.stop()
    sim = FleetSim(
        [SimReplica(max_slots=PAGED_KW["max_slots"],
                    budget=PAGED_KW["prefill_chunk"]
                    + PAGED_KW["max_slots"],
                    chunk=PAGED_KW["prefill_chunk"],
                    page_size=PAGED_KW["page_size"],
                    class_weights={"interactive": 4.0, "batch": 1.0})],
        cost=cost, slo=slo,
        tenant_class={"inter": "interactive", "bulk": "batch"})
    rep = sim.run(events)
    assert rep["finished"] == len(events)
    sim_slo = rep["slo"]
    for cls in ("interactive", "batch"):
        for metric in ("ttft", "e2e"):
            lv = live["classes"][cls]["metrics"][metric]["lifetime"]
            sv = sim_slo["classes"][cls]["metrics"][metric]["lifetime"]
            assert lv["total"] == sv["total"]
            if lv["total"]:
                assert abs(lv["attainment"] - sv["attainment"]) \
                    <= CALIBRATION_TOL, (
                        f"{cls}/{metric}: live {lv['attainment']:.3f} "
                        f"vs sim {sv['attainment']:.3f}")
    # ordering: when the live run shows a clear class-level queue-wait
    # gap (DRR favoring interactive), the sim must agree on direction
    def qw_mean(rep_cls):
        m = rep_cls["metrics"].get("queue_wait")
        return None if m is None or not m["lifetime"]["total"] else m
    li = live["classes"]["interactive"]["metrics"]["queue_wait"]
    lb = live["classes"]["batch"]["metrics"]["queue_wait"]
    si = sim_slo["classes"]["interactive"]["metrics"]["queue_wait"]
    sb = sim_slo["classes"]["batch"]["metrics"]["queue_wait"]
    if (li["lifetime"]["total"] and lb["lifetime"]["total"]
            and abs(li["lifetime"]["attainment"]
                    - lb["lifetime"]["attainment"]) > 0.3):
        live_inter_better = (li["lifetime"]["attainment"]
                             >= lb["lifetime"]["attainment"])
        sim_inter_better = (si["lifetime"]["attainment"]
                            >= sb["lifetime"]["attainment"])
        assert live_inter_better == sim_inter_better


# ---------------------------------------------------------------------------
# autoscaler policy (stub router, virtual clock)
# ---------------------------------------------------------------------------


class _FakeReplica:
    def __init__(self):
        self.num_active = 0
        self.num_pending = 0
        self.stopped = False

    def stop(self):
        self.stopped = True


class _FakeRouter:
    """The surface SLOBurnAutoscaler reads/actuates, nothing more."""

    def __init__(self, n=1, disagg=False):
        from cloud_server_tpu.utils.serving_metrics import MetricsRegistry
        self._registry = MetricsRegistry()
        self.replicas = [_FakeReplica() for _ in range(n)]
        self.roles = ["colocated"] * n
        self._disagg = disagg
        self.num_pending = 0
        self.report = None
        self.removed = []

    def attached_indices(self):
        return list(range(len(self.replicas)))

    def slo_report(self):
        return self.report

    def add_replica(self, replica, *, role="colocated"):
        self.replicas.append(replica)
        self.roles.append(role)
        return len(self.replicas) - 1

    def remove_replica(self, i, *, migrate=True, timeout=None):
        r = self.replicas.pop(i)
        self.roles.pop(i)
        self.removed.append(r)
        return r


def _burn_report(fast, slow, cls="interactive", metric="ttft",
                 windows=(5.0, 60.0)):
    return {"windows_s": list(windows),
            "classes": {cls: {"objective": 0.9, "metrics": {metric: {
                "windows": {f"{windows[0]:g}": {"burn_rate": fast},
                            f"{windows[-1]:g}": {"burn_rate": slow}},
                "lifetime": {}}}}}}


def _asc(router, spares=2, **cfg_kw):
    pool = [_FakeReplica() for _ in range(spares)]
    cfg = AutoscalerConfig(**{**dict(
        min_replicas=1, max_replicas=3, hold_s=10.0, poll_s=1.0,
        pending_high=8.0, pending_low=1.0), **cfg_kw})
    return SLOBurnAutoscaler(
        router, spawn=lambda role: pool.pop() if pool else None,
        config=cfg), pool


def test_autoscaler_multiwindow_up_and_cooldown():
    r = _FakeRouter()
    asc, _ = _asc(r)
    # fast-only burn is noise: no action
    r.report = _burn_report(fast=5.0, slow=0.2)
    assert asc.step(now=100.0) == "hold"
    # both windows burning: scale up
    r.report = _burn_report(fast=5.0, slow=2.0)
    assert asc.step(now=101.0) == "up"
    assert len(r.replicas) == 2
    # cooldown: the same signal cannot flap the fleet inside hold_s
    assert asc.step(now=101.5) == "hold"
    assert asc.step(now=105.0) == "hold"
    assert asc.step(now=112.0) == "up"
    assert len(r.replicas) == 3
    # max clamp: still burning but at ceiling
    assert asc.step(now=130.0) == "hold"
    assert len(r.replicas) == 3
    st = asc.stats()
    assert st["scale_up_total"] == 2 and st["replicas"] == 3


def test_autoscaler_pending_backstop_needs_no_slo():
    r = _FakeRouter()
    asc, _ = _asc(r)
    r.report = None              # no SLO config anywhere in the fleet
    r.num_pending = 20
    assert asc.step(now=10.0) == "up"
    assert asc.events[-1].reason.startswith("pending/replica")


def test_autoscaler_scale_down_idle_and_min_clamp():
    r = _FakeRouter(n=3)
    asc, _ = _asc(r, spares=0)
    r.report = _burn_report(fast=0.0, slow=0.0)
    assert asc.step(now=50.0) == "down"
    assert len(r.replicas) == 2 and len(r.removed) == 1
    # released via the default hook -> stopped
    assert r.removed[0].stopped
    assert asc.step(now=51.0) == "hold"   # cooldown
    assert asc.step(now=70.0) == "down"
    assert asc.step(now=90.0) == "hold"   # min_replicas clamp
    assert len(r.replicas) == 1


def test_autoscaler_blocked_paths():
    r = _FakeRouter()
    asc, pool = _asc(r, spares=0)
    r.report = _burn_report(fast=5.0, slow=5.0)
    assert asc.step(now=10.0) == "blocked"   # spawn pool empty
    assert asc.stats()["blocked_total"] == 1
    # a blocked attempt does NOT burn the cooldown window
    pool.append(_FakeReplica())
    assert asc.step(now=10.5) == "up"
    # drain timeout on the victim: remove_replica returns None
    r2 = _FakeRouter(n=2)
    asc2, _ = _asc(r2, spares=0)
    r2.remove_replica = lambda i, migrate=True, timeout=None: None
    r2.report = _burn_report(fast=0.0, slow=0.0)
    assert asc2.step(now=10.0) == "blocked"


def test_autoscaler_role_awareness():
    r = _FakeRouter(disagg=True)
    asc, _ = _asc(r)
    r.report = _burn_report(fast=5.0, slow=5.0, metric="ttft")
    asc.step(now=10.0)
    assert r.roles[-1] == "prefill"
    r.report = _burn_report(fast=5.0, slow=5.0, metric="itl")
    asc.step(now=30.0)
    assert r.roles[-1] == "decode"
    # colocated fleets always add colocated, whatever the metric
    rc = _FakeRouter(disagg=False)
    ascc, _ = _asc(rc)
    rc.report = _burn_report(fast=5.0, slow=5.0, metric="ttft")
    ascc.step(now=10.0)
    assert rc.roles[-1] == "colocated"


def test_autoscaler_metric_families_registered_eagerly():
    r = _FakeRouter()
    SLOBurnAutoscaler(r, spawn=lambda role: None)
    names = {n.split("{")[0] for n in r._registry.snapshot()}
    for fam in ("cloud_server_autoscaler_scale_up_total",
                "cloud_server_autoscaler_scale_down_total",
                "cloud_server_autoscaler_scale_blocked_total",
                "cloud_server_autoscaler_replicas",
                "cloud_server_autoscaler_burn_fast",
                "cloud_server_autoscaler_burn_slow",
                "cloud_server_autoscaler_pending_per_replica"):
        assert fam in names, fam


# ---------------------------------------------------------------------------
# live fleet: drain/resume under the autoscaler (zero lost requests)
# ---------------------------------------------------------------------------


def test_scale_down_drain_race_loses_nothing(params):
    """Scale-down mid-flood: the victim still holds in-flight work
    when the autoscaler removes it; drain(migrate=True) must move
    every request and the client sees ZERO losses."""
    def mk():
        return PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)

    router = ReplicatedRouter([mk(), mk()])
    released = []
    asc = SLOBurnAutoscaler(
        router, spawn=lambda role: None, release=released.append,
        config=AutoscalerConfig(min_replicas=1, max_replicas=2,
                                hold_s=0.0, pending_low=100.0,
                                drain_timeout_s=60.0))
    reqs = [router.submit([5, 9, 3], max_new_tokens=6)
            for _ in range(8)]
    router.step()                      # work lands on BOTH replicas
    assert all(r.num_active + r.num_pending > 0
               for r in router.replicas)
    stepper = threading.Thread(
        target=lambda: [router.step() or time.sleep(0.002)
                        for _ in range(4000)], daemon=True)
    stepper.start()
    # idle burns + empty queue threshold met by construction -> down
    assert asc.step(now=1.0) == "down"
    assert len(router.attached_indices()) == 1
    deadline = time.monotonic() + 60.0
    while (not all(r.done for r in reqs)
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert all(r.done for r in reqs)
    assert all(len(r.tokens) == 6 for r in reqs), (
        [(len(r.tokens), r.finish_reason) for r in reqs])
    assert not any(str(r.finish_reason).startswith("error")
                   for r in reqs)
    assert released and released[0].num_active == 0
    released[0].stop()
    router.stop()


# ---------------------------------------------------------------------------
# dispatch-count guard clone: replay-driven traffic on an otherwise
# unconfigured server adds ZERO dispatches/syncs per iteration
# ---------------------------------------------------------------------------


def test_replay_driven_step_dispatch_and_sync_count(params, monkeypatch):
    """The scenario harness drives the UNCONFIGURED serving path
    byte-identically: firing replayed events between steps keeps the
    mixed iteration at exactly ONE fused dispatch + ONE host sync
    (the test_observability guard's invariant, with the replay driver
    in the loop)."""
    from cloud_server_tpu.inference import paged_server as ps
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    warm = srv.submit([5, 9, 3, 1], max_new_tokens=40)
    srv.step()  # a warm decode runs while the replay fires events
    assert srv.num_active == 1

    events = [Event(time_s=0.1 * k, session=k, turn=0, tenant=None,
                    prompt=tuple([(k * 7 + j) % 60 + 1
                                  for j in range(20)]),
                    max_new_tokens=3)
              for k in range(6)]
    drv = ReplayDriver(srv, events)

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
                        lambda x: calls.__setitem__(
                            "get", calls["get"] + 1) or orig_get(x))

    now = 0.0
    steps = churn_steps = 0
    while (not drv.done or srv._jobs or srv.num_pending
           or srv.num_active):
        drv.tick(now)
        # the proven invariant's precondition: admissions in flight
        # when the step begins (test_observability's guard loop)
        churn = bool(srv._jobs or srv.num_pending)
        before = dict(calls)
        srv.step()
        if churn:
            churn_steps += 1
            assert calls["dispatch"] - before["dispatch"] == 1, \
                "replay-driven iteration must stay ONE fused dispatch"
            assert calls["get"] - before["get"] == 1, \
                "replay-driven iteration must stay ONE host sync"
        now += 0.1
        steps += 1
        assert steps < 300
    assert churn_steps >= 2  # the invariant really ran under churn
    for n, f in origs.items():
        monkeypatch.setattr(ps, n, f)
    monkeypatch.setattr(jax, "device_get", orig_get)
    assert warm.done
    assert drv.result()["completed"] == len(events)
    assert drv.result()["failed"] == 0
    srv.stop()
