"""Multi-tenant QoS: token buckets, deficit-round-robin admission,
weighted fairness under flood (the 3:1 property), starvation freedom,
priority preemption, differentiated per-tenant 429s with Retry-After,
and the zero-extra-dispatch guarantee with QoS ENABLED.

The load-bearing default-path property — with no QoS config the
schedulers are byte-identical to main — is pinned two ways: the
pre-existing mixed-vs-alternating exact-output tests run unchanged,
and `test_single_tenant_parity` here shows a configured-but-single-
tenant registry still produces token-for-token the same outputs."""

import dataclasses
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.qos import (
    DEFAULT_TENANT, TenantConfig, TenantQueueFullError, TenantRegistry,
    TokenBucket, resolve_registry)
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.inference.request import QueueFullError
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


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@dataclasses.dataclass
class _FakeReq:
    prompt: list
    tenant: str | None = None
    tokens: list = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# token bucket
# ---------------------------------------------------------------------------


def test_token_bucket_refill_burst_and_retry_after():
    clk = _Clock()
    b = TokenBucket(rate=10.0, burst=20.0, clock=clk)
    assert b.level() == 20.0  # starts full
    assert b.try_consume(20.0)
    assert not b.try_consume(1.0)  # empty
    assert b.retry_after(5.0) == pytest.approx(0.5)  # 5 tokens @ 10/s
    clk.t += 0.5
    assert b.level() == pytest.approx(5.0)
    assert b.try_consume(5.0)
    # refill never exceeds burst
    clk.t += 100.0
    assert b.level() == pytest.approx(20.0)
    # charge() takes debt below zero; retry_after(0) = time out of debt
    b.charge(30.0)
    assert b.level() == pytest.approx(-10.0)
    assert b.retry_after(0.0) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0)


def test_tenant_config_validation():
    with pytest.raises(ValueError, match="weight"):
        TenantConfig(name="x", weight=0.0)
    with pytest.raises(ValueError, match="priority"):
        TenantConfig(name="x", priority="turbo")
    with pytest.raises(ValueError, match="max_pending"):
        TenantConfig(name="x", max_pending=-1)
    with pytest.raises(ValueError, match="prompt_tokens_per_s"):
        TenantConfig(name="x", prompt_tokens_per_s=-5.0)
    with pytest.raises(ValueError, match="burst"):
        TenantConfig(name="x", prompt_burst=10.0)  # burst without rate
    with pytest.raises(ValueError, match="burst"):
        TenantConfig(name="x", prompt_tokens_per_s=10.0,
                     prompt_burst=0.0)  # would reject forever


def test_registry_config_parsing(tmp_path):
    cfg = {"quantum": 8,
           "tenants": {"a": {"weight": 3.0, "api_keys": ["k-1"]},
                       "b": {"priority": "best_effort"}}}
    reg = resolve_registry(json.dumps(cfg))
    assert reg.weight("a") == 3.0
    assert reg.tenant_for_api_key("k-1") == "a"
    assert reg.tenant_for_api_key("nope") is None
    assert reg.priority_rank("b") == 2
    assert reg.priority_rank("unseen") == 0  # default policy
    # file path form
    p = tmp_path / "qos.json"
    p.write_text(json.dumps(cfg))
    assert resolve_registry(str(p)).weight("a") == 3.0
    # disabled forms
    assert resolve_registry(None, "") is None
    assert resolve_registry(None, json.dumps(cfg)).weight("a") == 3.0
    with pytest.raises(ValueError, match="unknown qos config keys"):
        TenantRegistry({"tenant": {}})
    with pytest.raises(ValueError, match="api key"):
        TenantRegistry({"tenants": {"a": {"api_keys": ["k"]},
                                    "b": {"api_keys": ["k"]}}})


# ---------------------------------------------------------------------------
# deficit-round-robin admission (synthetic queues)
# ---------------------------------------------------------------------------


def test_drr_single_tenant_degenerates_to_fifo():
    reg = TenantRegistry({})
    pending = [_FakeReq([1] * 5) for _ in range(6)]
    for _ in range(20):
        idx = reg.next_admission_index(pending)
        assert idx == 0  # always the queue head == plain FIFO
        reg.charge_admission(None, 5)
    assert reg.next_admission_index([]) is None


def test_drr_weighted_interleave_and_fifo_within_tenant():
    reg = TenantRegistry({"quantum": 1,
                          "tenants": {"a": {"weight": 3.0},
                                      "b": {"weight": 1.0}}})
    pending = ([_FakeReq([1] * 3, "a") for _ in range(30)]
               + [_FakeReq([1] * 3, "b") for _ in range(30)])
    for i, req in enumerate(pending):
        req.seq = i
    picks = []
    while len(picks) < 24:
        idx = reg.next_admission_index(pending)
        req = pending.pop(idx)
        reg.charge_admission(req.tenant, len(req.prompt))
        picks.append(req)
    a = sum(r.tenant == "a" for r in picks)
    b = len(picks) - a
    assert b > 0 and 2.0 <= a / b <= 4.0, (a, b)
    # FIFO preserved within each tenant
    for t in ("a", "b"):
        seqs = [r.seq for r in picks if r.tenant == t]
        assert seqs == sorted(seqs)


def test_drr_huge_cost_uses_closed_form_topup():
    """A preempted continuation with a huge DRR cost (prompt+tokens)
    must not pay cost/quantum lock-held scan rounds per pick: the
    deficit top-up is closed-form, and the weighted order and
    deficit state match the round-by-round definition."""
    reg = TenantRegistry({"quantum": 1,
                          "tenants": {"a": {"weight": 3.0},
                                      "b": {"weight": 1.0}}})
    picks = []
    for _ in range(4):
        pending = [_FakeReq([1] * 500_000, "a"),
                   _FakeReq([1] * 500_000, "b")]
        idx = reg.next_admission_index(pending)
        picks.append(pending[idx].tenant)
        reg.charge_admission(pending[idx].tenant, 500_000)
    # weights hold at huge costs: b's deficit accrues across a's picks
    # until it covers a whole 500k head — 3:1, not a-forever
    assert picks == ["a", "a", "a", "b"]


def test_drr_work_conserving_when_all_over_budget():
    """Tenants in generated-token debt are skipped only while another
    tenant is eligible; when everyone is over budget the pick falls
    back to plain DRR instead of idling."""
    clk = _Clock()
    reg = TenantRegistry(
        {"quantum": 1,
         "tenants": {"a": {"generated_tokens_per_s": 10.0},
                     "b": {"generated_tokens_per_s": 10.0}}},
        clock=clk)
    reg.charge_generated("a", 100)  # deep debt
    pending = [_FakeReq([1] * 3, "a"), _FakeReq([1] * 3, "b")]
    idx = reg.next_admission_index(pending)
    assert pending[idx].tenant == "b"  # a skipped while b eligible
    reg.charge_generated("b", 100)  # now both in debt
    idx = reg.next_admission_index(pending)
    assert idx is not None  # work-conserving fallback still picks


def test_victim_rank_uses_recent_decayed_usage():
    """Preemption's "most over fair share" key is a decayed RATE, not
    a lifetime total: an established tenant's ancient history must not
    shield a tenant flooding right now."""
    clk = _Clock()
    reg = TenantRegistry({"tenants": {"old": {}, "hot": {}}}, clock=clk)
    reg.charge_generated("old", 1_000_000)  # ancient history
    clk.t += 600.0  # 20 half-lives later...
    reg.charge_generated("hot", 1_000)  # ...someone floods NOW
    assert reg.victim_rank("hot")[1] > reg.victim_rank("old")[1]
    # same priority class, so the current flooder is the victim
    assert max(["old", "hot"], key=reg.victim_rank) == "hot"
    # lifetime totals still feed the fair-share REPORTING view
    assert reg.stats()["old"]["generated"] == 1_000_000


def test_compute_fair_shares_is_the_single_definition():
    from cloud_server_tpu.inference.qos import compute_fair_shares
    assert compute_fair_shares({}) == {}
    even = compute_fair_shares({"a": (3.0, 30.0), "b": (1.0, 10.0)})
    assert even["a"] == pytest.approx(1.0)
    assert even["b"] == pytest.approx(1.0)
    skew = compute_fair_shares({"a": (3.0, 10.0), "b": (1.0, 10.0)})
    assert skew["b"] > 1.0 > skew["a"]
    # the registry's view IS this function (so the fleet merge in
    # ReplicatedRouter.tenant_stats can never diverge from it)
    reg = TenantRegistry({"tenants": {"a": {"weight": 3.0}}})
    reg.charge_generated("a", 30)
    reg.charge_generated(None, 10)
    assert reg.fair_shares() == pytest.approx(compute_fair_shares(
        {"a": (3.0, 30.0), DEFAULT_TENANT: (1.0, 10.0)}))


def test_gate_submit_differentiated_backpressure():
    clk = _Clock()
    reg = TenantRegistry(
        {"tenants": {"capped": {"max_pending": 1},
                     "limited": {"prompt_tokens_per_s": 10.0,
                                 "prompt_burst": 10.0}}},
        clock=clk)
    reg.gate_submit("capped", 4)  # fills the bound
    with pytest.raises(TenantQueueFullError) as exc:
        reg.gate_submit("capped", 4)
    assert exc.value.tenant == "capped"
    assert exc.value.retry_after_s >= 0.0
    assert isinstance(exc.value, QueueFullError)  # HTTP 429 mapping
    # other tenants keep admitting
    reg.gate_submit("other", 4)
    # prompt token bucket: burst 10 then a 429 carrying the refill time
    reg.gate_submit("limited", 10)
    with pytest.raises(TenantQueueFullError) as exc:
        reg.gate_submit("limited", 5)
    assert exc.value.retry_after_s == pytest.approx(0.5)
    # the rejected submit left no pending trace
    assert reg.stats()["limited"]["pending"] == 1
    assert reg.stats()["limited"]["rejected"] == 1
    reg.on_pending_removed("capped")
    reg.gate_submit("capped", 4)  # freed capacity admits again
    # a prompt larger than the burst could NEVER be admitted: terminal
    # ValueError (HTTP 400), not a retry-forever 429
    with pytest.raises(ValueError, match="burst capacity"):
        reg.gate_submit("limited", 11)


def test_unknown_tenants_collapse_to_default():
    """The tenant set is frozen at construction: spoofed X-Tenant names
    share the default bucket instead of minting new per-tenant state —
    no unbounded host memory / metric cardinality, and no fair-share
    multiplication for a flooder cycling names."""
    reg = TenantRegistry({"tenants": {"a": {"weight": 3.0}}})
    for i in range(50):
        assert reg.resolve(f"spoof-{i}") == DEFAULT_TENANT
        reg.gate_submit(f"spoof-{i}", 2)
    stats = reg.stats()
    assert set(stats) == {DEFAULT_TENANT, "a"}  # nothing minted
    assert stats[DEFAULT_TENANT]["pending"] == 50  # one shared bucket
    # force-off sentinel: False disables even when a config fallback
    # string is present (the bench's control arm depends on this)
    assert resolve_registry(False, '{"tenants": {"a": {}}}') is None


# ---------------------------------------------------------------------------
# server integration: parity, fairness, starvation, preemption
# ---------------------------------------------------------------------------


def _engine_reference(params, prompt, n_new):
    icfg = dataclasses.replace(GREEDY, max_decode_len=n_new)
    toks = engine.generate(
        params, np.asarray([prompt], np.int32), jax.random.key(1),
        cfg=CFG, infer_cfg=icfg)
    return list(np.asarray(toks)[0])


LONG = [(i * 7) % 60 + 1 for i in range(30)]
PROMPTS = [[5, 9, 3], [17, 2, 40, 8, 21], LONG, list(range(1, 14))]


def _staggered_run(srv, prompts, max_new):
    reqs = [srv.submit(p, max_new_tokens=max_new) for p in prompts[:2]]
    for _ in range(3):
        srv.step()
    reqs += [srv.submit(p, max_new_tokens=max_new) for p in prompts[2:]]
    srv.run_until_idle()
    return [r.result() for r in reqs]


def test_single_tenant_parity_token_for_token(params):
    """A configured registry with only the implicit default tenant must
    not change ONE token of the mixed scheduler's output — DRR over a
    single tenant IS FIFO, and weighted-fair prefill funding over one
    tenant IS the FIFO job order."""
    plain = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    qosd = PagedInferenceServer(params, CFG, GREEDY, qos={"default": {"weight": 2.0}},
                                **PAGED_KW)
    out_p = _staggered_run(plain, PROMPTS, 12)
    out_q = _staggered_run(qosd, PROMPTS, 12)
    assert out_p == out_q
    assert qosd.qos.stats()[DEFAULT_TENANT]["generated"] > 0


def test_fairness_converges_to_weight_ratio(params):
    """THE fairness property: two tenants with 3:1 weights submit
    identical floods; per-tenant generated-token counts converge to
    ~3:1 while both backlogs last."""
    srv = PagedInferenceServer(
        params, CFG, GREEDY, qos={"quantum": 1, "tenants": {"a": {"weight": 3.0},
                                       "b": {"weight": 1.0}}},
        **{**PAGED_KW, "max_slots": 2})
    reqs = []
    for i in range(24):  # identical interleaved floods
        reqs.append(srv.submit([5, 9, 3], max_new_tokens=4, tenant="a"))
        reqs.append(srv.submit([5, 9, 3], max_new_tokens=4, tenant="b"))
    for _ in range(400):
        srv.step()
        s = srv.qos.stats()
        if s["a"]["generated"] + s["b"]["generated"] >= 60:
            break
    s = srv.qos.stats()
    assert s["b"]["generated"] > 0, "low-weight tenant fully starved"
    ratio = s["a"]["generated"] / s["b"]["generated"]
    assert 2.0 <= ratio <= 4.5, s
    # fair_share normalizes by weight: both near 1.0 under saturation
    assert 0.6 <= s["a"]["fair_share"] <= 1.4, s
    assert 0.6 <= s["b"]["fair_share"] <= 1.4, s
    for r in reqs:
        r.cancel()
    srv.run_until_idle()


def test_starvation_free_best_effort_under_interactive_flood(params):
    """A best-effort tenant still makes progress while an interactive
    tenant floods: its admissions interleave into the flood (bounded
    queue-wait) instead of waiting for the flood to drain."""
    srv = PagedInferenceServer(
        params, CFG, GREEDY, qos={"quantum": 1,
             "tenants": {"fg": {"weight": 8.0, "priority": "interactive"},
                         "bg": {"weight": 1.0,
                                "priority": "best_effort"}}},
        **{**PAGED_KW, "max_slots": 2})
    fg = [srv.submit([5, 9, 3], max_new_tokens=4, tenant="fg")
          for _ in range(20)]
    bg = [srv.submit([7, 7, 2], max_new_tokens=4, tenant="bg")
          for _ in range(2)]
    srv.run_until_idle()
    assert all(r.done for r in fg + bg)
    last_fg_admit = max(r.admit_time for r in fg)
    for r in bg:
        assert r.admit_time is not None
        assert r.admit_time < last_fg_admit, \
            "best-effort tenant waited out the whole interactive flood"
        assert r.emit_times and r.emit_times[0] < last_fg_admit


def test_preemption_victim_order_prefers_best_effort(params):
    """Victim selection is (lowest priority class, most over fair
    share, youngest): the OLDEST live slot — which youngest-only
    preemption would never evict first — is chosen when it belongs to
    the best-effort tenant."""
    srv = PagedInferenceServer(
        params, CFG, GREEDY, allocation="ondemand",
        max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
        prompt_buckets=[16], num_pages=32, decode_chunk=1,
        qos={"tenants": {"bg": {"priority": "best_effort"},
                         "fg": {"priority": "interactive"}}})
    reqs = [srv.submit([5 + i, 9, 3, 1 + i], max_new_tokens=30,
                       tenant="bg" if i == 0 else "fg")
            for i in range(4)]
    for _ in range(30):  # ample pages: everyone activates, no famine
        srv.step()
        if int(srv.active.sum()) == 4 and not srv._jobs:
            break
    assert int(srv.active.sum()) == 4
    by_tenant = {srv._slots[i].req.tenant: i for i in range(4)}
    bg_slot = next(i for i in range(4)
                   if srv._slots[i].req.tenant == "bg")
    assert srv._slots[bg_slot].admit_seq == min(
        srv._slots[i].admit_seq for i in range(4))  # bg IS the oldest
    assert srv._preempt_youngest(protect=by_tenant["fg"])
    assert srv.num_pending == 1
    with srv._lock:
        victim = srv._pending[0]
    assert victim.tenant == "bg", \
        "best-effort slot must be evicted before any interactive one"
    assert srv.qos.stats()["bg"]["preempt_requeues"] == 1
    for r in reqs:
        r.cancel()
    srv.run_until_idle()


def test_preemption_under_qos_keeps_outputs_exact(params):
    """Page-famine preemption/requeue through the QoS victim order
    keeps every output token-for-token exact (the continuation
    re-admits through DRR), and preempt-requeues carry the tenant tag
    into the flight recorder and per-tenant counters."""
    prompts = [[(i * 9 + k) % 60 + 1 for k in range(8)] for i in range(6)]
    srv = PagedInferenceServer(
        params, CFG, GREEDY, allocation="ondemand",
        max_slots=6, max_context=64, page_size=8, prefill_chunk=16,
        prompt_buckets=[16], num_pages=12, decode_chunk=2,
        qos={"tenants": {"bg": {"priority": "best_effort"},
                         "fg": {"priority": "interactive"}}})
    reqs = [srv.submit(p, max_new_tokens=40,
                       tenant="bg" if i == 0 else "fg")
            for i, p in enumerate(prompts)]
    srv.run_until_idle()
    assert srv.preemptions > 0
    tagged = [t for rec in srv.flight_window()
              for t in rec.get("preempt_tenants", ())]
    assert len(tagged) == srv.preemptions
    stats = srv.qos.stats()
    assert (stats["bg"]["preempt_requeues"]
            + stats["fg"]["preempt_requeues"]) == srv.preemptions
    for p, r in zip(prompts, reqs):
        assert r.result() == _engine_reference(params, p, 40), p


# ---------------------------------------------------------------------------
# zero-extra-dispatch guarantee with QoS enabled
# ---------------------------------------------------------------------------


def test_mixed_step_dispatch_count_with_qos(params, monkeypatch):
    """QoS admission policy runs on host state the scheduler already
    owns: a two-tenant mixed iteration still issues exactly ONE fused
    dispatch and ONE host sync per step (the same regression guard the
    observability PR pinned for the unconfigured server)."""
    from cloud_server_tpu.inference import paged_server as ps
    srv = PagedInferenceServer(
        params, CFG, GREEDY, qos={"tenants": {"a": {"weight": 3.0}, "b": {"weight": 1.0}}},
        **PAGED_KW)
    warm = srv.submit([5, 9, 3, 1], max_new_tokens=24, tenant="a")
    srv.step()
    assert srv.num_active == 1

    # the (default) async scheduler dispatches _mixed_step while the
    # planned frame has prefill work and the decode/spec program on
    # kind-transition steps — ONE fused dispatch either way
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

    srv.submit([(k * 7) % 60 + 1 for k in range(40)],
               max_new_tokens=4, tenant="b")
    srv.submit([(k * 5) % 60 + 1 for k in range(20)],
               max_new_tokens=4, tenant="a")
    churn_steps = 0
    while srv._jobs or srv.num_pending:
        before = dict(calls)
        srv.step()
        churn_steps += 1
        assert calls["dispatch"] - before["dispatch"] == 1, \
            "QoS must not add dispatches to the mixed iteration"
        assert calls["get"] - before["get"] == 1, \
            "QoS must not add host syncs to the mixed iteration"
        assert churn_steps < 60
    assert churn_steps >= 2
    assert calls["mixed"] >= 2
    for n, f in origs.items():
        monkeypatch.setattr(ps, n, f)
    monkeypatch.setattr(jax, "device_get", orig_get)
    srv.run_until_idle()
    assert warm.done


# ---------------------------------------------------------------------------
# per-tenant metrics + HTTP surface
# ---------------------------------------------------------------------------


def test_per_tenant_labeled_metrics(params):
    srv = PagedInferenceServer(
        params, CFG, GREEDY,
        qos={"tenants": {"a": {"weight": 3.0}, "b": {"weight": 1.0}}},
        **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=3, tenant="a")
    srv.submit([7, 7, 2], max_new_tokens=3, tenant="b")
    srv.run_until_idle()
    snap = srv.metrics_snapshot()
    for t in ("a", "b"):
        key = f'cloud_server_tenant_generated_tokens_total{{tenant="{t}"}}'
        assert snap[key]["value"] == 3.0
        assert snap[key]["labels"] == {"tenant": t}
        fair = snap[f'cloud_server_tenant_fair_share{{tenant="{t}"}}']
        assert fair["type"] == "gauge"
        ttft = snap[f'cloud_server_tenant_ttft_seconds{{tenant="{t}"}}']
        assert ttft["type"] == "histogram" and ttft["count"] == 1
    from cloud_server_tpu.utils.serving_metrics import render_prometheus
    text = render_prometheus(snap)
    # one HELP/TYPE per family, one sample per labeled series
    family = "cloud_server_tenant_generated_tokens_total"
    lines = text.splitlines()
    assert sum(ln.startswith(f"# TYPE {family} ") for ln in lines) == 1
    assert f'{family}{{tenant="a"}} 3.0' in lines
    assert f'{family}{{tenant="b"}} 3.0' in lines


@pytest.fixture()
def qos_frontend(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(
        params, CFG, GREEDY,
        qos={"tenants": {
            "capped": {"max_pending": 0},
            "keyed": {"weight": 2.0, "api_keys": ["sk-test-1"]}}},
        **PAGED_KW).start()
    front = HttpFrontend(srv).start()
    yield front, srv
    front.stop()
    srv.stop()


def _post(front, path, body, headers=None):
    host, port = front.address
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=60)


def test_http_429_structured_with_retry_after(qos_frontend):
    front, srv = qos_frontend
    body = {"tokens": [5, 9, 3], "max_new_tokens": 2}
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(front, "/generate", body, {"X-Tenant": "capped"})
    err = exc.value
    assert err.code == 429
    assert int(err.headers["Retry-After"]) >= 1
    payload = json.loads(err.read())
    assert payload["tenant"] == "capped"
    assert payload["retry_after_s"] >= 0.0
    assert "retry" in payload["error"]
    # other tenants keep admitting through the same frontend; an
    # UNKNOWN tenant name collapses to the default bucket (untrusted
    # headers must not mint per-tenant state or fair shares)
    with _post(front, "/generate", body, {"X-Tenant": "anyone"}) as resp:
        lines = [json.loads(ln) for ln in resp.read().splitlines()]
    assert lines[-1]["done"] is True
    assert srv.qos.stats()["capped"]["rejected"] == 1
    assert "anyone" not in srv.qos.stats()
    assert srv.qos.stats()[DEFAULT_TENANT]["submitted"] == 1


def test_http_api_key_maps_to_tenant_and_stats(qos_frontend):
    front, srv = qos_frontend
    body = {"tokens": [5, 9, 3], "max_new_tokens": 2}
    with _post(front, "/generate", body,
               {"Authorization": "Bearer sk-test-1"}) as resp:
        resp.read()
    assert srv.qos.stats()["keyed"]["submitted"] == 1
    # anonymous requests ride the implicit default tenant
    with _post(front, "/generate", body) as resp:
        resp.read()
    assert srv.qos.stats()[DEFAULT_TENANT]["submitted"] == 1
    # /stats exposes the per-tenant section
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}/stats",
                                timeout=60) as resp:
        stats = json.loads(resp.read())
    assert stats["tenants"]["keyed"]["generated"] == 2
    assert stats["tenants"]["keyed"]["weight"] == 2.0


def test_http_header_cannot_impersonate_keyed_tenant(qos_frontend):
    """The X-Tenant header is trusted only for tenants with no
    configured api_keys: a bare header claiming a key-protected tenant
    falls through to anonymous/default, and a valid key beats a
    conflicting header claim."""
    front, srv = qos_frontend
    assert front._resolve_tenant({"X-Tenant": "keyed"}) is None
    assert front._resolve_tenant({"X-Tenant": "capped"}) == "capped"
    assert front._resolve_tenant(
        {"Authorization": "Bearer sk-test-1"}) == "keyed"
    assert front._resolve_tenant(
        {"X-Tenant": "capped",
         "Authorization": "Bearer sk-test-1"}) == "keyed"
    # RFC 7235: the auth scheme is case-insensitive
    assert front._resolve_tenant(
        {"Authorization": "bearer sk-test-1"}) == "keyed"
    # end-to-end: a header-only submit bills default, never "keyed"
    body = {"tokens": [5, 9, 3], "max_new_tokens": 2}
    with _post(front, "/generate", body, {"X-Tenant": "keyed"}) as resp:
        resp.read()
    stats = srv.qos.stats()
    assert stats["keyed"]["submitted"] == 0
    assert stats[DEFAULT_TENANT]["submitted"] == 1


def test_http_tenant_header_ignored_without_qos():
    """With QoS disabled there is no frozen tenant set to bound header
    values, so X-Tenant must be ignored entirely — otherwise an
    attacker cycling header values mints one permanent labeled TTFT
    histogram per name (unbounded metric cardinality)."""
    from cloud_server_tpu.inference.http_server import HttpFrontend

    class _NoQosBackend:
        pass  # no `qos` attribute, like any server without a registry

    front = HttpFrontend.__new__(HttpFrontend)  # no socket bind needed
    front.srv = _NoQosBackend()
    assert front._resolve_tenant({"X-Tenant": "anyone"}) is None
    assert front._resolve_tenant(
        {"Authorization": "Bearer sk-test-1"}) is None


# ---------------------------------------------------------------------------
# router: tenant affinity + merged per-tenant stats
# ---------------------------------------------------------------------------


def test_router_tenant_affinity_and_merged_stats(params):
    qos_cfg = {"tenants": {"a": {"weight": 3.0}, "b": {"weight": 1.0}}}
    replicas = [PagedInferenceServer(params, CFG, GREEDY, qos=qos_cfg,
                                     **PAGED_KW)
                for _ in range(2)]
    router = ReplicatedRouter(replicas)
    assert router.qos is replicas[0].qos
    # idle-fleet affinity: the same tenant picks the same home replica
    assert router._pick(tenant="a") == router._pick(tenant="a")
    reqs = [router.submit([5, 9, 3], max_new_tokens=3, tenant=t)
            for t in ("a", "a", "b", "b")]
    router.run_until_idle()
    assert all(r.done for r in reqs)
    merged = router.tenant_stats()
    assert merged["a"]["submitted"] == 2
    assert merged["b"]["submitted"] == 2
    assert merged["a"]["generated"] == 6
    # merged labeled series add across replicas by series key
    snap = router.metrics_snapshot()
    key = 'cloud_server_tenant_generated_tokens_total{tenant="a"}'
    assert snap[key]["value"] == 6.0
    # ...but the fair-share RATIO gauge must NOT add (two fair
    # replicas are fair, not 2x over-served): the fleet value is
    # recomputed from the merged totals, exactly tenant_stats()'s
    for t in ("a", "b"):
        fair = snap[f'cloud_server_tenant_fair_share{{tenant="{t}"}}']
        assert fair["value"] == pytest.approx(merged[t]["fair_share"])
    assert snap['cloud_server_tenant_fair_share{tenant="a"}'][
        "value"] < 2.0


def test_library_tenant_ignored_without_qos(params):
    """submit(tenant=...) on a QoS-disabled server must not carry the
    raw string onto the request: observe_emit labels TTFT by
    req.tenant, so per-caller strings would mint unbounded labeled
    series with no registry to bound the tenant set."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    req = srv.submit([5, 9, 3], max_new_tokens=2, tenant="evil-123")
    srv.run_until_idle()
    assert req.tenant is None
    assert not any("tenant=" in k for k in srv.metrics_snapshot())
