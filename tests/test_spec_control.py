"""Adaptive speculative-decoding control: the host-side controller's
hysteresis/probe/staleness law (pure-Python unit tests), the
dispatch-count regression for the fused mixed+draft-spec+adaptive path
(one fused dispatch, one host sync per iteration — the controller adds
ZERO device work), the QoS wasted-speculation ledger, and the /stats
`speculation` summary's fleet merge."""

import dataclasses

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.qos import TenantRegistry
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.inference.spec_control import (
    SpecControlConfig, SpecController, resolve_controller)
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
SRV_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 48])


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


def _draft_setup():
    draft_cfg = dataclasses.replace(CFG, embed_dim=16, num_layers=1,
                                    num_heads=2, num_kv_heads=2,
                                    mlp_dim=32)
    draft_params = transformer.init_params(draft_cfg, jax.random.key(9))
    return draft_params, draft_cfg


# ---------------------------------------------------------------------------
# controller law (no jax, no server)
# ---------------------------------------------------------------------------


def _ctl(**kw):
    has_draft = kw.pop("has_draft_model", False)
    return SpecController(kw.pop("max_drafts", 3),
                          SpecControlConfig(**kw),
                          has_draft_model=has_draft)


def test_starts_at_initial_and_climbs_on_acceptance():
    c = _ctl(initial=1, high=0.5, low=0.2, ewma=0.5, cooldown=2)
    c.on_admit(0)
    assert c.draft_len(0) == 1
    for _ in range(8):
        c.observe(0, drafted=c.draft_len(0), accepted=c.draft_len(0))
    assert c.draft_len(0) == 3  # climbed to max_drafts
    assert c.length_changes >= 2


def test_decays_to_zero_on_rejection_and_cooldown_gates_changes():
    c = _ctl(low=0.3, high=0.7, ewma=0.5, cooldown=3)
    c.on_admit(0)
    assert c.draft_len(0) == 3  # optimistic default start
    changes = []
    for _ in range(24):
        before = c.draft_len(0)
        c.observe(0, drafted=before, accepted=0)
        if c.draft_len(0) != before:
            changes.append(before)
    assert c.draft_len(0) == 0  # all-rejected converges to plain decode
    # hysteresis: lengths stepped down one at a time, never jumped
    assert changes == [3, 2, 1]


def test_ngram_probe_recovers_from_zero():
    c = _ctl(low=0.3, high=0.6, ewma=1.0, cooldown=1, probe_period=4)
    c.on_admit(0)
    for _ in range(8):
        c.observe(0, c.draft_len(0), 0)
    assert c.draft_len(0) == 0
    for _ in range(4):  # zero-length rounds accrue probe credit
        c.observe(0, 0, 0)
    assert c.draft_len(0) == 1  # probed back on


def test_draft_model_plain_dispatch_is_sticky_off():
    c = _ctl(low=0.3, high=0.6, ewma=1.0, cooldown=1, probe_period=3,
             has_draft_model=True)
    c.on_admit(0)
    for _ in range(3):  # one step down per all-rejected round
        c.observe(0, c.draft_len(0), 0)
    assert c.draft_len(0) == 0
    c.on_plain_dispatch([0], rounds=8)  # draft cache goes stale
    for _ in range(16):
        c.observe(0, 0, 0)
    assert c.draft_len(0) == 0, "stale draft cache must never probe back"
    c.on_admit(0)  # re-admission re-prefills the draft cache
    assert c.draft_len(0) == 3


def test_release_forgets_slot_state():
    c = _ctl(ewma=1.0, cooldown=1)
    c.on_admit(0)
    c.observe(0, 3, 0)
    c.on_release(0)
    c.observe(0, 3, 0)  # unknown slot: ignored, no crash
    c.on_admit(0)
    assert c.draft_len(0) == 3


def test_resolve_controller_forms():
    assert resolve_controller(False, "", 3, has_draft_model=False) is None
    assert resolve_controller(None, "off", 3,
                              has_draft_model=False) is None
    assert resolve_controller(None, "", 0, has_draft_model=False) is None
    c = resolve_controller(None, "", 3, has_draft_model=True)
    assert isinstance(c, SpecController) and c.has_draft_model
    c = resolve_controller({"low": 0.1, "high": 0.9, "initial": 2}, "",
                           4, has_draft_model=False)
    assert c.config.initial == 2 and c.max_drafts == 4
    with pytest.raises(ValueError, match="unknown spec_control"):
        resolve_controller({"lo": 0.1}, "", 3, has_draft_model=False)
    with pytest.raises(ValueError, match="low"):
        SpecControlConfig(low=0.9, high=0.5)
    # a pre-built controller must agree with the server's spec_drafts:
    # planning lengths above the dispatch width would overbill the
    # drafted ledgers and depress every accept rate
    ready = SpecController(5)
    with pytest.raises(ValueError, match="max_drafts"):
        resolve_controller(ready, "", 3, has_draft_model=False)
    assert resolve_controller(ready, "", 5,
                              has_draft_model=False) is ready


# ---------------------------------------------------------------------------
# dispatch-count regression: one fused dispatch + one sync with
# draft-model speculation AND the adaptive controller live
# ---------------------------------------------------------------------------


def test_mixed_draft_spec_adaptive_dispatch_and_sync_count(
        params, monkeypatch):
    """The fused mixed+draft-spec+adaptive iteration still issues
    exactly ONE `_mixed_step` dispatch and ONE `device_get` per step
    while an admission is in flight — the draft model's prefill and
    per-round decode ride inside the one program, and the controller
    (planning, feedback, flight fields) is pure host arithmetic on the
    counts that single sync already returned."""
    from cloud_server_tpu.inference import paged_server as ps
    draft_params, draft_cfg = _draft_setup()
    srv = PagedInferenceServer(
        params, CFG, GREEDY, spec_drafts=2,
        draft_params=draft_params, draft_cfg=draft_cfg,
        spec_control={"cooldown": 1, "ewma": 0.5}, **SRV_KW)
    assert srv.spec_control is not None
    warm = srv.submit([5, 9, 3, 1], max_new_tokens=24)
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

    long = srv.submit([(k * 7) % 60 + 1 for k in range(40)],
                      max_new_tokens=4)
    churn_steps = 0
    while srv._jobs or srv.num_pending:
        before = dict(calls)
        srv.step()
        churn_steps += 1
        assert calls["dispatch"] - before["dispatch"] == 1, \
            "mixed+draft-spec iteration must stay ONE fused dispatch"
        assert calls["get"] - before["get"] == 1, \
            "mixed+draft-spec iteration must stay ONE host sync"
        assert churn_steps < 50
    assert churn_steps >= 2  # the admission really spanned iterations
    assert calls["mixed"] >= 2
    for n, f in origs.items():
        monkeypatch.setattr(ps, n, f)
    monkeypatch.setattr(jax, "device_get", orig_get)
    srv.run_until_idle()
    assert warm.done and long.done
    # the ledger was fed from that single sync's counts
    assert srv.spec_tokens_drafted > 0


# ---------------------------------------------------------------------------
# accounting surfaces: flight recorder, QoS ledger, /stats merge
# ---------------------------------------------------------------------------


def test_flight_recorder_and_metrics_record_speculation(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3, **SRV_KW)
    rep = [3, 4, 5, 6] * 5
    srv.generate([rep, [7, 8, 9]], max_new_tokens=10)
    recs = [r for r in srv.flight_window() if r.get("spec_rows")]
    assert recs, "no speculative iteration recorded"
    r = recs[-1]
    assert r["spec_window"] >= 2
    assert "spec_tokens_drafted" in r and "spec_tokens_accepted" in r
    assert "spec_draft_lens" in r  # adaptive on by default
    snap = srv.metrics_snapshot()
    drafted = snap["cloud_server_spec_tokens_drafted_total"]["value"]
    accepted = snap["cloud_server_spec_tokens_accepted_total"]["value"]
    assert drafted > 0 and 0 <= accepted <= drafted
    assert 0.0 <= snap["cloud_server_spec_accept_rate"]["value"] <= 1.0
    stats = srv.speculation_stats()
    assert stats["enabled"] and stats["adaptive"]
    assert stats["tokens_drafted"] == drafted
    assert stats["tokens_accepted"] == accepted


def test_qos_wasted_speculation_ledger(params):
    """Committed tokens bill the generated bucket; rejected draft work
    lands on the per-tenant wasted-speculation counter only."""
    reg = TenantRegistry({"tenants": {"a": {"weight": 2.0}}})
    srv = PagedInferenceServer(params, CFG, GREEDY, spec_drafts=3, qos=reg, **SRV_KW)
    r = srv.submit([3, 4, 5, 6] * 5, max_new_tokens=10, tenant="a")
    srv.run_until_idle()
    s = reg.stats()["a"]
    assert s["generated"] == len(r.tokens)  # only committed tokens
    assert s["spec_drafted"] >= s["spec_accepted"] >= 0
    assert s["spec_wasted"] == s["spec_drafted"] - s["spec_accepted"]
    snap = srv.metrics_snapshot()
    key = 'cloud_server_tenant_spec_wasted_tokens_total{tenant="a"}'
    assert snap[key]["value"] == s["spec_wasted"]


def test_router_merges_speculation_stats(params):
    """Fleet /stats `speculation`: counts sum across replicas and the
    accept-rate ratio recomputes from the merged totals (never a sum
    of per-replica ratios), like tenant_fair_share."""
    reps = [PagedInferenceServer(params, CFG, GREEDY, spec_drafts=2, **SRV_KW)
            for _ in range(2)]
    router = ReplicatedRouter(reps)
    for rep in reps:  # drive each replica directly so both have counts
        rep.generate([[3, 4, 5, 6] * 4], max_new_tokens=8)
    merged = router.speculation_stats()
    assert merged["tokens_drafted"] == sum(
        rep.spec_tokens_drafted for rep in reps)
    assert merged["tokens_accepted"] == sum(
        rep.spec_tokens_accepted for rep in reps)
    assert merged["accept_rate"] == pytest.approx(
        merged["tokens_accepted"] / max(merged["tokens_drafted"], 1))
    snap = router.metrics_snapshot()
    assert snap["cloud_server_spec_accept_rate"]["value"] == \
        pytest.approx(merged["accept_rate"])
