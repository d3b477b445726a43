"""Per-class SLO tracking: hand-computed window math and burn rates
(fake clock), class mapping from the QoS priority classes, report
merging (fleet semantics), the /slo HTTP surface, gauge mirroring
under the docs drift check's families, and the no-config parity path
(byte-identical pre-SLO behavior)."""

import json
import urllib.request

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.inference.slo import (
    SLOTracker, merge_reports, resolve_slo)
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
PAGED_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
                prompt_buckets=[16, 48])

# generous targets: every CPU-test observation lands "good", making
# counts (not timings) the asserted quantity
EASY = {"windows_s": [10, 60],
        "classes": {"default": {"objective": 0.9, "ttft_s": 30.0,
                                "itl_s": 30.0, "queue_wait_s": 30.0,
                                "e2e_s": 120.0}}}


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


# ---------------------------------------------------------------------------
# window math, hand-computed
# ---------------------------------------------------------------------------


def test_window_math_hand_computed():
    """Four observations at known times against a 1.0 s ttft target,
    objective 0.9: every attainment/burn number is checked by hand."""
    cfg = {"windows_s": [10, 60], "bucket_s": 1,
           "classes": {"default": {"objective": 0.9, "ttft_s": 1.0}}}
    t = SLOTracker(cfg, clock=lambda: 155.0)
    t.observe(None, "ttft", 0.5, 100.5)   # good
    t.observe(None, "ttft", 2.0, 100.7)   # bad (same bucket)
    t.observe(None, "ttft", 0.9, 105.0)   # good
    t.observe(None, "ttft", 0.2, 150.0)   # good
    rep = t.report()  # now = 155.0 via the injected clock
    m = rep["classes"]["default"]["metrics"]["ttft"]
    assert m["target_s"] == 1.0
    # 10 s window (145, 155]: only the t=150 observation
    w10 = m["windows"]["10"]
    assert (w10["good"], w10["total"]) == (1, 1)
    assert w10["attainment"] == 1.0
    assert w10["burn_rate"] == 0.0
    # 60 s window (95, 155]: all four -> 3/4 good; burn = 0.25 / 0.1
    w60 = m["windows"]["60"]
    assert (w60["good"], w60["total"]) == (3, 4)
    assert w60["attainment"] == pytest.approx(0.75)
    assert w60["burn_rate"] == pytest.approx(2.5)
    life = m["lifetime"]
    assert (life["good"], life["total"]) == (3, 4)
    assert life["burn_rate"] == pytest.approx(2.5)
    # windows age out: 60 s later the ring only retains t=150
    rep2 = t.report(now=205.0)
    w60b = rep2["classes"]["default"]["metrics"]["ttft"]["windows"]["60"]
    assert (w60b["good"], w60b["total"]) == (1, 1)
    # ...and lifetime never forgets
    life2 = rep2["classes"]["default"]["metrics"]["ttft"]["lifetime"]
    assert (life2["good"], life2["total"]) == (3, 4)


def test_ring_slot_reuse_discards_stale_buckets():
    """An observation landing in a reused ring slot (same index, new
    absolute bucket) must not inherit the stale slot's counts."""
    cfg = {"windows_s": [5, 10], "bucket_s": 1,
           "classes": {"default": {"objective": 0.5, "ttft_s": 1.0}}}
    t = SLOTracker(cfg, clock=lambda: 0.0)
    t.observe(None, "ttft", 0.1, 3.0)
    # bucket index 3 reused at t=14 (ring size 11: 14 % 11 == 3)
    t.observe(None, "ttft", 0.1, 14.0)
    w = t.report(now=14.5)["classes"]["default"]["metrics"]["ttft"]
    assert w["windows"]["10"]["total"] == 1  # only the t=14 event
    assert w["lifetime"]["total"] == 2


def test_empty_window_semantics():
    cfg = {"windows_s": [10], "classes":
           {"default": {"objective": 0.99, "ttft_s": 1.0}}}
    t = SLOTracker(cfg, clock=lambda: 50.0)
    w = t.report()["classes"]["default"]["metrics"]["ttft"]["windows"]
    assert w["10"]["attainment"] is None
    assert w["10"]["burn_rate"] == 0.0


def test_config_validation():
    with pytest.raises(ValueError):
        SLOTracker({"classes": {}})  # nothing to track
    with pytest.raises(ValueError):
        SLOTracker({"classes": {"a": {"objective": 1.0, "ttft_s": 1}}})
    with pytest.raises(ValueError):
        SLOTracker({"classes": {"a": {"ttft_s": -1}}})
    with pytest.raises(ValueError):
        SLOTracker({"classes": {"a": {}}})  # no targets at all
    with pytest.raises(ValueError):
        SLOTracker({"bogus_key": 1,
                    "classes": {"a": {"ttft_s": 1.0}}})
    with pytest.raises(ValueError):
        SLOTracker({"windows_s": [60, 10],
                    "classes": {"a": {"ttft_s": 1.0}}})


def test_class_fallback_and_drop():
    # no "default" entry: unknown classes are dropped silently
    t = SLOTracker({"windows_s": [10],
                    "classes": {"interactive": {"ttft_s": 1.0}}},
                   clock=lambda: 5.0)
    t.observe(None, "ttft", 0.1, 1.0)          # no class -> dropped
    t.observe("batch", "ttft", 0.1, 1.0)       # unknown -> dropped
    t.observe("interactive", "ttft", 0.1, 1.0)
    t.observe("interactive", "itl", 0.1, 1.0)  # untracked metric
    rep = t.report()
    m = rep["classes"]["interactive"]["metrics"]
    assert m["ttft"]["lifetime"]["total"] == 1
    assert "itl" not in m
    # with a default entry, everything unmatched funnels into it
    t2 = SLOTracker({"windows_s": [10],
                     "classes": {"default": {"ttft_s": 1.0}}},
                    clock=lambda: 5.0)
    t2.observe(None, "ttft", 0.1, 1.0)
    t2.observe("whatever", "ttft", 5.0, 1.0)
    life = t2.report()["classes"]["default"]["metrics"]["ttft"]["lifetime"]
    assert (life["good"], life["total"]) == (1, 2)


def test_resolve_slo_paths(tmp_path):
    assert resolve_slo(None, "") is None
    assert resolve_slo(False, json.dumps(EASY)) is None  # force-off
    t = resolve_slo(EASY)
    assert isinstance(t, SLOTracker)
    assert resolve_slo(t) is t
    assert isinstance(resolve_slo(json.dumps(EASY)), SLOTracker)
    p = tmp_path / "slo.json"
    p.write_text(json.dumps(EASY))
    assert isinstance(resolve_slo(str(p)), SLOTracker)
    assert isinstance(resolve_slo(None, json.dumps(EASY)), SLOTracker)
    with pytest.raises(ValueError):
        resolve_slo([1, 2])  # neither str, dict, tracker, nor None


# ---------------------------------------------------------------------------
# merge (fleet semantics)
# ---------------------------------------------------------------------------


def test_merge_reports_sums_counts_and_recomputes_ratios():
    cfg = {"windows_s": [10], "bucket_s": 1,
           "classes": {"default": {"objective": 0.9, "ttft_s": 1.0}}}
    a = SLOTracker(cfg, clock=lambda: 9.0)
    b = SLOTracker(cfg, clock=lambda: 9.0)
    for v in (0.5, 0.5, 0.5):       # 3 good on replica a
        a.observe(None, "ttft", v, 5.0)
    for v in (0.5, 2.0):            # 1 good, 1 bad on replica b
        b.observe(None, "ttft", v, 5.0)
    merged = merge_reports([a.report(), b.report()])
    w = merged["classes"]["default"]["metrics"]["ttft"]["windows"]["10"]
    assert (w["good"], w["total"]) == (4, 5)
    assert w["attainment"] == pytest.approx(0.8)
    assert w["burn_rate"] == pytest.approx(0.2 / 0.1)
    life = merged["classes"]["default"]["metrics"]["ttft"]["lifetime"]
    assert (life["good"], life["total"]) == (4, 5)
    # empty/None inputs collapse to None (no SLO anywhere)
    assert merge_reports([]) is None
    assert merge_reports([None, None]) is None
    # mismatched windows refuse to merge
    other = SLOTracker({"windows_s": [20], "classes":
                        {"default": {"objective": 0.9, "ttft_s": 1.0}}},
                       clock=lambda: 9.0)
    with pytest.raises(ValueError):
        merge_reports([a.report(), other.report()])


# ---------------------------------------------------------------------------
# live servers: class mapping from QoS, gauges, no-config parity
# ---------------------------------------------------------------------------


def test_class_mapping_from_qos_priority(params):
    """A request's SLO class is its tenant's QoS priority class; the
    per-class counts land accordingly."""
    qos = {"default": {"priority": "best_effort"},
           "tenants": {"team-a": {"priority": "interactive"},
                       "scraper": {"priority": "batch"}}}
    slo = {"windows_s": [60],
           "classes": {"interactive": {"ttft_s": 30.0},
                       "batch": {"ttft_s": 30.0},
                       "default": {"ttft_s": 30.0}}}
    srv = PagedInferenceServer(params, CFG, GREEDY, qos=qos, slo=slo,
                               **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=2, tenant="team-a")
    srv.submit([7, 7, 2], max_new_tokens=2, tenant="scraper")
    # anonymous -> QoS default tenant (best_effort), a class with no
    # SLO entry: the observation funnels into the "default" SLO class
    srv.submit([1, 2, 3], max_new_tokens=2)
    srv.run_until_idle()
    rep = srv.slo_report()
    per_cls = {c: rep["classes"][c]["metrics"]["ttft"]["lifetime"]["total"]
               for c in ("interactive", "batch", "default")}
    assert per_cls == {"interactive": 1, "batch": 1, "default": 1}


def test_server_report_matches_hand_count(params):
    """N finished requests -> exactly N ttft/queue_wait/e2e
    observations and (tokens-1)*N itl observations, all good under
    generous targets."""
    srv = PagedInferenceServer(params, CFG, GREEDY, slo=EASY, **PAGED_KW)
    for i in range(2):
        srv.submit([5 + i, 9, 3], max_new_tokens=4)
    srv.run_until_idle()
    m = srv.slo_report()["classes"]["default"]["metrics"]
    assert m["ttft"]["lifetime"] == {
        "good": 2, "total": 2, "attainment": 1.0, "burn_rate": 0.0}
    assert m["queue_wait"]["lifetime"]["total"] == 2
    assert m["e2e"]["lifetime"]["total"] == 2
    assert m["itl"]["lifetime"]["total"] == 6  # 3 gaps x 2 requests


def test_slo_gauges_in_snapshot(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, slo=EASY, **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=2)
    srv.run_until_idle()
    snap = srv.metrics_snapshot()
    att = {k: v for k, v in snap.items()
           if k.startswith("cloud_server_slo_attainment{")}
    burn = {k: v for k, v in snap.items()
            if k.startswith("cloud_server_slo_burn_rate{")}
    # 4 metrics x 2 windows, one series each
    assert len(att) == 8 and len(burn) == 8
    for entry in list(att.values()) + list(burn.values()):
        assert entry["type"] == "gauge"
        assert set(entry["labels"]) == {"class", "metric", "window_s"}
    key = ('cloud_server_slo_attainment{class="default",'
           'metric="ttft",window_s="10"}')
    assert snap[key]["value"] == 1.0


def test_no_config_parity(params):
    """Without an SLO config nothing changes: no tracker, no slo_class
    on requests, no slo gauge families, /slo reports disabled."""
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    assert srv.slo is None
    req = srv.submit([5, 9, 3], max_new_tokens=2)
    srv.run_until_idle()
    assert req.slo_class is None
    assert srv.slo_report() is None
    # no cloud_server_slo_* FAMILY registered (the anomaly watchdog's
    # always-registered families carry a rule="slo_burn" LABEL, which
    # is not an SLO-tracker family)
    assert not any(k.startswith("cloud_server_slo_")
                   for k in srv.metrics_snapshot())


# ---------------------------------------------------------------------------
# router merge + HTTP surface
# ---------------------------------------------------------------------------


def test_router_slo_report_merges_fleet(params):
    replicas = [PagedInferenceServer(params, CFG, GREEDY, slo=EASY,
                                     **PAGED_KW) for _ in range(2)]
    router = ReplicatedRouter(replicas)
    for i in range(4):
        router.submit([5 + i, 9, 3], max_new_tokens=2)
    router.run_until_idle()
    merged = router.slo_report()
    life = merged["classes"]["default"]["metrics"]["ttft"]["lifetime"]
    assert life["total"] == 4  # fleet-wide, not replica-0's
    per_replica = [r.slo_report()["classes"]["default"]["metrics"]
                   ["ttft"]["lifetime"]["total"] for r in replicas]
    assert sum(per_replica) == 4 and all(v > 0 for v in per_replica)
    # the merged RATIO gauges read the fleet ratio, not a sum of ratios
    snap = router.metrics_snapshot()
    key = ('cloud_server_slo_attainment{class="default",'
           'metric="ttft",window_s="10"}')
    assert snap[key]["value"] <= 1.0
    # a router over slo-less replicas reports None
    bare = ReplicatedRouter([PagedInferenceServer(params, CFG, GREEDY,
                                                  **PAGED_KW)])
    assert bare.slo_report() is None


def test_slo_endpoint_over_http(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, GREEDY, slo=EASY,
                               **PAGED_KW).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address
        srv.submit([5, 9, 3], max_new_tokens=2).result(timeout=120)
        with urllib.request.urlopen(f"http://{host}:{port}/slo",
                                    timeout=60) as resp:
            rep = json.loads(resp.read())
        assert rep["windows_s"] == [10.0, 60.0]
        assert rep["classes"]["default"]["metrics"]["ttft"][
            "lifetime"]["total"] == 1
    finally:
        front.stop()
        srv.stop()


def test_slo_endpoint_disabled(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address
        with urllib.request.urlopen(f"http://{host}:{port}/slo",
                                    timeout=60) as resp:
            assert json.loads(resp.read()) == {"enabled": False}
    finally:
        front.stop()
        srv.stop()
