"""Per-request distributed tracing: W3C traceparent parsing, head
sampling determinism, span-tree phase derivation (contiguity across
finish and preemption), iteration-span cross-links to the flight
recorder, router pick-to-replica stitching, the HTTP surface
(/debug/requests, /traces, traceparent in/out), and the access-log
trace/tenant correlation."""

import io
import json
import urllib.error
import urllib.request

import jax
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.request_trace import (
    PHASES, TraceRecorder, build_tree, chrome_trace, format_traceparent,
    parse_traceparent, request_phases, resolve_recorder)
from cloud_server_tpu.inference.router import ReplicatedRouter
from cloud_server_tpu.models import transformer
from cloud_server_tpu.utils.logging import JsonLogger

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
# traceparent + sampling primitives
# ---------------------------------------------------------------------------


def test_traceparent_roundtrip():
    tid = "0af7651916cd43dd8448eb211c80319c"
    sid = "b7ad6b7169203331"
    hdr = format_traceparent(tid, sid, sampled=True)
    assert hdr == f"00-{tid}-{sid}-01"
    assert parse_traceparent(hdr) == (tid, sid, True)
    assert parse_traceparent(format_traceparent(tid, sid, False)) \
        == (tid, sid, False)
    # forward-compat: extra flag bits / future fields still parse
    assert parse_traceparent(f"00-{tid}-{sid}-03-extra") == (tid, sid,
                                                             True)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-b7ad6b7169203331-01",
    f"00-{'0' * 32}-b7ad6b7169203331-01",       # all-zero trace id
    "00-0af7651916cd43dd8448eb211c80319c-0000000000000000-01",
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",
    "00-zzf7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",
])
def test_traceparent_rejects(bad):
    assert parse_traceparent(bad) is None


def test_head_sampling_deterministic():
    full = TraceRecorder(sample_rate=1.0)
    none = TraceRecorder(sample_rate=0.0)
    half_a = TraceRecorder(sample_rate=0.5)
    half_b = TraceRecorder(sample_rate=0.5)
    # entropy in the LEADING 8 hex chars — the bits the head decision
    # reads (uuid4 ids are uniform there)
    ids = [f"{i:08x}" + "c" * 24 for i in range(0, 2 ** 32, 2 ** 28)]
    for tid in ids:
        assert full.should_sample(tid)
        assert not none.should_sample(tid)
        # the decision is a pure function of the id: two recorders
        # (two replicas) always agree
        assert half_a.should_sample(tid) == half_b.should_sample(tid)
    assert 0 < sum(half_a.should_sample(t) for t in ids) < len(ids)
    with pytest.raises(ValueError):
        TraceRecorder(sample_rate=1.5)
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_resolve_recorder_paths():
    assert resolve_recorder(None, 0.0) is None
    assert resolve_recorder(False, 1.0) is None  # force-off wins
    assert resolve_recorder(0.0) is None
    assert resolve_recorder(None, 0.25).sample_rate == 0.25
    rec = TraceRecorder(sample_rate=0.5)
    assert resolve_recorder(rec) is rec


# ---------------------------------------------------------------------------
# span trees on live servers
# ---------------------------------------------------------------------------


def _phases(tree):
    return [c for c in tree["root"]["children"] if c["name"] in PHASES]


def _assert_contiguous(tree):
    root = tree["root"]
    phases = _phases(tree)
    assert phases[0]["start"] == root["start"]
    for a, b in zip(phases, phases[1:]):
        assert a["end"] == b["start"], \
            f"gap between {a['name']} and {b['name']}"
    assert phases[-1]["end"] == root["end"]
    times = [p["start"] for p in phases] + [phases[-1]["end"]]
    assert times == sorted(times)


def test_span_tree_paged_server(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, tracing=1.0,
                               **PAGED_KW)
    reqs = [srv.submit([5, 9, 3], max_new_tokens=4),
            srv.submit([7, 7, 2, 1], max_new_tokens=4)]
    srv.run_until_idle()
    trees = srv.trace_trees()
    assert len(trees) == 2  # exactly one tree per request
    for r in reqs:
        tree = srv.lookup_trace(r.request_id)
        assert tree is not None
        assert tree["request_id"] == r.request_id
        assert tree["root"]["start"] == r.submit_time
        names = [p["name"] for p in _phases(tree)]
        for want in ("queue", "prefill", "decode", "emit"):
            assert want in names, names
        _assert_contiguous(tree)
        # external timing agreement: the prefill phase ends exactly at
        # the externally observed first token
        pre = next(p for p in _phases(tree) if p["name"] == "prefill")
        assert pre["end"] == r.emit_times[0]
        # iteration spans cross-link to the flight recorder by index
        iter_spans = [c for ph in _phases(tree)
                      for c in ph.get("children", ())]
        assert any(c["name"] == "prefill_chunk" for c in iter_spans)
        assert any(c["name"] == "decode_segment" for c in iter_spans)
        for c in iter_spans:
            assert 1 <= c["tags"]["iteration"] <= srv.flight.iterations
    assert srv.lookup_trace("nonexistent") is None


def test_span_tree_survives_preemption(params):
    """The on-demand page-famine preemption path: a preempted
    request's ONE tree shows the preempt_gap phase, stays contiguous,
    and covers the re-admission (a second prefill phase)."""
    prompts = [[(i * 9 + k) % 60 + 1 for k in range(8)] for i in range(6)]
    srv = PagedInferenceServer(
        params, CFG, GREEDY, allocation="ondemand", max_slots=6,
        max_context=64, page_size=8, prefill_chunk=16,
        prompt_buckets=[16], num_pages=12, decode_chunk=2, tracing=1.0)
    reqs = [srv.submit(p, max_new_tokens=40) for p in prompts]
    srv.run_until_idle()
    assert srv.preemptions > 0
    assert len(srv.trace_trees()) == len(reqs)  # one tree each
    preempted = [r for r in reqs
                 if any(n == "preempt_requeue" for n, _ in r.timeline())]
    assert preempted
    for r in preempted:
        tree = srv.lookup_trace(r.request_id)
        names = [p["name"] for p in _phases(tree)]
        assert "preempt_gap" in names
        assert names.count("prefill") >= 2  # the re-admission
        _assert_contiguous(tree)


def test_unsampled_and_disabled_paths(params):
    # tracing disabled: no recorder, no trace, byte-identical request
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW)
    assert srv.trace_recorder is None
    req = srv.submit([5, 9, 3], max_new_tokens=2)
    srv.run_until_idle()
    assert req.trace is None
    assert srv.lookup_trace(req.request_id) is None
    assert srv.trace_trees() == []
    # rate 0 via a recorder: recorder exists but samples nothing —
    # unless an upstream traceparent says "sampled"
    srv2 = PagedInferenceServer(params, CFG, GREEDY,
                                tracing=TraceRecorder(sample_rate=0.0),
                                **PAGED_KW)
    r0 = srv2.submit([5, 9, 3], max_new_tokens=2)
    ctx = ("0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331", True)
    r1 = srv2.submit([5, 9, 3], max_new_tokens=2, trace_ctx=ctx)
    r2 = srv2.submit([5, 9, 3], max_new_tokens=2,
                     trace_ctx=(ctx[0], ctx[1], False))
    srv2.run_until_idle()
    assert r0.trace is None and r2.trace is None
    assert r1.trace is not None
    assert r1.trace.trace_id == ctx[0]
    assert r1.trace.parent_span_id == ctx[1]


def test_ring_eviction():
    rec = TraceRecorder(sample_rate=1.0, capacity=2)

    class _Req:
        def __init__(self, rid):
            self.request_id = rid
            self.trace = None
            self.submit_time = 0.0
            self.tenant = None
            self.finish_reason = "length"
            self.tokens = []
            self.emit_times = []

        def timeline(self):
            return [("submit", 0.0), ("finish:length", 1.0)]

    reqs = [_Req(f"req{i}") for i in range(3)]
    for r in reqs:
        rec.begin(r)
        rec.finish(r)
    assert rec.lookup("req0") is None  # evicted
    assert rec.lookup("req2") is not None
    assert rec.evicted_total == 1
    assert len(rec.trees()) == 2


# ---------------------------------------------------------------------------
# router: one tree across pick -> replica
# ---------------------------------------------------------------------------


def test_rejected_submit_never_enters_recorder(params):
    """A submit refused by backpressure (or drain) must not leak into
    the recorder's live set — overload would otherwise grow it
    unboundedly (one entry per 429, never finished)."""
    srv = PagedInferenceServer(params, CFG, GREEDY, tracing=1.0,
                               max_pending=1, **PAGED_KW)
    ok = srv.submit([5, 9, 3], max_new_tokens=2)
    with pytest.raises(Exception):  # QueueFullError
        srv.submit([5, 9, 3], max_new_tokens=2)
    assert len(srv.trace_recorder._live) == 1  # only the accepted one
    srv.run_until_idle()
    assert ok.done
    assert srv.trace_recorder._live == {}
    assert len(srv.trace_trees()) == 1
    # draining refusal: same rule
    assert srv.drain() is True
    with pytest.raises(RuntimeError):
        srv.submit([5, 9, 3], max_new_tokens=2)
    assert srv.trace_recorder._live == {}
    # n <= 0 bounds mean "nothing", never "everything"
    assert srv.trace_trees(0) == []
    assert srv.trace_trees(-1) == []


def test_finished_ring_drops_request_payload(params):
    """The ring retains a slim snapshot, not the Request: prompt /
    token / logprob lists are released at finish while the tree stays
    fully buildable (final token count included)."""
    srv = PagedInferenceServer(params, CFG, GREEDY, tracing=1.0,
                               **PAGED_KW)
    req = srv.submit([5, 9, 3], max_new_tokens=4)
    srv.run_until_idle()
    (kept,) = srv.trace_recorder._ring
    assert not hasattr(kept, "prompt") and not hasattr(kept, "logprobs")
    tree = srv.lookup_trace(req.request_id)
    assert tree["root"]["tags"]["tokens"] == 4
    _assert_contiguous(tree)


def test_router_single_tree_with_pick_span(params):
    replicas = [PagedInferenceServer(params, CFG, GREEDY, tracing=1.0,
                                     **PAGED_KW) for _ in range(2)]
    router = ReplicatedRouter(replicas)
    ctx = ("0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331", True)
    reqs = [router.submit([5 + i, 9, 3], max_new_tokens=3,
                          trace_ctx=ctx if i == 0 else None)
            for i in range(4)]
    router.run_until_idle()
    # each request has exactly one tree, findable THROUGH the router
    all_trees = router.trace_trees()
    assert len(all_trees) == 4
    assert len({t["request_id"] for t in all_trees}) == 4
    for r in reqs:
        tree = router.lookup_trace(r.request_id)
        assert tree is not None
        # the fleet half: a router_pick span tagged with the replica,
        # and the replica tag on the root
        picks = [c for c in tree["root"]["children"]
                 if c["name"] == "router_pick"]
        assert len(picks) == 1
        replica = picks[0]["tags"]["replica"]
        assert tree["root"]["tags"]["replica"] == replica
        # ...stitched to the replica-side execution in the SAME tree
        names = [p["name"] for p in _phases(tree)]
        assert "prefill" in names and "decode" in names
        _assert_contiguous(tree)
    # the upstream trace context rode through the router untouched
    assert router.lookup_trace(reqs[0].request_id)["trace_id"] == ctx[0]


# ---------------------------------------------------------------------------
# chrome export
# ---------------------------------------------------------------------------


def test_chrome_trace_export(params):
    srv = PagedInferenceServer(params, CFG, GREEDY, tracing=1.0,
                               **PAGED_KW)
    srv.submit([5, 9, 3], max_new_tokens=3)
    srv.run_until_idle()
    out = chrome_trace(srv.trace_trees())
    assert out["displayTimeUnit"] == "ms"
    evs = out["traceEvents"]
    assert any(e["ph"] == "M" for e in evs)  # thread-name metadata
    xs = [e for e in evs if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    names = {e["name"] for e in xs}
    assert {"queue", "prefill", "decode"} <= names
    json.dumps(out)  # JSON-serializable end to end


# ---------------------------------------------------------------------------
# phase derivation unit coverage (no server)
# ---------------------------------------------------------------------------


def test_request_phases_cancel_before_admission():
    class _Req:
        submit_time = 1.0
        emit_times = []

        def timeline(self):
            return [("submit", 1.0), ("finish:cancelled", 2.0)]

    phases = request_phases(_Req())
    assert [(p["name"], p["start"], p["end"]) for p in phases] == \
        [("queue", 1.0, 2.0)]


def test_request_phases_in_flight_open_end():
    class _Req:
        submit_time = 1.0
        emit_times = []

        def timeline(self):
            return [("submit", 1.0), ("admit", 2.0)]

    phases = request_phases(_Req())
    assert phases[-1]["name"] == "prefill"
    assert phases[-1]["end"] is None


# ---------------------------------------------------------------------------
# HTTP surface: traceparent in/out, /debug/requests, /traces, access log
# ---------------------------------------------------------------------------


@pytest.fixture()
def traced_frontend(params):
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(
        params, CFG, GREEDY, tracing=1.0,
        qos={"tenants": {"team-a": {"weight": 2.0}}}, **PAGED_KW).start()
    log_stream = io.StringIO()
    front = HttpFrontend(srv, access_log=JsonLogger(
        stream=log_stream)).start()
    yield front, srv, log_stream
    front.stop()
    srv.stop()


def _url(front, path):
    host, port = front.address
    return f"http://{host}:{port}{path}"


def test_http_traceparent_in_out_and_lookup(traced_frontend):
    front, srv, log_stream = traced_frontend
    tid = "0af7651916cd43dd8448eb211c80319c"
    req = urllib.request.Request(
        _url(front, "/generate"),
        data=json.dumps({"tokens": [5, 9, 3],
                         "max_new_tokens": 3}).encode(),
        headers={"traceparent": f"00-{tid}-b7ad6b7169203331-01",
                 "X-Tenant": "team-a"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        out_hdr = resp.headers.get("traceparent")
        resp.read()
    # the response traceparent names the SAME trace the client started
    assert out_hdr is not None
    parsed = parse_traceparent(out_hdr)
    assert parsed is not None and parsed[0] == tid
    # the tree is retrievable and joined to the client's trace
    trees = srv.trace_trees()
    assert len(trees) == 1
    rid = trees[0]["request_id"]
    with urllib.request.urlopen(_url(front, f"/debug/requests/{rid}"),
                                timeout=60) as resp:
        tree = json.loads(resp.read())
    assert tree["trace_id"] == tid
    assert tree["root"]["tags"]["tenant"] == "team-a"
    # /traces: the chrome export of the ring
    with urllib.request.urlopen(_url(front, "/traces"),
                                timeout=60) as resp:
        export = json.loads(resp.read())
    assert export["traceEvents"]
    # unknown id -> 404
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(_url(front, "/debug/requests/nope"),
                               timeout=60)
    assert err.value.code == 404
    # access log correlates: trace_id + tenant on the POST line
    records = [json.loads(ln) for ln in
               log_stream.getvalue().splitlines() if ln]
    post = [r for r in records if r.get("event") == "access"
            and r["path"] == "/generate"]
    assert post and post[0]["trace_id"] == tid
    assert post[0]["tenant"] == "team-a"


def test_http_fresh_trace_without_header(traced_frontend):
    front, srv, _ = traced_frontend
    req = urllib.request.Request(
        _url(front, "/generate"),
        data=json.dumps({"tokens": [5, 9], "max_new_tokens": 2}).encode())
    with urllib.request.urlopen(req, timeout=60) as resp:
        out_hdr = resp.headers.get("traceparent")
        resp.read()
    assert out_hdr is not None  # a fresh trace was started and echoed
    assert parse_traceparent(out_hdr) is not None


def test_build_tree_none_for_untraced():
    class _Req:
        trace = None

    assert build_tree(_Req()) is None
