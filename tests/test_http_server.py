"""HTTP front-end: loopback round-trip, streaming, protocol errors —
over the paged server with and without in-server speculation."""

import dataclasses
import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.data.tokenizer import get_tokenizer
from cloud_server_tpu.inference import engine
from cloud_server_tpu.inference.http_server import HttpFrontend
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import transformer

CFG = ModelConfig(
    vocab_size=300, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=128, dtype="float32",
    param_dtype="float32", remat="none")
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)


@pytest.fixture(scope="module", params=["paged", "paged-spec"])
def frontend(request):
    params = transformer.init_params(CFG, jax.random.key(0))
    srv = PagedInferenceServer(
        params, CFG, GREEDY, max_slots=2, max_context=64, page_size=8,
        prefill_chunk=16, prompt_buckets=[16, 48],
        spec_drafts=2 if request.param == "paged-spec" else 0)
    srv.start()
    front = HttpFrontend(srv, tokenizer=get_tokenizer("byte")).start()
    yield front, params
    front.stop()
    srv.stop()


def _post(front, payload: dict, path="/generate"):
    host, port = front.address
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return [json.loads(line) for line in resp if line.strip()]


def test_generate_roundtrip_tokens(frontend):
    front, params = frontend
    prompt = [5, 9, 3]
    lines = _post(front, {"tokens": prompt, "max_new_tokens": 6})
    assert lines[-1]["done"] is True
    got = lines[-1]["tokens"]
    icfg = dataclasses.replace(GREEDY, max_decode_len=6)
    want = engine.generate(params, np.asarray([prompt], np.int32),
                           jax.random.key(1), cfg=CFG, infer_cfg=icfg)
    assert got == list(np.asarray(want)[0])
    # streamed lines match the final accumulated list
    assert [ln["token"] for ln in lines[:-1]] == got


def test_generate_text_prompt_decodes(frontend):
    front, _ = frontend
    lines = _post(front, {"prompt": "ab", "max_new_tokens": 4})
    assert lines[-1]["done"] is True
    assert len(lines[-1]["tokens"]) == 4
    assert all("text" in ln for ln in lines[:-1])


def test_healthz_readiness_tracks_drain():
    """`ready` (vs `ok` liveness) flips false while the backend drains
    — the load-balancer shed signal — and back on resume; `ok` and the
    counts stay up throughout."""
    params = transformer.init_params(CFG, jax.random.key(0))
    srv = PagedInferenceServer(
        params, CFG, GREEDY, max_slots=2, max_context=64, page_size=8,
        prefill_chunk=16, prompt_buckets=[16, 48]).start()
    front = HttpFrontend(srv).start()
    try:
        host, port = front.address

        def health():
            with urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=30) as resp:
                return json.loads(resp.read())

        assert health() == {"ok": True, "ready": True, "active": 0,
                            "pending": 0}
        assert srv.drain() is True  # idle: quiesces immediately
        h = health()
        assert h["ok"] is True and h["ready"] is False
        srv.resume()
        assert health()["ready"] is True
        srv.stop()  # stopped: live HTTP layer, unready backend
        assert health()["ready"] is False
    finally:
        front.stop()
        srv.stop()


def test_healthz_and_errors(frontend):
    front, _ = frontend
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}/healthz",
                                timeout=30) as resp:
        health = json.loads(resp.read())
    assert health["ok"] is True
    assert health["ready"] is True  # serving: ready to take traffic
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(front, {"nonsense": 1})
    assert err.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(front, {"tokens": [1]}, path="/bogus")
    assert err.value.code == 404


# ---------------------------------------------------------------------------
# per-request sampling over HTTP + OpenAI-compatible endpoints
# ---------------------------------------------------------------------------


def _raw_post(front, payload: dict, path: str) -> list[str]:
    host, port = front.address
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return [ln.decode().rstrip("\n") for ln in resp
                if ln.strip()]


def _sse_events(lines: list[str]) -> list[dict]:
    assert lines[-1] == "data: [DONE]"
    return [json.loads(ln[len("data: "):]) for ln in lines[:-1]]


def test_generate_sampling_fields(frontend):
    """Per-request sampling rides through /generate: a huge repetition
    penalty forbids repeats; a seed makes resubmission deterministic."""
    front, _ = frontend
    lines = _post(front, {"tokens": [5, 9, 3], "max_new_tokens": 8,
                          "repetition_penalty": 1e9})
    toks = lines[-1]["tokens"]
    assert len(set(toks)) == len(toks)
    a = _post(front, {"tokens": [7, 8], "max_new_tokens": 6,
                      "temperature": 1.3, "seed": 7})[-1]["tokens"]
    # bitwise seed reproducibility holds without in-server speculation
    if getattr(front.srv, "spec_drafts", 0) == 0:
        b = _post(front, {"tokens": [7, 8], "max_new_tokens": 6,
                          "temperature": 1.3, "seed": 7})[-1]["tokens"]
        assert a == b


def test_v1_models(frontend):
    front, _ = frontend
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}/v1/models",
                                timeout=30) as resp:
        data = json.loads(resp.read())
    assert data["object"] == "list"
    assert data["data"][0]["id"] == "cloud-server-tpu"


def test_v1_completions_matches_generate(frontend):
    front, _ = frontend
    gen = _post(front, {"prompt": "ab", "max_new_tokens": 6})[-1]
    comp = json.loads(_raw_post(
        front, {"prompt": "ab", "max_tokens": 6}, "/v1/completions")[0])
    assert comp["object"] == "text_completion"
    choice = comp["choices"][0]
    assert choice["finish_reason"] in ("stop", "length")
    assert choice["text"] == front.tokenizer.decode(gen["tokens"])
    assert comp["usage"]["completion_tokens"] == 6
    assert comp["usage"]["prompt_tokens"] == 2


def test_v1_completions_n_and_logprobs(frontend):
    front, _ = frontend
    comp = json.loads(_raw_post(
        front, {"prompt": "ab", "max_tokens": 4, "n": 2, "logprobs": 1},
        "/v1/completions")[0])
    assert len(comp["choices"]) == 2
    assert comp["choices"][0]["text"] == comp["choices"][1]["text"]  # greedy
    lp = comp["choices"][0]["logprobs"]
    assert len(lp["token_logprobs"]) == 4


def test_v1_completions_stream(frontend):
    front, _ = frontend
    plain = json.loads(_raw_post(
        front, {"prompt": "ab", "max_tokens": 6}, "/v1/completions")[0])
    events = _sse_events(_raw_post(
        front, {"prompt": "ab", "max_tokens": 6, "stream": True},
        "/v1/completions"))
    text = "".join(e["choices"][0]["text"] for e in events)
    assert text == plain["choices"][0]["text"]
    assert events[-1]["choices"][0]["finish_reason"] in ("stop", "length")


def test_v1_chat_roundtrip_and_stream(frontend):
    front, _ = frontend
    body = {"messages": [{"role": "system", "content": "s"},
                         {"role": "user", "content": "hi"}],
            "max_tokens": 6}
    resp = json.loads(_raw_post(front, body, "/v1/chat/completions")[0])
    assert resp["object"] == "chat.completion"
    msg = resp["choices"][0]["message"]
    assert msg["role"] == "assistant"
    events = _sse_events(_raw_post(
        front, {**body, "stream": True}, "/v1/chat/completions"))
    assert events[0]["choices"][0]["delta"].get("role") == "assistant"
    text = "".join(e["choices"][0]["delta"].get("content", "")
                   for e in events)
    assert text == msg["content"]
    assert events[-1]["choices"][0]["finish_reason"] in ("stop", "length")


def test_v1_stop_tokens(frontend):
    """A token-id stop sequence truncates the completion and reports
    finish_reason 'stop' (string stops take the same path after
    tokenization; the toy model's greedy bytes rarely form clean UTF-8,
    so the exact-id form is what is testable here)."""
    front, _ = frontend
    toks = _post(front, {"tokens": [5, 9, 3],
                         "max_new_tokens": 8})[-1]["tokens"]
    stop = toks[2:4]
    comp = json.loads(_raw_post(
        front, {"prompt": [5, 9, 3], "max_tokens": 8, "stop": [stop]},
        "/v1/completions")[0])
    assert comp["choices"][0]["finish_reason"] == "stop"
    # the completion ends strictly before the first stop match
    usage = comp["usage"]["completion_tokens"]
    assert usage < len(toks)


def test_v1_errors(frontend):
    front, _ = frontend
    with pytest.raises(urllib.error.HTTPError) as err:
        _raw_post(front, {"messages": []}, "/v1/chat/completions")
    assert err.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as err:
        _raw_post(front, {"prompt": "ab", "temperature": -2.0},
                  "/v1/completions")
    assert err.value.code == 400


def test_logit_bias_and_min_tokens_over_http(frontend):
    front, _ = frontend
    lines = _post(front, {"tokens": [5, 9, 3], "max_new_tokens": 4,
                          "logit_bias": {"42": 1e9}})
    assert lines[-1]["tokens"] == [42, 42, 42, 42]
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(front, {"tokens": [5], "logit_bias": {"x": 1}})
    assert err.value.code == 400


def test_metrics_endpoint(frontend):
    front, _ = frontend
    _post(front, {"tokens": [5, 9, 3], "max_new_tokens": 2})
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
    lines = dict(ln.rsplit(" ", 1) for ln in text.splitlines()
                 if ln and not ln.startswith("#"))
    assert float(lines["cloud_server_tokens_emitted_total"]) >= 2
    assert "cloud_server_active_slots" in lines
    # lifecycle histograms are exposed with buckets + sum/count
    assert float(lines["cloud_server_ttft_seconds_count"]) >= 1
    assert 'cloud_server_itl_seconds_bucket{le="+Inf"}' in lines
    if hasattr(front.srv, "allocator"):
        assert "cloud_server_pages_total" in lines


def test_stats_endpoint(frontend):
    front, _ = frontend
    _post(front, {"tokens": [7, 2, 9], "max_new_tokens": 3})
    host, port = front.address
    with urllib.request.urlopen(f"http://{host}:{port}/stats?n=8",
                                timeout=30) as resp:
        stats = json.loads(resp.read())
    assert stats["latency"]["cloud_server_ttft_seconds"]["count"] >= 1
    assert stats["counters"]["cloud_server_requests_finished_total"] >= 1
    if hasattr(front.srv, "flight_window"):
        window = stats["flight_recorder"]
        assert window and len(window) <= 8
        assert all("tokens_scheduled" in rec for rec in window)
