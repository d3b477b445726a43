"""The scheduler's phases and the model's scopes inside a jax profiler
trace (CPU): every busy iteration of a traced paged server is one
`sched/iteration` event and its `sched/<phase>` events, carrying the
flight record's index and adding up to its `duration_ms`; the program's
own capture keeps the Python tracer off; the step programs' ops carry
the scope names a device trace tells them apart by."""
import glob
import itertools
import os

import jax
import jax.numpy as jnp
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.inference import paged_server as ps
from cloud_server_tpu.inference.iteration_profile import PHASES
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.models import moe, transformer
from cloud_server_tpu.utils import annotate, capture_trace

CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
MOE_CFG = ModelConfig(
    vocab_size=64, embed_dim=32, num_layers=1, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none", num_experts=4,
    num_experts_per_token=2, expert_capacity_factor=2.0)
GREEDY = InferConfig(max_decode_len=8, temperature=0.0, eos_token_id=-1,
                     pad_token_id=0)
PAGED_KW = dict(max_slots=4, max_context=64, page_size=8, prefill_chunk=16,
                prompt_buckets=[16, 48])


def host_events(logdir, prefix):
    """[(name, start_ns, duration_ns, stats)] of the host planes' events
    whose name starts with `prefix`, and the number of all host events."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    found, total = [], 0
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                total += 1
                if ev.name.startswith(prefix):
                    found.append((ev.name, ev.start_ns, ev.duration_ns,
                                  dict(ev.stats)))
    return found, total


def traced_churn(tmp_path, **server_kw):
    """A few warm steps untraced, then a churn (two decoding rows and a
    long prompt admitted in chunks) inside the program's own capture."""
    params = transformer.init_params(CFG, jax.random.key(0))
    srv = PagedInferenceServer(params, CFG, GREEDY, **PAGED_KW, **server_kw)
    warm = [srv.submit([5 + i, 9, 3], max_new_tokens=8) for i in range(2)]
    srv.step()
    srv.step()
    before = srv.flight.iterations
    with capture_trace(tmp_path / "trace"):
        srv.submit([(k * 7) % 60 + 1 for k in range(40)], max_new_tokens=4)
        srv.run_until_idle()
        srv.step()  # an idle step: an iteration event without an index
    assert all(r.done for r in warm)
    records = [r for r in srv.flight_window() if r["iteration"] > before]
    return records, host_events(tmp_path / "trace", "sched/")


def test_every_busy_iteration_is_events_on_the_profilers_clock(tmp_path):
    records, (events, _) = traced_churn(tmp_path)
    assert len(records) >= 4
    iters = {e[3]["iteration"]: e for e in events
             if e[0] == "sched/iteration" and "iteration" in e[3]}
    assert sorted(iters) == [r["iteration"] for r in records]
    # the idle step at the end: an iteration event that recorded nothing
    assert [e for e in events
            if e[0] == "sched/iteration" and "iteration" not in e[3]]
    for rec in records:
        _, t0, dur, _ = iters[rec["iteration"]]
        inside = [e for e in events if e[0] != "sched/iteration"
                  and e[3].get("iteration") == rec["iteration"]
                  and t0 <= e[1] and e[1] + e[2] <= t0 + dur]
        inside.sort(key=lambda e: e[1])
        names = [e[0][len("sched/"):] for e in inside]
        assert set(names) <= set(PHASES)
        # the phases the record crossed, and no other
        assert set(names) == set(rec["phases_ms"])
        # each crossed once, in the record's order; a step that launched
        # ahead crossed `launch` before `device`
        crossed = [n for n, _ in itertools.groupby(names)]
        order = [p for p in PHASES if p in rec["phases_ms"]]
        ahead = list(order)
        if "launch" in ahead and "device" in ahead:
            ahead.remove("launch")
            ahead.insert(ahead.index("device"), "launch")
        assert crossed in (order, ahead), (crossed, rec["phases_ms"])
        # one after the other on the profiler's clock, and all of them
        # inside the iteration's event: their sum does not exceed it
        for before, after in zip(inside, inside[1:]):
            assert before[1] + before[2] <= after[1]
        assert sum(e[2] for e in inside) <= dur


def test_profiler_off_emits_no_event(tmp_path):
    records, (events, _) = traced_churn(tmp_path, iteration_profile=False)
    assert records and all("phases_ms" not in r for r in records)
    assert events == []


def test_capture_keeps_annotations_and_drops_the_python_tracer(tmp_path):
    """`capture_trace` is the program's one way to start a trace: the
    Python tracer is off (JAX's default hooks every Python call of the
    traced threads), and `annotate`'s events with their stats stay."""
    def work():
        for i in range(3):
            with annotate("unit-test-region", step=i):
                sum(len(str(k)) for k in range(200))  # Python calls
                jnp.ones((8, 8)).sum().block_until_ready()

    with annotate("outside-any-capture"):  # an inactive check
        work()
    with capture_trace(tmp_path / "ours"):
        work()
    ours, n_ours = host_events(tmp_path / "ours", "unit-test-region")
    assert [e[3] for e in ours] == [{"step": 0}, {"step": 1}, {"step": 2}]
    assert all(e[2] > 0 for e in ours)
    jax.profiler.start_trace(str(tmp_path / "default"))
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    default, n_default = host_events(tmp_path / "default",
                                     "unit-test-region")
    assert len(default) == 3
    # the default options record the Python calls inside the regions
    assert n_default > n_ours + 100


# what the step's decode half runs: the default eight rounds keep the
# two walks of the layers, one round a step joins them
@pytest.mark.parametrize("decode_chunk", [8, 1])
def test_mixed_step_ops_carry_the_scope_names(monkeypatch, decode_chunk):
    """The HLO of a tiny MoE `_mixed_step` names its two halves, the
    walk they share where they share it, and the expert einsums in its
    `op_name`s (metadata only)."""
    params = moe.init_params(MOE_CFG, jax.random.key(0))
    srv = PagedInferenceServer(params, MOE_CFG, GREEDY,
                               decode_chunk=decode_chunk, **PAGED_KW)
    texts = []
    orig = ps._mixed_step

    def lowering(*args, **kwargs):
        if not texts and kwargs["n_rounds"] > 0:  # both halves present
            texts.append(orig.lower(*args, **kwargs).compile().as_text())
        return orig(*args, **kwargs)

    monkeypatch.setattr(ps, "_mixed_step", lowering)
    first = srv.submit([5, 9, 3], max_new_tokens=8)
    srv.step()
    srv.submit([(k * 7) % 60 + 1 for k in range(40)], max_new_tokens=2)
    srv.run_until_idle()
    assert first.done and texts
    names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in texts[0].splitlines() if 'op_name="' in ln]
    joined = decode_chunk == 1
    # the weights' side of a layer, once for both halves or once in each
    shared = "joined_walk/" if joined else "prefill_group/"
    for scope in (shared + "attn/", shared + "moe_route/",
                  shared + "moe_experts/", shared + "unembed/",
                  "prefill_group/attn/", "prefill_group/sample/",
                  "decode_rounds/", "/attn/", "/moe_dispatch/",
                  "/moe_experts/", "/moe_combine/", "/unembed/",
                  "/sample/"):
        assert any(scope in n for n in names), scope
    assert any("joined_walk/" in n for n in names) == joined
    decode = [n for n in names if "decode_rounds/" in n]
    # a half's own experts and its own product with the head only where
    # it walks the layers itself; its cache write, kernel and sampler
    # always
    assert any("/moe_experts/" in n for n in decode) != joined
    dots = [n for n in names if "/unembed/" in n and "dot_general" in n]
    assert dots and all(("joined_walk/" in n) == joined for n in dots), dots
    for scope in ("/attn/", "/sample/"):
        assert any(scope in n for n in decode), scope
    # every op of the program itself lies in one of the halves or in
    # the walk they share (the reducers XLA's CPU backend names
    # `reduce_sum` and the like are not ops of the traced function)
    stray = [n for n in names if n.startswith("jit(")
             and "prefill_group/" not in n and "decode_rounds/" not in n
             and "joined_walk/" not in n]
    assert not stray, stray[:5]
