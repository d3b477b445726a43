"""One serving cell, once: set-up, warm-up, the window, the check.

The system under test is the server `generate --serve-http` builds
(`PagedInferenceServer` behind `HttpFrontend` on port 0), built here
in the process that holds the chip: the reference then checks the very
weights that were served without a second init, the profiler and
`memory_stats()` are at hand, and compile events are read from JAX's own
log. The load generator is a child that never imports JAX. Measured
requests go over HTTP; the warm-up drives the server's public
`submit`/`step` before the scheduler thread starts, because only a
caller that decides which requests share an iteration can reach each
dispatch shape on purpose.
"""

from __future__ import annotations

import json
import logging
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

from cellbench import stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def log(msg: str) -> None:
    print(f"[cellbench] {msg}", file=sys.stderr, flush=True)


class CompileLog(logging.Handler):
    """Every compile JAX logs (`jax_log_compiles`), stamped on the
    monotonic clock: [(t, function name, seconds)] from the
    'Finished XLA compilation of <name> in <s> sec' lines, which JAX
    writes for a cache hit as for a real compile."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.events: list[tuple[float, str, float]] = []
        self.shapes: list[tuple[float, str, str]] = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:  # noqa: BLE001 - a log line must never raise
            return
        if msg.startswith("Finished XLA compilation of "):
            rest = msg[len("Finished XLA compilation of "):]
            name, _, tail = rest.rpartition(" in ")
            try:
                secs = float(tail.split()[0])
            except (ValueError, IndexError):
                secs = 0.0
            self.events.append((time.monotonic(), name, secs))
        elif msg.startswith("Compiling "):
            # the small integer and boolean arguments are the dispatch
            # shape (group, width, prompt bucket, decode rows); weights
            # and pools are the same in every program
            name = msg.split()[1]
            dims = re.findall(r"(?:int32|bool)\[[\d,]*\]", msg)
            self.shapes.append((time.monotonic(), name, " ".join(dims)))

    def count_between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.events if t0 <= t < t1)

    def summary(self, until: float) -> dict:
        by: dict[str, list] = {}
        for t, name, secs in self.events:
            if t < until:
                e = by.setdefault(name, [0, 0.0])
                e[0] += 1
                e[1] += secs
        return {k: {"n": v[0], "xla_s": round(v[1], 2)}
                for k, v in sorted(by.items(), key=lambda kv: -kv[1][1])[:8]}


def model_config(cfg_file: dict, overrides: dict | None = None):
    """The program's ModelConfig from a configuration file's published
    keys and its `serving` options."""
    from cloud_server_tpu.config import ModelConfig
    c = dict(cfg_file)
    c.update(overrides or {})
    sv = dict(cfg_file.get("serving", {}))
    sv.update((overrides or {}).get("serving", {}))
    return ModelConfig(
        vocab_size=c["vocab_size"], embed_dim=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        mlp_dim=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        dtype=sv.get("dtype", "bfloat16"),
        param_dtype=sv.get("param_dtype", "bfloat16"),
        decode_attention_impl=sv.get("decode_attention_impl", "pallas"),
        kv_cache_dtype=sv.get("kv_cache_dtype", "model"),
        num_experts=c.get("num_local_experts", 0),
        num_experts_per_token=c.get("num_experts_per_tok", 2),
        expert_capacity_factor=sv.get("expert_capacity_factor", 1.25))


def make_model(cfg_file: dict, overrides: dict | None, seed: int):
    """(ModelConfig, weights from the seed) of a configuration file."""
    from cloud_server_tpu.models import moe, transformer

    from cellbench import weights as wmod
    mcfg = model_config(cfg_file, overrides)
    module = moe if mcfg.num_experts >= 2 else transformer
    return mcfg, wmod.make_weights(module.param_shapes(mcfg), seed,
                                   mcfg.param_dtype)


def reference_spec(cfg_file: dict) -> dict:
    return {"norm_eps": cfg_file["rms_norm_eps"],
            "rope_theta": cfg_file["rope_theta"],
            "num_experts": cfg_file.get("num_local_experts", 0),
            "num_experts_per_token": cfg_file.get("num_experts_per_tok", 2)}


def build_server(mcfg, weights, server_opts: dict, max_answer: int):
    """`generate.py`'s `make_server` with the workload file's options:
    greedy, no EOS (an answer is as long as it was asked to be, so every
    seed does the same work)."""
    from cloud_server_tpu.config import InferConfig
    from cloud_server_tpu.inference.paged_server import PagedInferenceServer
    o = server_opts
    ps = int(o.get("page_size", 128))
    max_context = -(-int(o["max_len"]) // ps) * ps
    infer = InferConfig(max_decode_len=max_answer, temperature=0.0,
                        eos_token_id=-1, pad_token_id=0)
    return PagedInferenceServer(
        weights, mcfg, infer, max_slots=int(o["max_slots"]),
        max_context=max_context, page_size=ps,
        num_pages=int(o["num_pages"]),
        decode_chunk=int(o.get("decode_chunk", 1)),
        prefill_chunk=int(o.get("prefill_chunk", 256)),
        mixed_token_budget=int(o.get("mixed_token_budget", 0)),
        flight_recorder_size=int(o.get("flight_recorder", 16384)),
        seed=0)


def _random_prompt(rng, n: int, vocab: int) -> list[int]:
    return [rng.randrange(1, vocab) for _ in range(n)]


def warm_up(srv, plan: dict, vocab: int, seed: int, clog: CompileLog,
            deadline_s: float):
    """Drive every dispatch shape the cell's traffic can reach, by the
    server's own `submit` and `step`, before its scheduler thread runs.

    `anchors` long-lived requests (16-token prompts, admitted as one
    group) hold the decode rows in the bucket the window runs in. Then,
    group sizes ascending, for each group size g and each prompt length
    the plan lists for it, g prompts of that length are admitted
    together and stepped to their first token. With the default token
    budget they move in lockstep, so the group meets the first-chunk
    program of its prompt bucket, the full-width later-chunk program,
    and the program of the width bucket its last chunk falls in: the
    lengths are chosen to meet each once. The last group of each size
    in `keep` stays alive after its first token, so that at the end
    every slot is live and the decode program that skips compaction
    runs too. Returns the rows that hold the slots, the kept ones first
    and then the anchors, all still running: the caller hands their
    slots to real requests one at a time (`hand_over`)."""
    import random
    rng = random.Random(f"warmup/{seed}")
    t_end = time.monotonic() + deadline_s
    steps = 0

    def step_until(done, what: str):
        nonlocal steps
        while not done():
            if time.monotonic() > t_end:
                raise TimeoutError(
                    f"warm-up passed its {deadline_s:.0f} s deadline "
                    f"while waiting for {what}")
            srv.step()
            steps += 1

    long_new = int(plan.get("anchor_tokens", 1600))
    anchors = [srv.submit(_random_prompt(rng, 16, vocab),
                          max_new_tokens=long_new)
               for _ in range(int(plan["anchors"]))]
    step_until(lambda: all(len(a.tokens) >= 2 for a in anchors),
               "the anchor rows' first tokens")
    kept = []
    for g in sorted(plan["groups"], key=int):
        lengths = plan["groups"][g]
        n0 = len(clog.events)
        for k, length in enumerate(lengths):
            keep = int(g) in plan.get("keep", []) and k == len(lengths) - 1
            reqs = [srv.submit(_random_prompt(rng, int(length), vocab),
                               max_new_tokens=long_new if keep else 1)
                    for _ in range(int(g))]
            step_until(lambda: all(len(r.tokens) >= 1 for r in reqs),
                       f"a group of {g} prompts of {length}")
            if keep:
                kept.extend(reqs)
        log(f"warm-up groups of {g}, prompts of {lengths}: "
            f"{len(clog.events) - n0} programs")
    n0 = len(clog.events)
    for _ in range(4):
        srv.step()
        steps += 1
    log(f"warm-up with {len(anchors) + len(kept)} rows live: "
        f"{len(clog.events) - n0} programs; {steps} steps, "
        f"{len(clog.events)} programs so far")
    return kept + anchors


def hand_over(srv, holders: list, plan: dict, t0: float) -> float:
    """Hand the slots the warm-up's rows hold to the real requests that
    wait for them, and return the instant the last one was handed over.

    A closed loop on the server's own state, not a timetable: a holder
    is cancelled when its turn has come (evenly from `retire_after_s`
    to `retire_by_s` after t0, the pace of a sound run) AND fewer than
    `retire_outstanding` slots are without a decoding row (free, or
    their prompt still in prefill). So however slow the host is at that
    moment, no more than that many prompts are in prefill together, the
    decode rows never leave the bucket the window runs in, and the ramp
    meets only the group sizes the warm-up drove. On a timetable, a
    host stalled for a second met a group of 16, compiled it for 15 s
    while every slot was freed on time, met a group of 64 next, and the
    window measured nothing (the refusal of PR 24's first check)."""
    import numpy as np
    limit = int(plan.get("retire_outstanding", 3))
    first = t0 + float(plan.get("retire_after_s", 1.0))
    last = t0 + float(plan["retire_by_s"])
    deadline = t0 + float(plan.get("ramp_deadline_s", 60.0))
    slots = len(srv.active)

    def wait(done, what: str):
        while not done():
            if time.monotonic() > deadline:
                raise SystemExit(
                    f"the ramp passed its deadline "
                    f"({plan.get('ramp_deadline_s', 60.0)} s after the "
                    f"generator's start) while waiting for {what}: "
                    f"{int(np.count_nonzero(srv.active))} of {slots} rows "
                    f"decode, {srv.num_pending} requests wait; nothing "
                    "was measured")
            time.sleep(0.004)

    def room() -> bool:
        return slots - int(np.count_nonzero(srv.active)) < limit

    for i, row in enumerate(holders):
        at = first + (last - first) * i / max(len(holders), 1)
        wait(lambda: time.monotonic() >= at and room(),
             f"room to hand over slot {i + 1} of {len(holders)}")
        row.cancel()
        wait(lambda: row.finish_reason is not None,
             f"the cancellation of holder {i + 1}")
    wait(room, "the last slots handed over to decode")
    return time.monotonic()


def _get_json(port: int, path: str, timeout: float = 20.0) -> dict:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
        return json.loads(r.read())


def kill_group(proc: subprocess.Popen) -> None:
    """The child's whole process group, on every way out."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def run_phase(port: int, wl: dict, requests: list, run_dir: str,
              t0: float, max_s: float) -> subprocess.Popen:
    """Start the load generator child on `requests`. It runs until the
    end `end_phase` writes, or `max_s` from t0."""
    req_file = os.path.join(run_dir, "requests.json")
    with open(req_file, "w") as f:
        json.dump(requests, f)
    spec = {"host": "127.0.0.1", "port": port,
            "clients": wl["clients"], "ramp_s": wl.get("ramp_s", 0.0),
            "requests_file": req_file, "t0": t0, "max_s": max_s,
            "end_file": os.path.join(run_dir, "end.json"),
            "grace_s": wl.get("grace_s", 2.0),
            "out": os.path.join(run_dir, "timeline.json")}
    spec_file = os.path.join(run_dir, "loadgen_spec.json")
    with open(spec_file, "w") as f:
        json.dump(spec, f)
    return subprocess.Popen(
        [sys.executable, os.path.join(HERE, "loadgen.py"), spec_file],
        stdout=subprocess.DEVNULL,
        stderr=open(os.path.join(run_dir, "loadgen.err"), "w"),
        start_new_session=True)


def end_phase(run_dir: str, end_s: float) -> None:
    """Tell the load generator when its phase ends, seconds from t0."""
    tmp = os.path.join(run_dir, "end.json.tmp")
    with open(tmp, "w") as f:
        json.dump({"end_s": end_s}, f)
    os.replace(tmp, os.path.join(run_dir, "end.json"))


def check_correct(weights, cfg_file: dict, records: list, check: dict,
                  seed: int) -> dict:
    """Teacher-force a sample of the window's finished requests through
    the plain reference and compare the served log-probabilities: a
    random sample from the seed over all lengths, as many as `samples`
    and `seconds` allow."""
    import random

    import numpy as np

    from cellbench import reference
    done = [r for r in records
            if r["done"] and r["tokens"] and None not in r["logprobs"]
            and r.get("prompt") is not None]
    done.sort(key=lambda r: r["id"])
    random.Random(f"{int(seed)}/check").shuffle(done)
    per_token, used = reference.teacher_force_all(
        weights, reference_spec(cfg_file),
        [(r["prompt"], r["tokens"], r["logprobs"])
         for r in done[:int(check["samples"])]],
        pad_to=int(check.get("pad_to", 256)),
        until=time.monotonic() + float(check.get("seconds", 60.0)))
    if not per_token["served"]:
        return {"correct": False, "reason": "no finished request to check",
                "requests": 0}
    cmp = reference.compare(per_token, **check.get("compare", {}))
    limits = dict(check["limits"])
    ok = all(k in cmp and np.isfinite(cmp[k]) and cmp[k] <= v
             for k, v in limits.items())
    return {"correct": bool(ok), "requests": used, **cmp, "limits": limits}


def run_cell(wl: dict, cfg_file: dict, seed: int, seconds: float,
             trace: bool, run_dir: str, t_process: float, *,
             overrides: dict | None = None,
             require_tpu: bool = True) -> dict:
    """Run one serving cell once and return everything the result line
    and the metric readers need. `overrides`/`require_tpu=False` are the
    CPU rehearsal's (tiny widths; no device metric is ever printed from
    it)."""
    import jax

    from cloud_server_tpu.utils.platform import (
        device_info, enable_compile_cache)
    cache_dir = enable_compile_cache()
    dev = device_info()
    log(f"device: {json.dumps(dev)}; compile cache {cache_dir}")
    if require_tpu and (dev["platform"] != "tpu"
                        or dev["count"] < int(wl["chips"])):
        raise SystemExit(
            f"cell needs {wl['chips']} TPU chip(s); JAX found "
            f"{dev['count']} x {dev['platform']} ({dev['kind']})")
    clog = CompileLog()
    jax.config.update("jax_log_compiles", True)
    jlog = logging.getLogger("jax")
    for h in list(jlog.handlers):  # counted here, not printed line by line
        jlog.removeHandler(h)
    jlog.addHandler(clog)
    jlog.propagate = False

    from cloud_server_tpu.inference.http_server import HttpFrontend
    ov = dict(overrides or {})
    t = time.monotonic()
    mcfg, weights = make_model(cfg_file, ov, seed)
    jax.block_until_ready(weights)
    t_weights = time.monotonic() - t
    srv_opts = dict(wl["server"])
    srv_opts.update(ov.get("server", {}))
    t = time.monotonic()
    srv = build_server(mcfg, weights, srv_opts,
                       int(wl["answer_len"]["max"]))
    t_server = time.monotonic() - t

    t = time.monotonic()
    holders = warm_up(srv, wl["warmup"], mcfg.vocab_size, seed, clog,
                      float(wl["warmup"].get("deadline_s", 1100.0)))
    t_warm = time.monotonic() - t
    programs_warm = len(clog.events)

    srv.start()
    front = HttpFrontend(srv, tokenizer=None, port=0)
    front.start()
    port = front.address[1]

    preroll_s = float(wl["preroll_s"])
    requests = traffic.make_requests(wl, seed, mcfg.vocab_size)
    prompts = {r["id"]: r["tokens"] for r in requests}
    t0 = time.monotonic() + float(wl.get("lead_s", 2.0))
    ramp_deadline_s = float(wl["warmup"].get("ramp_deadline_s", 60.0))
    settle_s = float(wl.get("settle_s", 3.0))
    child = run_phase(port, wl, requests, run_dir, t0,
                      max(preroll_s, ramp_deadline_s + settle_s) + seconds)
    trace_dir = os.path.join(run_dir, "trace")
    trace_span = None
    try:
        # the window starts `preroll_s` after t0, as in every sound run,
        # or `settle_s` after the ramp's end where that came later
        ramp_end = hand_over(srv, holders, wl["warmup"], t0)
        w0 = max(t0 + preroll_s, ramp_end + settle_s)
        w1 = w0 + seconds
        end_phase(run_dir, w1 - t0)
        time.sleep(max(0.0, w0 - time.monotonic()))
        programs_preroll = len(clog.events)
        if trace:
            ts = float(wl.get("trace_s", 3.0))
            time.sleep(max(0.0, w1 - ts - time.monotonic()))
            jax.profiler.start_trace(trace_dir)
            t_tr0 = time.monotonic()
            time.sleep(max(0.0, w1 - time.monotonic()))
            jax.profiler.stop_trace()
            trace_span = (t_tr0, w1)
        time.sleep(max(0.0, w1 - time.monotonic()))
        # the window is over: stop the scheduler before the connections
        # the generator closes cancel its rows, or the shrinking batch
        # meets dispatch shapes no warm-up drove and compiles them (18 s
        # at Mixtral's depth, seen on the chip). What the stop does to
        # unfinished requests happens after w1 and is no failure.
        srv.stop()
        try:
            child.wait(timeout=float(wl.get("grace_s", 2.0)) + 15.0)
        except subprocess.TimeoutExpired:
            log("the load generator outlived its deadline; killed")
        mem = jax.devices()[0].memory_stats() or {}
        try:
            stats_json = _get_json(port, "/stats?n=16384")
        except (OSError, ValueError) as exc:  # the window was measured
            log(f"/stats gave no answer ({exc!r}): the scheduler's "
                "metrics are left out")
            stats_json = {}
    finally:
        kill_group(child)
        stopper = threading.Thread(
            target=lambda: (front.stop(), srv.stop()), daemon=True)
        stopper.start()
        stopper.join(timeout=40.0)
        if stopper.is_alive():
            log("server stop passed its 40 s deadline; left to exit")
    out_file = os.path.join(run_dir, "timeline.json")
    if not os.path.exists(out_file):
        with open(os.path.join(run_dir, "loadgen.err")) as f:
            sys.stderr.write(f.read()[-3000:])
        raise SystemExit("the load generator wrote no timeline: nothing "
                         "was measured")
    with open(out_file) as f:
        timeline = json.load(f)
    records = timeline["records"]
    for r in records:
        r["prompt"] = prompts.get(r["id"])
    # times in the timeline are seconds from t0
    rw0, rw1 = w0 - t0, w1 - t0
    in_window = [r for r in records if r["sent"] is not None
                 and r["sent"] >= rw0]
    srv.state = None  # the pools: room for the reference
    t = time.monotonic()
    try:
        check = check_correct(weights, cfg_file, in_window, wl["check"],
                              seed)
    except Exception as exc:  # noqa: BLE001 - the window was measured
        import traceback
        traceback.print_exc()
        check = {"correct": False, "reason": f"the check raised {exc!r}"}
    t_check = time.monotonic() - t
    return {
        "device": dev, "mem": mem, "records": records,
        "window": (rw0, rw1), "window_abs": (w0, w1), "t0": t0,
        "wall_minus_mono": time.time() - time.monotonic(),
        "setup_s": w0 - t_process, "seconds": seconds,
        "compile_log": clog, "programs_warm": programs_warm,
        "programs_preroll": programs_preroll,
        "stats": stats_json, "trace_dir": trace_dir if trace else None,
        "trace_span": trace_span, "check": check,
        "num_pages": int(srv_opts["num_pages"]),
        "phases": {"weights_s": round(t_weights, 2),
                   "server_s": round(t_server, 2),
                   "warm_up_s": round(t_warm, 2),
                   "ramp_s": round(ramp_end - t0, 2),
                   "preroll_s": round(w0 - t0, 2),
                   # the server's stop, the generator's end, `/stats`
                   "after_window_s": round(t - w1, 2),
                   "check_s": round(t_check, 2)},
        "loadgen": {"ended_at": timeline["ended_at"],
                    "threads_left": timeline["threads_left"]},
    }


def trace_planes(ctx: dict):
    """The run's device planes (`xplane.load`), read once; None for an
    untraced run or a trace that was not written."""
    if "_planes" not in ctx:
        from cellbench import xplane
        path = ctx["trace_dir"] and xplane.find_xplane(ctx["trace_dir"])
        ctx["_planes"] = xplane.load(path) if path else None
        if ctx["trace_dir"]:
            found = sum(([os.path.join(d, f) for f in fs]
                         for d, _, fs in os.walk(ctx["trace_dir"])), [])
            log(f"trace files: {found[:6]}; planes: " + json.dumps(
                {k: {ln: len(ev) for ln, ev in v.items()}
                 for k, v in (ctx["_planes"] or {}).items()}))
    return ctx["_planes"]


def first_plane(ctx: dict):
    """The first chip's plane of the run's trace, or None."""
    planes = trace_planes(ctx)
    return planes[sorted(planes)[0]] if planes else None


def flight_in(ctx: dict, t_lo: float, t_hi: float) -> list[dict]:
    """The scheduler's flight records (one per busy iteration, from
    `/stats`) whose closing stamp lies in [t_lo, t_hi) on the monotonic
    clock. Records carry `ts` on the wall clock; `wall_minus_mono`
    brings them over."""
    off = ctx["wall_minus_mono"]
    return [r for r in ctx["stats"].get("flight_recorder", [])
            if t_lo <= r.get("ts", 0.0) - off < t_hi]


def untraced_span(ctx: dict) -> tuple[float, float]:
    """The part of the window the profiler was off in: host-clock layer
    metrics are read there, so tracing's own cost stays out of them."""
    w0, w1 = ctx["window_abs"]
    return (w0, ctx["trace_span"][0]) if ctx["trace_span"] else (w0, w1)


def result_line(ctx: dict, metrics: dict, trace: bool) -> dict:
    """The contract's last line, after the run's own report on stderr."""
    from cellbench import xplane
    records, (rw0, rw1) = ctx["records"], ctx["window"]
    attempted, failed = stats.count_attempted_failed(records, rw0, rw1)
    gaps = stats.gap_samples(records, rw0, rw1)
    ttft, censored = stats.ttft_samples(records, rw0, rw1)
    w0, w1 = ctx["window_abs"]
    clog = ctx["compile_log"]
    log("phases " + json.dumps(ctx["phases"]))
    log(f"programs: {ctx['programs_warm']} after warm-up, "
        f"{ctx['programs_preroll']} at the window's start, "
        f"{len(clog.events)} at its end; in the window "
        f"{clog.count_between(w0, w1)}; by function "
        + json.dumps(clog.summary(w1)))
    log(f"window: attempted {attempted} failed {failed} tokens "
        f"{stats.tokens_in_window(records, rw0, rw1)}; ttft n={len(ttft)} "
        f"censored={censored} p50={stats.pct(ttft, .5) * 1e3:.1f} ms "
        f"p95={stats.pct(ttft, .95) * 1e3:.1f} ms; gaps n={len(gaps)} "
        f"p50={stats.pct(gaps, .5) * 1e3:.2f} ms "
        f"p95={stats.pct(gaps, .95) * 1e3:.2f} ms; loadgen "
        + json.dumps(ctx["loadgen"]))
    late = [(round(t - ctx["t0"], 2), n, sh[-150:])
            for t, n, sh in clog.shapes
            if t >= ctx["t0"] and ("_mixed_step" in n or "_decode" in n)]
    if late:
        log("programs met after the warm-up (s from the pre-roll's start, "
            "function, integer argument shapes): " + json.dumps(late))
    off = ctx["wall_minus_mono"]
    for name, lo, hi in (("warm-up", 0.0, ctx["t0"]),
                         ("pre-roll", ctx["t0"], w0), ("window", w0, w1)):
        ds = [r["duration_ms"] for r in ctx["stats"].get(
            "flight_recorder", []) if lo <= r.get("ts", 0.0) - off < hi
            and "duration_ms" in r]
        if ds:
            log(f"iterations in the {name}: n={len(ds)} "
                f"p50={stats.pct(ds, .5):.1f} ms p95={stats.pct(ds, .95):.1f}"
                f" ms max={max(ds):.0f} ms")
    log("check " + json.dumps(ctx["check"]))
    if attempted < 1:
        raise SystemExit(
            "no request was sent inside the window (a closed loop sends "
            "one whenever one ends, so the server ended none in "
            f"{ctx['seconds']:.0f} s): nothing was measured")
    device = dict(ctx["device"])
    device["memory_peak_bytes"] = int(
        ctx["mem"].get("peak_bytes_in_use", 0))
    line = {"correct": bool(ctx["check"]["correct"]),
            "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        planes = trace_planes(ctx)
        if planes:
            busy, window = xplane.device_busy(planes)
            device["busy_s"], device["window_s"] = busy, window
            first = first_plane(ctx)
            host = {}
            for r in flight_in(ctx, *ctx["trace_span"]):
                for ph, ms in r.get("phases_ms", {}).items():
                    if ph != "device":
                        host[ph] = host.get(ph, 0.0) + ms / 1e3
            by_host = sorted(([f"host:{k}", v] for k, v in host.items()),
                             key=lambda e: -e[1])[:5]
            line["breakdown"] = {
                "device_ops": xplane.top_ops(first, 10),
                "idle_gaps": by_host + [
                    ["before:" + n, s]
                    for n, s in xplane.idle_gaps(first, 5)]}
    return line
