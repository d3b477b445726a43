"""Iteration-phase profiler: host-gap attribution for the scheduler
hot loop.

The flight recorder (PR 3) stamps every busy scheduler iteration with
one `duration_ms` — enough to see that an iteration was slow, not
enough to say WHERE the time went. The scheduler overlaps host policy
work with device compute; this is the measurement layer under that
claim: per-phase attribution of every iteration, so the host gap the
pipeline hides, and the part it does not, are measured numbers
(`host_gap_frac`), not inferences from end-to-end tok/s.

Phase taxonomy (one contiguous partition of the iteration, stamped at
boundaries the scheduler already crosses):

    sweep       cancelled-request reaping at the top of step()
    admission   QoS/DRR admission, token-budget planning, chain
                extension/preemption policy — the host DECIDING what
                to dispatch
    build       host array prep (numpy staging, padding, gathers) up
                to the jitted call
    device      the dispatch statement (arg device transfer + launch)
                through the one sanctioned `device_get` commit point —
                the only phase that waits on the accelerator
    commit      ledger writes, tokens recorded on their requests,
                grammar / speculation bookkeeping on the synced
                results, up to the next phase's first statement. Nobody
                is woken here
    launch      the patch + next-dispatch launch. In the steady state
                it comes BEFORE `device`: the next program goes onto
                the device's queue while the one in flight still runs,
                from the planned frame, and `device`, `commit` and the
                rest follow under it (the record of the dispatch says
                so: `launch_ahead`). Where the launch needs what only
                the commit knows (`launch_waits`: draft tokens, a
                constrained row, a hand-off) it follows the commit,
                patched from the ledger: the tail of the serialized
                critical path
    deliver     the commit's stream calls and completions, run after
                the launch: the streaming threads they wake take the
                interpreter lock under the program just launched, not
                between two programs (6 ms of an 8 ms commit with 64
                clients attached before; PERF.md, PR 25 and PR 30)
    epilogue    flight-recorder / tracing / SLO bookkeeping at the end
                of the iteration

A step that COMMITS a program (every busy step but a fill; its record
says `overlap: true`): sweep / admission / build run WHILE the device
executes the program the step commits, and deliver while it executes
the next one's, so they are not device-idle time. Those phases fold
into `overlap_ms` (and the single `overlap`-labeled histogram series),
`device` is the RESIDUAL wait after the overlapped host work, and
`host_gap_frac` measures only the host tail (`commit` + `launch` +
`epilogue`) — the residual cost the pipeline could not hide where the
launch waits for the commit. In a step that launched ahead that tail is
the same work and the same `host_ms`, but it too runs beside a program
(`launch` under the one in flight, `commit` and `epilogue` under the
one just queued): there `host_gap_frac` is the tail's share of the
step, an upper bound on the idle share, and whether the device stood
idle at all is the record's `host_late`. The per-record identity is
`host_ms + device_wait_ms + overlap_ms == duration_ms`.

A FILL step (nothing was in flight: it plans and launches, and commits
nothing; its record says `fill: true`) has phases and a duration but
waited on no program: its record carries no `host_ms`,
`device_wait_ms`, `overlap_ms` or `host_gap_frac`, and its phases go
into their own histogram series, unfolded, where `profile_summary`
counts them as host time.

Outside that identity, and in no phase: `between_ms`, from a busy
step's `end()` to the next step's `begin()` (the loop's yield to the
streaming threads, the step lock, the preamble), so that
`between_ms + duration_ms` is the scheduler's period and the records
of a window add up to its length. Inside `build`, which stays whole:
`stage_ms`, two `lap()`s around the block that hands the plan's
arrays to the device.

Design rules (the metrics layer's own):

  * **Stdlib only, zero device work.** The clock is
    `time.perf_counter`; a phase boundary is one clock read, one dict
    add and one trace annotation (handed in by the servers, inactive
    unless a jax profiler capture is running: the same boundary is
    then a `sched/<phase>` event on the profiler's clock, next to the
    device's programs). The module is on the analysis hot-path lint
    roster AND the dispatch-discipline host-policy (jax-free) roster;
    the mixed scheduler's dispatch/sync-count regression test runs a
    profiling-enabled clone, and a bounded CONSTANT number of clock
    reads per mixed iteration is asserted by monkeypatching
    `perf_counter` (tests/test_iteration_profile.py).
  * **Same plumbing as every other signal.** Phases land in the
    flight record (`phases_ms` + derived `host_ms` /
    `device_wait_ms` / `host_gap_frac`), in rolling per-phase
    histograms (`cloud_server_iter_phase_ms`, labeled by phase,
    fleet-merged bucket-for-bucket through
    `ReplicatedRouter.metrics_snapshot()`), in `/stats`
    (`iteration_profile`: per-phase p50/p99 + `host_gap_frac`), and
    in a scheduler-timeline Perfetto export
    (`GET /debug/scheduler_trace?n=K`) cross-linked to the
    per-request span trees by the flight-recorder iteration index.
  * **Disable-able.** `InferConfig.iteration_profile` (default on) /
    the servers' `iteration_profile=` constructor argument; disabled
    servers keep the exact pre-profiler clock behavior (two
    perf_counter reads per busy iteration).

Timebase note: with profiling enabled, a busy iteration's
`duration_ms` spans the WHOLE iteration (sweep through epilogue), so
`host_ms + device_wait_ms == duration_ms` by construction; with it
disabled, `duration_ms` keeps its historical meaning (dispatch start
to epilogue). Flight records gain `t_start` (the iteration's
perf_counter start), which is what lets the scheduler timeline export
share a timebase with the request-trace export (`GET /traces`).
"""

from __future__ import annotations

from time import perf_counter

from cloud_server_tpu.utils.serving_metrics import histogram_percentile

# Canonical phase order — the contiguous partition of one iteration.
PHASES = ("sweep", "admission", "build", "device", "commit", "launch",
          "deliver", "epilogue")

# Phases that run concurrently with a device program in a step that
# commits one; they fold into the `overlap` histogram label and
# `overlap_ms`.
OVERLAP_PHASES = ("sweep", "admission", "build", "deliver")

# Histogram label set: the fine-grained phases plus the folded
# `overlap` series committing steps observe instead of their
# sweep/admission/build/deliver split (keeping `profile_summary`'s
# host-gap arithmetic honest across fill and committing steps — the
# fine split of a committing step stays in its flight record).
HIST_PHASES = PHASES + ("overlap",)

# Millisecond bucket ladder for the per-phase histograms: sub-0.1 ms
# host blips through multi-second cold dispatches. Fixed at
# registration so replica snapshots merge bucket-for-bucket.
PHASE_MS_BUCKETS: tuple[float, ...] = (
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0,
    250.0, 500.0, 1000.0, 5000.0)

# Histogram family (one labeled series per phase). Shared between the
# servers' eager registration and `profile_summary`'s snapshot walk.
PHASE_FAMILY = "iter_phase_ms"
_FULL_FAMILY = f"cloud_server_{PHASE_FAMILY}"

# The phases' names as events of a jax profiler trace (one per open
# phase, `iteration=<n>` in their stats) and the event enclosing a
# step: what `cellbench/hostplane.py` and a Perfetto reader look for.
_PHASE_EVENTS = {p: "sched/" + p for p in PHASES}
_ITERATION_EVENT = "sched/iteration"

# Flight-record scalars worth carrying into the Perfetto iteration
# track's args (post-mortem context next to the phase bars).
_ITER_ARG_KEYS = ("iteration", "fill", "n_live", "decode_rounds",
                  "decode_tokens", "prefill_tokens", "tokens_scheduled",
                  "budget_utilization", "host_ms", "device_wait_ms",
                  "host_gap_frac", "preemptions", "pending", "n_jobs",
                  "overlap", "overlap_ms", "overlap_launch_lead_ms",
                  "delivered", "joined", "grouped", "launch_h2d",
                  "between_ms", "stage_ms", "plan_h2d", "host_late",
                  "launch_ahead", "launch_waits")


class IterationProfiler:
    """Host-side phase clock for one scheduler iteration.

    `begin(iteration)` opens the iteration in its first phase, `sweep`;
    `enter(phase)` is a phase BOUNDARY: the time since the previous
    boundary goes to the phase that was open, and `phase` opens
    (entering the phase that is already open is no boundary: no clock
    read, no event). Time ACCUMULATES per phase, so a phase visited
    several times in one iteration sums. `end()` closes a busy
    iteration.
    All three return the boundary's timestamp so callers reuse it
    instead of reading the clock again: the scheduler pays a
    bounded constant number of `perf_counter` reads per iteration
    (asserted by test).

    The same boundaries are events on the jax profiler's clock: handed
    `annotate` (`utils.tracing.annotate`; this module stays jax-free),
    every open phase is a `sched/<phase>` trace event and the iteration
    an enclosing `sched/iteration`, each carrying `iteration=<n>`, the
    flight-recorder index the step gets when it records. A step
    that dispatches nothing ends in `close()`: no record, and its
    `sched/iteration` carries no index. With no capture running an
    annotation is an inactive check.

    `between_ms` is what passed between the `end()` of a busy step and
    this step's `begin()`, on the two clock reads those already make;
    None on a first step and after a `close()`: a step that recorded
    nothing, or a loop that went to wait for work."""

    __slots__ = ("t0", "between_ms", "_last", "_ended", "_acc", "_phase",
                 "_annotate", "_stats", "_iter_span", "_span")

    def __init__(self, annotate=None):
        self.t0 = 0.0
        self.between_ms: float | None = None
        self._last = 0.0
        self._ended = False
        self._acc: dict[str, float] = {}
        self._phase = PHASES[0]
        self._annotate = annotate
        self._stats: dict[str, int] = {}
        self._iter_span = None
        self._span = None

    def begin(self, iteration: int | None = None) -> float:
        if self._iter_span is not None:
            self.close()  # the previous step raised mid-iteration
        t = perf_counter()
        self.between_ms = (t - self._last) * 1e3 if self._ended else None
        self._ended = False
        self.t0 = self._last = t
        self._acc = {}
        self._phase = phase = PHASES[0]
        if self._annotate is not None:
            self._stats = ({} if iteration is None
                           else {"iteration": iteration})
            self._iter_span = self._annotate(_ITERATION_EVENT)
            self._iter_span.__enter__()
            self._span = self._annotate(_PHASE_EVENTS[phase],
                                        **self._stats)
            self._span.__enter__()
        return t

    def enter(self, phase: str) -> float:
        cur = self._phase
        if phase == cur:
            return self._last
        t = perf_counter()
        acc = self._acc
        acc[cur] = acc.get(cur, 0.0) + (t - self._last)
        self._last = t
        self._phase = phase
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = self._annotate(_PHASE_EVENTS[phase],
                                        **self._stats)
            self._span.__enter__()
        return t

    def end(self) -> float:
        """Close a busy iteration: the open phase ends here, and the
        `sched/iteration` event takes the iteration's index."""
        t = perf_counter()
        acc = self._acc
        cur = self._phase
        acc[cur] = acc.get(cur, 0.0) + (t - self._last)
        self._last = t
        if self._iter_span is not None:
            self._iter_span.set_metadata(**self._stats)
            self.close()
        self._ended = True
        return t

    def lap(self) -> float:
        """One read of the phase clock that is no boundary: two of them
        time a part of the open phase (`stage_ms` inside `build`)."""
        return perf_counter()

    def close(self) -> None:
        """Close the open trace events without a clock read: the end of
        a step that recorded nothing, or of a stretch of steps (the
        loop waits for work), so the time to the next `begin()` is no
        step's `between_ms`."""
        self._ended = False
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        if self._iter_span is not None:
            self._iter_span.__exit__(None, None, None)
            self._iter_span = None

    def phases_ms(self) -> dict[str, float]:
        """Accumulated per-phase milliseconds, canonical order. The
        values partition [t0, last boundary]: their sum is the elapsed
        time between those clock reads (no time is double-counted or
        dropped), which is what makes the flight record's
        `host_ms + device_wait_ms == duration_ms` hold exactly."""
        acc = self._acc
        return {p: acc[p] * 1e3 for p in PHASES if p in acc}


def register_phase_hists(registry) -> dict:
    """Eagerly register the per-phase histogram family on a server's
    registry (one labeled series per phase) and return the
    phase -> Histogram dict the per-iteration observe path indexes.
    THE one registration site: the family name, help
    text, and ms ladder must match everywhere or the router's
    bucket-for-bucket fleet merge breaks."""
    return {
        p: registry.histogram(
            PHASE_FAMILY,
            "Scheduler iteration time by phase (milliseconds)",
            buckets=PHASE_MS_BUCKETS, labels={"phase": p})
        for p in HIST_PHASES}


def resolve_profiler(profile, cfg_enabled: bool = True,
                     annotate=None) -> IterationProfiler | None:
    """The one constructor: `profile` may be a ready
    IterationProfiler, True/False, "off", or None (falling back to
    `InferConfig.iteration_profile`). Returns None when disabled —
    every guarded call site short-circuits and the scheduler keeps
    the exact pre-profiler clock behavior. `annotate` (the servers
    hand `utils.tracing.annotate`) is what a profiler built here opens
    its trace events with."""
    if profile is False or profile == "off":
        return None
    if isinstance(profile, IterationProfiler):
        return profile
    if profile is True:
        return IterationProfiler(annotate)
    if profile is None:
        return IterationProfiler(annotate) if cfg_enabled else None
    raise ValueError(
        "iteration_profile must be True, False, 'off', None, or an "
        f"IterationProfiler; got {profile!r}")


def derive_gap_fields(phases_ms: dict[str, float],
                      duration_ms: float) -> dict[str, float]:
    """The derived flight-record fields of a step that committed a
    program, from its phase split: sweep/admission/build and deliver
    ran concurrently with a device program, so they are `overlap_ms`;
    `host_ms` is the residual serialized tail (commit + launch +
    epilogue), `device_wait_ms` the wait for the program, and
    `host_gap_frac` therefore measures what the pipeline could NOT
    hide."""
    device = phases_ms.get("device", 0.0)
    overlap = sum(phases_ms.get(p, 0.0) for p in OVERLAP_PHASES)
    host = sum(v for k, v in phases_ms.items()
               if k != "device" and k not in OVERLAP_PHASES)
    return {"host_ms": host, "device_wait_ms": device,
            "overlap_ms": overlap,
            "host_gap_frac": host / duration_ms if duration_ms > 0
            else 0.0}


def profile_summary(snapshot: dict) -> dict | None:
    """The `/stats` `iteration_profile` payload from a metrics
    snapshot (one server's, or the router's fleet-merge — the phase
    histograms merged bucket-for-bucket upstream, so these are true
    fleet percentiles): per-phase count/mean/p50/p99 milliseconds
    plus the aggregate `host_gap_frac` recomputed from the merged
    sums (a ratio must never be added across replicas). None when no
    phase histograms are present (profiling disabled, or a backend
    without it)."""
    phases: dict[str, dict] = {}
    host_ms = device_ms = overlap_ms = 0.0
    for key, entry in snapshot.items():
        if not key.startswith(_FULL_FAMILY + "{") \
                or entry.get("type") != "histogram":
            continue
        phase = (entry.get("labels") or {}).get("phase")
        if phase is None:
            continue
        count = entry["count"]
        phases[phase] = {
            "count": count,
            "mean_ms": entry["sum"] / count if count else 0.0,
            "p50_ms": histogram_percentile(entry, 0.50),
            "p99_ms": histogram_percentile(entry, 0.99)}
        if phase == "device":
            device_ms += entry["sum"]
        elif phase == "overlap":
            # host work performed while a dispatch was in flight (a
            # committing step's OVERLAP_PHASES): not device-idle time,
            # so not host gap
            overlap_ms += entry["sum"]
        else:
            host_ms += entry["sum"]
    if not phases:
        return None
    total = host_ms + device_ms + overlap_ms
    return {"phases": {p: phases[p] for p in HIST_PHASES if p in phases},
            "host_ms_total": host_ms,
            "device_wait_ms_total": device_ms,
            "overlap_ms_total": overlap_ms,
            "host_gap_frac": host_ms / total if total > 0 else 0.0}


def scheduler_chrome_trace(records: list[dict]) -> dict:
    """Render flight-recorder records as Chrome trace event format
    JSON (chrome://tracing / ui.perfetto.dev): one process per
    replica, one track per phase plus an `iteration` track whose args
    carry the record's scalars. Timestamps are microseconds on the
    servers' perf_counter timebase — the SAME timebase as the
    request-trace export (`GET /traces`), and every event's args
    carry the flight-recorder `iteration` index, which is also the
    tag on every `prefill_chunk`/`decode_segment` span in a request's
    tree: the two exports cross-link in both directions ("why was
    this request's decode_segment slow" ↔ "what was the scheduler
    doing that iteration").

    Phases render laid out consecutively in canonical order inside
    the iteration window: a step that launched ahead crossed `launch`
    before `device`, so bar order within an iteration is attribution,
    not a literal interleaving. Records written with profiling disabled
    carry no `t_start`/`phases_ms` and are skipped.

    Iterations are NOT disjoint in device time: the program committed
    by iteration k+1 was launched inside iteration k's window. Each
    record of a step that launched carries `t_launch`, and the export renders an
    `inflight` track whose slices span from that launch to the END of
    the NEXT record's residual `device` wait — so the device slice
    visibly runs CONCURRENT with (nested under) the next iteration's
    sweep/admission/build bars instead of the export pretending
    iteration bounds partition device time."""
    events: list[dict] = []
    seen_pids: set[int] = set()
    inflight_tid = len(PHASES) + 1
    last_launch: dict[int, tuple[float, int]] = {}  # pid -> (ts, iter)
    for rec in records:
        t0 = rec.get("t_start")
        if t0 is None:
            continue
        pid = int(rec.get("replica", 0))
        if pid not in seen_pids:
            seen_pids.add(pid)
            events.append({"ph": "M", "name": "process_name",
                           "pid": pid,
                           "args": {"name": f"scheduler replica {pid}"}})
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": 0, "args": {"name": "iteration"}})
            for i, p in enumerate(PHASES):
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": i + 1,
                               "args": {"name": p}})
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": inflight_tid,
                           "args": {"name": "inflight"}})
        args = {k: rec[k] for k in _ITER_ARG_KEYS if k in rec}
        events.append({"ph": "X",
                       "name": f"iteration {rec.get('iteration')}",
                       "ts": t0 * 1e6,
                       "dur": rec.get("duration_ms", 0.0) * 1e3,
                       "pid": pid, "tid": 0, "args": args})
        off = t0 * 1e6
        device_end = None
        for i, p in enumerate(PHASES):
            v = (rec.get("phases_ms") or {}).get(p, 0.0)
            if v <= 0:
                continue
            events.append({"ph": "X", "name": p, "ts": off,
                           "dur": v * 1e3, "pid": pid, "tid": i + 1,
                           "args": {"iteration": rec.get("iteration")}})
            off += v * 1e3
            if p == "device":
                device_end = off
        if rec.get("overlap") and pid in last_launch \
                and device_end is not None:
            # the dispatch THIS record committed: launched inside the
            # previous record's window, device-resident until this
            # record's residual sync — one concurrent slice
            ts_launch, it_launch = last_launch.pop(pid)
            events.append({"ph": "X",
                           "name": f"dispatch (committed by iteration "
                                   f"{rec.get('iteration')})",
                           "ts": ts_launch * 1e6,
                           "dur": max(device_end - ts_launch * 1e6, 0.0),
                           "pid": pid, "tid": inflight_tid,
                           "args": {"launched_in_iteration": it_launch,
                                    "iteration": rec.get("iteration")}})
        if rec.get("t_launch") is not None:
            last_launch[pid] = (rec["t_launch"], rec.get("iteration"))
    return {"traceEvents": events, "displayTimeUnit": "ms"}
