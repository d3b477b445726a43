"""The host's phases and the device's ops of one trace, on one clock.

`jax.profiler.ProfileData` shows an event's own stats, which is enough
for the scheduler's `sched/<phase>` events (`iteration=<n>`), but not the
stats of an event's *metadata*, where a TPU trace keeps what an op is:
`tf_op`, the `jax.named_scope` path of the HLO op
(`jit(_mixed_step)/decode_rounds/moe_experts/ecd,edf->ecf/dot_general:`).
So this file reads the `.xplane.pb` itself: the few fields of the XSpace
protobuf it needs, decoded from the wire format with the standard library
(no protobuf schema is installed beside JAX). `load(path)` gives plain
lists; every reduction below works on those, so it is tested on hand-made
ones and once on a real file.

An event is (name, start_ns, duration_ns, stat) where start is the line's
`timestamp_ns` plus the event's `offset_ps`: the profiler's clock, the
same for host and device planes. `stat` is the `iteration` of a
`sched/*` event and the `tf_op` of a device op ("" or None without).

Idle time between programs is put down gap by gap: each gap between
consecutive events of `XLA Modules` goes to the `sched/*` phases that
overlap it, by overlap length. What the scheduler's thread did while a
program ran counts nothing, whatever its total.
"""

from __future__ import annotations

import struct

from cellbench import xplane
from cellbench.xplane import MODULES_LINE, OPS_LINE

Span = tuple[str, float, float, object]  # name, start_ns, duration_ns, stat

EVENT_PREFIX = "sched/"
ITERATION_EVENT = EVENT_PREFIX + "iteration"

# which idle share a phase's overlap with a gap between programs goes to:
# `commit` is the serialized read-back (the end of `device` after the
# program, `commit`, `epilogue`), `launch` the patch and dispatch of the
# next program, `plan` what the overlap was meant to hide and did not
IDLE_GROUPS = {"device": "commit", "commit": "commit", "epilogue": "commit",
               "launch": "launch",
               "sweep": "plan", "admission": "plan", "build": "plan"}
UNNAMED = "unnamed"


# -- the wire format -------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: ints for varint
    and fixed fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 1:
            v = bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            v = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an XSpace")
        yield num, wt, v


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: dict[int, str]):
    """(stat name, value) of one XStat."""
    name, value = "", None
    for num, _, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v, "")
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _int64(v)
        elif num == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for num, _, v in _fields(buf):
        if num == 1:
            key = _int64(v)
        elif num == 2:
            value = v
    return key, value


def _event_metadata(buf, stat_names, want_stat: str):
    """(name, the value of stat `want_stat` or None) of an XEventMetadata."""
    name, stat = "", None
    for num, _, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 5:
            k, val = _stat(v, stat_names)
            if k == want_stat:
                stat = val
    return name, stat


def _line_events(buf, keep: dict[int, tuple[str, object]], stat_names,
                 want_stat: str | None) -> tuple[str, list[Span]]:
    """A line's name and its events whose metadata id is in `keep`
    ({id: (name, metadata stat)}). With `want_stat`, the event's own stat
    of that name takes the metadata stat's place."""
    name, t0_ns = "", 0
    out: list[Span] = []
    for num, _, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif num == 3:
            t0_ns = _int64(v)
        elif num == 4:
            # most events of a host line under JAX's default options are
            # Python calls: left unread after their first field
            if v[0] == 8 and _int64(_varint(v, 1)[0]) not in keep:
                continue
            mid = off_ps = dur_ps = 0
            stats = []
            for enum, _, ev in _fields(v):
                if enum == 1:
                    mid = _int64(ev)
                    if mid not in keep:
                        break
                elif enum == 2:
                    off_ps = _int64(ev)
                elif enum == 3:
                    dur_ps = _int64(ev)
                elif enum == 4 and want_stat is not None:
                    stats.append(ev)
            if mid not in keep:
                continue
            ev_name, stat = keep[mid]
            for st in stats:
                k, val = _stat(st, stat_names)
                if k == want_stat:
                    stat = val
            out.append((ev_name, off_ps / 1e3, dur_ps / 1e3, stat))
    # the line's own timestamp, wherever in the message it stood
    out = [(n, t0_ns + s, d, st) for n, s, d, st in out]
    return name, out


def load(path: str) -> dict:
    """{"sched": {thread line: [(sched/<name>, start, dur, iteration)]},
    "devices": {plane: {"XLA Modules": [(name, start, dur, None)],
                        "XLA Ops": [(name, start, dur, tf_op)]}}}"""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    sched: dict[str, list[Span]] = {}
    devices: dict[str, dict[str, list[Span]]] = {}
    for num, _, plane in _fields(space):
        if num != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for pnum, _, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode("utf-8", "replace")
            elif pnum == 3:
                lines.append(v)
            elif pnum == 4:
                metas.append(v)
            elif pnum == 5:
                sid, sm = _map_entry(v)
                for snum, _, sv in _fields(sm):
                    if snum == 2:
                        stat_names[sid] = bytes(sv).decode("utf-8",
                                                            "replace")
        device = name.startswith("/device:TPU:")
        if not device and not name.startswith("/host:"):
            continue
        keep = {}
        for m in metas:
            mid, mv = _map_entry(m)
            ev_name, stat = _event_metadata(mv, stat_names, "tf_op")
            if device or ev_name.startswith(EVENT_PREFIX):
                keep[mid] = (ev_name, stat if device else None)
        if not keep:
            continue
        for ln in lines:
            ln_name, events = _line_events(
                ln, keep, stat_names, None if device else "iteration")
            if device and ln_name in (OPS_LINE, MODULES_LINE):
                devices.setdefault(name, {}).setdefault(
                    ln_name, []).extend(events)
            elif not device and events:
                sched.setdefault(ln_name, []).extend(events)
    return {"sched": sched, "devices": devices}


# -- reductions on plain lists ---------------------------------------------

def phases(trace: dict) -> list[Span]:
    """The `sched/<phase>` events of the scheduler's thread (the line
    with most of them), by start; the enclosing `sched/iteration`
    events left out."""
    lines = trace["sched"].values()
    if not lines:
        return []
    line = max(lines, key=len)
    return sorted((e for e in line if e[0] != ITERATION_EVENT),
                  key=lambda e: e[1])


def iterations(trace: dict) -> list[Span]:
    """The `sched/iteration` events that carry an index: busy steps."""
    lines = trace["sched"].values()
    if not lines:
        return []
    return sorted((e for e in max(lines, key=len)
                   if e[0] == ITERATION_EVENT and e[3] is not None),
                  key=lambda e: e[1])


def first_device(trace: dict) -> dict[str, list[Span]] | None:
    devs = trace["devices"]
    return devs[sorted(devs)[0]] if devs else None


def union_ns(events) -> float:
    """Nanoseconds covered by at least one event."""
    return xplane.union_seconds([e[:3] for e in events]) * 1e9


def span_ns(plane: dict[str, list[Span]]) -> float:
    """First start to last end of a device plane's ops and programs:
    the span `device_idle_share` is a share of."""
    return xplane.span_seconds(
        [e[:3] for ln in (OPS_LINE, MODULES_LINE)
         for e in plane.get(ln, [])]) * 1e9


def gaps(modules) -> list[tuple[float, float]]:
    """[(start, end)] of the time between consecutive programs."""
    out, end = [], None
    for e in sorted(modules, key=lambda e: e[1]):
        if end is not None and e[1] > end:
            out.append((end, e[1]))
        end = e[1] + e[2] if end is None else max(end, e[1] + e[2])
    return out


def idle_by_group(modules, sched_phases) -> dict[str, float]:
    """Nanoseconds of idle time between programs, by what the
    scheduler's thread was in: {"commit", "launch", "plan", "unnamed"}.
    Each gap goes to the phases that overlap it, by overlap length; the
    part no phase covers is `unnamed`."""
    out = {g: 0.0 for g in set(IDLE_GROUPS.values())}
    out[UNNAMED] = 0.0
    ph = sorted(sched_phases, key=lambda e: e[1])
    j = 0
    for g0, g1 in gaps(modules):
        while j < len(ph) and ph[j][1] + ph[j][2] <= g0:
            j += 1
        covered, k = 0.0, j
        while k < len(ph) and ph[k][1] < g1:
            name, s, d = ph[k][0], ph[k][1], ph[k][2]
            ov = min(g1, s + d) - max(g0, s)
            group = IDLE_GROUPS.get(name[len(EVENT_PREFIX):])
            if ov > 0 and group is not None:
                out[group] += ov
                covered += ov
            k += 1
        out[UNNAMED] += max((g1 - g0) - covered, 0.0)
    return out


def idle_shares(trace: dict) -> dict[str, float] | None:
    """{group: % of the traced span} on the first chip, plus
    `in_program`: the idle time between the ops of one program, which
    no phase of the host explains. None where the trace has no
    `sched/*` event or no program (a program older than the events, an
    untraced run)."""
    plane, ph = first_device(trace), phases(trace)
    if not plane or not ph or not plane.get(MODULES_LINE):
        return None
    span = span_ns(plane)
    if span <= 0:
        return None
    mods = plane[MODULES_LINE]
    by = idle_by_group(mods, ph)
    by["in_program"] = max(
        union_ns(mods) - union_ns(plane.get(OPS_LINE, [])), 0.0)
    return {k: 100.0 * v / span for k, v in by.items()}


def scope_share(trace: dict, *needles: str) -> float | None:
    """% of device busy time (the union of op intervals, first chip) in
    ops whose `tf_op` holds every needle. None where no op carries a
    `tf_op` with the first needle: the program has no such scope."""
    plane = first_device(trace)
    ops = plane.get(OPS_LINE, []) if plane else []
    if not any(e[3] and needles[0] in e[3] for e in ops):
        return None
    busy = union_ns(ops)
    hit = [e for e in ops if e[3] and all(n in e[3] for n in needles)]
    return 100.0 * union_ns(hit) / busy if busy > 0 else None


def trace_of(ctx: dict) -> dict | None:
    """The run's trace, read once and kept in the run's context; None
    for an untraced run or a trace that was not written."""
    if "_hostplane" not in ctx:
        path = ctx.get("trace_dir") and xplane.find_xplane(ctx["trace_dir"])
        ctx["_hostplane"] = load(path) if path else None
    return ctx["_hostplane"]


def idle_share_of(ctx: dict, group: str) -> float | None:
    """One of the run's `idle_shares`, for the metric readers."""
    trace = trace_of(ctx)
    shares = idle_shares(trace) if trace else None
    return shares[group] if shares else None
