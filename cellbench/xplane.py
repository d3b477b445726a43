"""From a profiler trace to numbers.

`load(path)` reads an `.xplane.pb` with nothing but JAX
(`jax.profiler.ProfileData`) into plain lists; every reduction below
works on those lists, so it is tested on hand-made ones.

A device plane (`/device:TPU:n`) has a line of ops (`XLA Ops`) and a line
of whole programs (`XLA Modules`); an event is (name, start, duration) in
nanoseconds on the plane's own clock. Busy time is the union of the op
intervals, so nested or overlapping events are not counted twice; idle
share is one minus busy over the traced span on that plane.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys

Event = tuple[str, float, float]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> dict[str, dict[str, list[Event]]]:
    """{device plane name: {line name: [events]}} for the TPU planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: dict[str, dict[str, list[Event]]] = {}
    names = [plane.name for plane in data.planes]
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
    if not out:
        print(f"[cellbench] no TPU plane among {names}", file=sys.stderr)
    return out


def union_seconds(events: list[Event]) -> float:
    """Seconds covered by at least one event."""
    total, cur_end = 0.0, None
    cur_start = 0.0
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total / 1e9


def span_seconds(events: list[Event]) -> float:
    """First start to last end."""
    if not events:
        return 0.0
    return (max(s + d for _, s, d in events)
            - min(s for _, s, _ in events)) / 1e9


def busy_and_window(plane: dict[str, list[Event]]) -> tuple[float, float]:
    """(busy_s, window_s) of one device plane: the union of its op
    intervals, over the span from the first to the last event of its op
    and module lines."""
    ops = plane.get(OPS_LINE, [])
    both = ops + plane.get(MODULES_LINE, [])
    return union_seconds(ops), span_seconds(both)


def device_busy(planes: dict) -> tuple[float, float]:
    """busy_s and window_s averaged over the device planes."""
    pairs = [busy_and_window(p) for p in planes.values()]
    pairs = [p for p in pairs if p[1] > 0]
    if not pairs:
        return 0.0, 0.0
    return (sum(b for b, _ in pairs) / len(pairs),
            sum(w for _, w in pairs) / len(pairs))


def matching_seconds(events: list[Event], needles: list[str]) -> float:
    """Summed duration of the events whose name holds any needle."""
    return sum(d for n, _, d in events
               if any(k in n for k in needles)) / 1e9


def module_median_ms(plane: dict[str, list[Event]], needle: str):
    """Median duration, ms, of the programs whose name holds `needle`;
    None if none ran."""
    ds = [d / 1e6 for n, _, d in plane.get(MODULES_LINE, []) if needle in n]
    return statistics.median(ds) if ds else None


def op_family(name: str) -> str:
    """An op's family: its HLO name without the `%` and the trailing
    `.123` counter, and its result's type and shape. The 16 layers'
    `%paged_attention_wide.65 = bf16[64,8,4,128]{...} custom-call(...)`
    are one family, `paged_attention_wide bf16[64,8,4,128]`; a fusion is
    told from another by what it produces."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.strip().lstrip("%"))
    m = re.match(r"\(?([a-z0-9]+\[[\d,]*\])", rest.strip())
    return f"{base} {m.group(1)}" if m else base


def family_seconds(events: list[Event], k: int) -> list[list]:
    """The k op families with most summed time: [[family, seconds]]."""
    by: dict[str, float] = {}
    for n, _, d in events:
        fam = op_family(n)
        by[fam] = by.get(fam, 0.0) + d / 1e9
    return [[n, s] for n, s in
            sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def top_ops(plane: dict[str, list[Event]], k: int = 10) -> list[list]:
    return family_seconds(plane.get(OPS_LINE, []), k)


def idle_gaps(plane: dict[str, list[Event]], k: int = 10) -> list[list]:
    """The k longest gaps between programs on the device:
    [[the program that ran after the gap, seconds], ...]."""
    mods = sorted(plane.get(MODULES_LINE, []), key=lambda e: e[1])
    gaps = []
    for (_, s0, d0), (n1, s1, _) in zip(mods, mods[1:]):
        g = s1 - (s0 + d0)
        if g > 0:
            gaps.append([n1[:80], g / 1e9])
    return sorted(gaps, key=lambda e: -e[1])[:k]
