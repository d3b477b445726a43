"""Scheduler: how much slower the loop ran while the profiler was on:
median `duration_ms` of the flight records (`/stats`) closed inside the
traced span over the median of those closed in the untraced part of the
window. Every `device_trace` metric of the run describes a loop this
many times slower than the one the end-to-end metrics were taken from."""
import statistics

from cellbench import serve


def _median_ms(ctx, lo, hi):
    ds = [r["duration_ms"] for r in serve.flight_in(ctx, lo, hi)
          if r.get("duration_ms", 0) > 0]
    return statistics.median(ds) if ds else None


def read(ctx):
    if not ctx.get("trace_span"):
        return None
    traced = _median_ms(ctx, *ctx["trace_span"])
    untraced = _median_ms(ctx, *serve.untraced_span(ctx))
    return traced / untraced if traced and untraced else None
