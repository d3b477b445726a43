"""Scheduler: the serialized host share of the scheduler's iterations,
sum of `host_ms` over sum of `duration_ms` of the flight records
(`/stats`) in the untraced part of the window. A host-clock share of the
scheduler's loop, not a device idle share."""
from cellbench import serve


def read(ctx):
    recs = [r for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
            if "host_ms" in r and r.get("duration_ms", 0) > 0]
    if not recs:
        return None
    return 100.0 * sum(r["host_ms"] for r in recs) / sum(
        r["duration_ms"] for r in recs)
