"""Scheduler: median `phases_ms["commit"]` of the flight records
(`/stats`) in the untraced part of the window: one half of the serialized
tail that `sched_host_share` sums."""
import statistics

from cellbench import serve


def read(ctx):
    ms = [r["phases_ms"]["commit"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if "commit" in r.get("phases_ms", {})]
    return statistics.median(ms) if ms else None
