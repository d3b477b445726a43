"""Scheduler: median `phases_ms["deliver"]` of the flight records
(`/stats`) in the untraced part of the window: the stream calls and
completions of an iteration's commit, made by the scheduler's thread
after the next program's launch and so under it, not in the serialized
tail that `sched_host_share` sums. Nothing to read on a program whose
commit wakes its clients itself."""
import statistics

from cellbench import serve


def read(ctx):
    ms = [r["phases_ms"]["deliver"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if "deliver" in r.get("phases_ms", {})]
    return statistics.median(ms) if ms else None
