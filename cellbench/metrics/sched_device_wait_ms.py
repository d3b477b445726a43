"""Scheduler: median `device_wait_ms` of the overlapped flight records
(`/stats`) in the untraced part of the window: how long the step waited
for the program it came to commit, that is the room the host's hidden
work left under the program. Higher is better: at 0 the host sets the
pace (`host_late_iter_share`). Nothing to read on a program that
launches nothing ahead."""
import statistics

from cellbench import serve


def read(ctx):
    ms = [r["device_wait_ms"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if r.get("overlap") and "device_wait_ms" in r]
    return statistics.median(ms) if ms else None
