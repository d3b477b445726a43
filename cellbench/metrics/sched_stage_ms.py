"""Scheduler: median `stage_ms` of the flight records (`/stats`) in the
untraced part of the window: the part of `build` that hands the plan's
launch-stable arrays to the device, one transfer an array
(`plan_h2d_mean` counts them); `sched_build_ms` less this is the plan's
numpy work. Nothing to read on a program whose records have no such
field."""
import statistics

from cellbench import serve


def read(ctx):
    ms = [r["stage_ms"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if "stage_ms" in r]
    return statistics.median(ms) if ms else None
