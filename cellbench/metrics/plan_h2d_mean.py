"""Scheduler: mean `plan_h2d` of the flight records (`/stats`) in the
untraced part of the window: host arrays the step's planning handed to
the device, one transfer each, a busy iteration (a mixed plan stages
more than a decode-only one, so the mean follows the mix). Nothing to
read on a program whose records have no such field."""
import statistics

from cellbench import serve


def read(ctx):
    ns = [r["plan_h2d"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if "plan_h2d" in r]
    return statistics.fmean(ns) if ns else None
