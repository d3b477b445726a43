"""Scheduler: median `between_ms` of the flight records (`/stats`) in
the untraced part of the window: what passes between one busy step's
closing stamp and the next step's opening one, in no phase: the loop's
yield, under which the streaming threads write their lines, the step
lock, the next step's preamble. With a record's `duration_ms` it is the
scheduler's period. Nothing to read on a program whose records have no
such field."""
import statistics

from cellbench import serve


def read(ctx):
    ms = [r["between_ms"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if "between_ms" in r]
    return statistics.median(ms) if ms else None
