"""Scheduler: median `phases_ms["build"]` of the overlapped flight
records (`/stats`) in the untraced part of the window: the numpy work of
the next program's plan and the staging of its arrays onto the device,
done while the program before it runs. Hidden work: it costs the loop
nothing until it outlasts that program. Nothing to read on a program
that plans nothing ahead."""
import statistics

from cellbench import serve


def read(ctx):
    ms = [r["phases_ms"]["build"]
          for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
          if r.get("overlap") and "build" in r.get("phases_ms", {})]
    return statistics.median(ms) if ms else None
