"""Scheduler: % of the overlapped busy iterations in the untraced part
of the window whose flight record (`/stats`) says `host_late`: the
program had already finished when the step came for its results, so the
device stood idle for the host in that iteration. Nothing to read on a
program whose records have no such field."""
from cellbench import serve


def read(ctx):
    late = [r["host_late"]
            for r in serve.flight_in(ctx, *serve.untraced_span(ctx))
            if r.get("overlap") and "host_late" in r]
    return 100.0 * sum(late) / len(late) if late else None
