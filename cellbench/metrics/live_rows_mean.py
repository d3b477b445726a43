"""Scheduler: mean live decode rows per busy iteration over the window
(flight records' `n_live`)."""
from cellbench import serve


def read(ctx):
    recs = [r["n_live"] for r in serve.flight_in(ctx, *ctx["window_abs"])
            if "n_live" in r]
    return sum(recs) / len(recs) if recs else None
