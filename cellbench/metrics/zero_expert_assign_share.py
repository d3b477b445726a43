"""Model: of the window's router assignments (tokens x experts a token x
expert layers, the flight records' `assign_held`, `assign_zero` and
`assign_absent`), the share that chose an expert that computes nothing
(about a third with an even router: 256 of 768 columns). Nothing to read
where the program records no such counts."""
from cellbench import serve


def read(ctx):
    recs = [r for r in serve.flight_in(ctx, *ctx["window_abs"])
            if "assign_zero" in r]
    n = sum(r["assign_held"] + r["assign_zero"] + r["assign_absent"]
            for r in recs)
    return 100.0 * sum(r["assign_zero"] for r in recs) / n if n else None
