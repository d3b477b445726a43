"""Model: of the window's router assignments (the flight records'
`assign_held`, `assign_zero` and `assign_absent`), the share that chose
an expert this chip holds (about 2.1 with an even router: 16 of 768
columns). Nothing to read where the program records no such counts."""
from cellbench import serve


def read(ctx):
    recs = [r for r in serve.flight_in(ctx, *ctx["window_abs"])
            if "assign_held" in r]
    n = sum(r["assign_held"] + r["assign_zero"] + r["assign_absent"]
            for r in recs)
    return 100.0 * sum(r["assign_held"] for r in recs) / n if n else None
