"""Model: % of the window's busy iterations whose program's expert
calls took the sorted dispatch (the flight records' `grouped`,
`/stats`): every expert's rows on row tiles of their own through one
grouped matmul a weight (`moe._moe_grouped`). The rest are programs
whose calls stay under the threshold (a decode round's rows alone, a
short chunk beside them) and keep the dense dispatch. Nothing to read
on a program whose records have no such field."""
from cellbench import serve


def read(ctx):
    recs = [r["grouped"] for r in serve.flight_in(ctx, *ctx["window_abs"])
            if "grouped" in r]
    return 100.0 * sum(recs) / len(recs) if recs else None
