"""Kernels: the rows the experts' way in computes over the rows a token
owns. Over the window's flight records of joined steps (the ones whose
expert calls sort: `sorted_experts_roofline_share` reads the same),
`assign_rows_computed` (each expert's assignments of each expert layer
rounded up to the kernel's sub-tiles, `ops/grouped_matmul.py`) over
`assign_total`: 1 is a kernel that multiplies no row without an
assignment, and whole 256-row tiles under this cell's router would read
about 2. Nothing to read where the program records no such count."""
from cellbench import serve


def read(ctx):
    recs = [r for r in serve.flight_in(ctx, *ctx["window_abs"])
            if r.get("joined") and r.get("assign_total")
            and "assign_rows_computed" in r]
    if not recs:
        return None
    return (sum(r["assign_rows_computed"] for r in recs)
            / sum(r["assign_total"] for r in recs))
