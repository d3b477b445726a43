"""Cache manager: the most pages active at once in the window over the
pool's size (flight records' `pool_active`, `--num-pages`)."""
from cellbench import serve


def read(ctx):
    recs = [r["pool_active"] for r in serve.flight_in(
        ctx, *ctx["window_abs"]) if "pool_active" in r]
    return 100.0 * max(recs) / ctx["num_pages"] if recs else None
