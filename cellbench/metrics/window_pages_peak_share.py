"""Cache manager: the most pages of the window kind's pool held at once
in the window over that pool's size (flight records'
`window_pool_active` and `window_num_pages`; the program sizes the
pool). A server without a window pool records neither."""
from cellbench import serve


def read(ctx):
    recs = [r for r in serve.flight_in(ctx, *ctx["window_abs"])
            if r.get("window_num_pages")]
    if not recs:
        return None
    return 100.0 * max(r["window_pool_active"] / r["window_num_pages"]
                       for r in recs)
