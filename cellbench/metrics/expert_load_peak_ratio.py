"""Model: how far the fullest expert's rows lie over the even share. Over
the window's flight records, `assign_peak` (the most assignments any one
expert of any layer received in a walk) over `assign_total` / (experts x
expert layers), what every expert receives from an even router: 1 is even,
and an expert at 1.9 times the share of 132 rows of a 2,112-token step
fills a 256-row tile. Nothing to read where the program records no such
counts."""
from cellbench import serve


def read(ctx):
    recs = [r for r in serve.flight_in(ctx, *ctx["window_abs"])
            if r.get("assign_total")]
    if not recs:
        return None
    cfg = ctx["config"]
    cells = cfg["num_experts"] * (cfg["num_hidden_layers"]
                                  - cfg["num_dense_layers"])
    return (sum(r["assign_peak"] for r in recs) * cells
            / sum(r["assign_total"] for r in recs))
