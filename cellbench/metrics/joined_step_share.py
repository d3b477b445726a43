"""Device programs: % of the window's busy iterations whose program
walked the layer stack once for its prefill group and its decode round
together (the flight records' `joined`, `/stats`). The rest are
programs of decode rounds alone and mixed steps that keep two walks
(further rounds, drafts, a capacity that can drop, a live adapter).
Nothing to read on a program whose records have no such field."""
from cellbench import serve


def read(ctx):
    recs = [r["joined"] for r in serve.flight_in(ctx, *ctx["window_abs"])
            if "joined" in r]
    return 100.0 * sum(recs) / len(recs) if recs else None
