"""End to end: gaps between successive token lines of one request at the
client, pooled over requests, 95th percentile. Host clock at the client."""
from cellbench import stats


def read(ctx):
    w0, w1 = ctx["window"]
    gaps = stats.gap_samples(ctx["records"], w0, w1)
    return stats.pct(gaps, 0.95) * 1e3 if gaps else None
