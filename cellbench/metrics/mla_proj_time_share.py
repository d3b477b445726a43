"""Model: device time of latent attention's low-rank projections and its
two absorptions (ops whose `tf_op` lies under `/mla_proj/`) over device
busy time, traced span (`hostplane.scope_share`). Nothing to read on a
program without that scope."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/mla_proj/") if trace else None
