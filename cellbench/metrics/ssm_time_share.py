"""Model: device time of the mixer (ops whose `tf_op` lies under `/ssm/`,
the scope `paged_engine.forward_sets` puts around the mixer's projections,
convolution, scan and gated norm of every row set) over device busy time,
traced span (`hostplane.scope_share`). A program without a mixer has no
such scope and nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/ssm/") if trace else None
