"""Model: device time of the leading dense layers' MLPs (ops whose `tf_op`
lies under `lead_dense`, the scope `paged_engine.forward_sets` puts around
the MLP of a layer of the stack before the expert layers) over device busy
time, traced span (`hostplane.scope_share`). A program without leading
dense layers has no such scope and nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/lead_dense/") if trace else None
