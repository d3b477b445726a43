"""Model: device time of the shared expert, the always-on gated MLP beside
an expert layer's routed experts (ops whose `tf_op` lies under
`moe_shared`: its three products and the activation) over device busy
time, traced span (`hostplane.scope_share`). A program without a shared
expert has no such scope and nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/moe_shared/") if trace else None
