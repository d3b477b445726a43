"""Model: device time of the expert matmuls and activation of every walk
of the layers, joined or not, sorted or dense (ops whose `tf_op` lies
under `moe_experts`) over device busy time, traced span
(`hostplane.scope_share`)."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/moe_experts/") if trace else None
