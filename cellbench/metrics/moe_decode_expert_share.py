"""Model: device time of the decode rows' expert einsums and activation
(ops whose `tf_op` lies under `decode_rounds/` and `moe_experts`) over
device busy time, traced span (`hostplane.scope_share`)."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(
        trace, "/decode_rounds/", "/moe_experts/") if trace else None
