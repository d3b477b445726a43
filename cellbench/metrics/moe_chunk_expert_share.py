"""Model: device time of the prefill chunks' expert einsums and
activation at capacity (ops whose `tf_op` lies under `prefill_group/` and
`moe_experts`) over device busy time, traced span
(`hostplane.scope_share`)."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(
        trace, "/prefill_group/", "/moe_experts/") if trace else None
