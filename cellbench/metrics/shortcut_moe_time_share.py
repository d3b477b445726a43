"""Model: device time of the shortcut experts, all of `s`: router,
dispatch, the held experts, the identity term and the combine (ops whose
`tf_op` lies under `/moe_shortcut/`) over device busy time, traced span
(`hostplane.scope_share`). Nothing to read on a program without that
scope."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/moe_shortcut/") if trace else None
