"""Kernels: device time of the window layers' cache write and paged
kernel (ops whose `tf_op` lies under `/attn/window/`, the scope
`paged_engine.forward_sets` puts around each row set's share of a
sliding-window layer) over device busy time, traced span
(`hostplane.scope_share`). A program without window layers has no such
scope and nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/attn/window/") if trace else None
