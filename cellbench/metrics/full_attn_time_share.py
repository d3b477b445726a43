"""Kernels: device time of the full layers' cache write and paged kernel
in a model that also has window layers (ops whose `tf_op` lies under
`/attn/full/`) over device busy time, traced span
(`hostplane.scope_share`). A model of one kind of layer has no such
scope and nothing to read: `paged_attn_time_share` reads its kernels."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/attn/full/") if trace else None
