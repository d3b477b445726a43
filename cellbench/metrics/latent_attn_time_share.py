"""Kernels: device time of the latent kind's cache write and paged kernel
(ops whose `tf_op` lies under `/attn/latent/`, the scope
`paged_engine.forward_sets` puts around each row set's share of a latent
attention block) over device busy time, traced span
(`hostplane.scope_share`). A program without a latent cache has no such
scope and nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(trace, "/attn/latent/") if trace else None
