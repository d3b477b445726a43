"""Model: device time of the expert matmuls that took `moe_mlp`'s sorted,
dropless dispatch (ops whose `tf_op` lies under `moe_experts/grouped`:
the grouped matmul kernels and the activation between them) over device
busy time, traced span (`hostplane.scope_share`). Beside
`moe_chunk_expert_share` it says how much of the chunks' expert time
took that path; a program without the scope has nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(
        trace, "/moe_experts/grouped/") if trace else None
