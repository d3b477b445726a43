"""Model: device time of the expert matmuls and activation that a mixed
step's one walk of the layers runs over chunk tokens and decode rows
together (ops whose `tf_op` lies under `joined_walk/` and `moe_experts`)
over device busy time, traced span (`hostplane.scope_share`). Beside it
`moe_chunk_expert_share` and `moe_decode_expert_share` read what is left
to programs that walk each half alone; a program without the scope has
nothing to read."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    return hostplane.scope_share(
        trace, "/joined_walk/", "/moe_experts/") if trace else None
