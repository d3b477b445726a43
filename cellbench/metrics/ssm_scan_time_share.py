"""Kernels: device time of the mixer's part that is no matrix product
with weights, the convolution and the scan (ops whose `tf_op` lies under
`/ssm/scan/` or `/ssm/conv/`: the chunked scan of chunk rows, the
one-token update of decode rows, and the gathers and scatters of the rows'
states), over device busy time, traced span. Nothing to read where the
program has no such scope."""
from cellbench import hostplane


def read(ctx):
    trace = hostplane.trace_of(ctx)
    plane = hostplane.first_device(trace) if trace else None
    ops = plane.get(hostplane.OPS_LINE, []) if plane else []
    hit = [e for e in ops if e[3] and ("/ssm/scan/" in e[3]
                                       or "/ssm/conv/" in e[3])]
    busy = hostplane.union_ns(ops)
    return 100.0 * hostplane.union_ns(hit) / busy if hit and busy > 0 \
        else None
