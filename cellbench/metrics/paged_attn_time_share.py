"""Kernels: device time of the paged-attention kernels (op names that
hold `paged_attention`) over device busy time, traced span (xplane)."""
from cellbench import serve, xplane


def read(ctx):
    plane = serve.first_plane(ctx)
    if not plane:
        return None
    ops = plane.get(xplane.OPS_LINE, [])
    busy = xplane.union_seconds(ops)
    if busy <= 0:
        return None
    return 100.0 * xplane.matching_seconds(ops, ["paged_attention"]) / busy
