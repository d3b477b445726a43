"""Device: one minus the union of device-op intervals over the traced
span, averaged over the chips used (xplane)."""
from cellbench import serve, xplane


def read(ctx):
    planes = serve.trace_planes(ctx)
    if not planes:
        return None
    busy, window = xplane.device_busy(planes)
    return 100.0 * (1.0 - busy / window) if window > 0 else None
