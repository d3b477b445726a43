"""Device programs: median device duration of the `_mixed_step`
program's executions in the traced span (xplane, `XLA Modules` line)."""
from cellbench import serve, xplane


def read(ctx):
    plane = serve.first_plane(ctx)
    return xplane.module_median_ms(plane, "_mixed_step") if plane else None
