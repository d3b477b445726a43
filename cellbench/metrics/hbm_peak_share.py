"""Device: `memory_stats()["peak_bytes_in_use"]` after the window over
`bytes_limit`."""


def read(ctx):
    mem = ctx["mem"]
    if not mem.get("bytes_limit"):
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
