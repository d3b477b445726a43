"""Cache manager: the bytes of the per-slot states of a model with a mixer
(`/stats`, `cache.pool.ssm_state_bytes`: a recurrent state and the
convolution's held inputs for every slot and layer, held whether the slot
is or not) over the device's memory (`memory_stats()["bytes_limit"]`).
Nothing to read where the program reports no such pool."""


def read(ctx):
    held = ctx["stats"].get("cache", {}).get("pool", {}).get(
        "ssm_state_bytes")
    limit = ctx["mem"].get("bytes_limit")
    return 100.0 * held / limit if held and limit else None
