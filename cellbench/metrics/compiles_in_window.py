"""Scheduler: programs JAX compiled (or loaded from the cache) inside the
window, from `jax_log_compiles` lines stamped there. 0 when the warm-up
covered every dispatch shape the window met."""


def read(ctx):
    return ctx["compile_log"].count_between(*ctx["window_abs"])
