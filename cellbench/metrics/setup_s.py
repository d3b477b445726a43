"""End to end: process start to the window's start: JAX start, weights,
server, warm-up of the cell's dispatch shapes, pre-roll. Host clock."""


def read(ctx):
    return ctx["setup_s"]
