"""End to end: output tokens whose line reached a client inside the
window, over the window's length. Host clock at the client."""
from cellbench import stats


def read(ctx):
    w0, w1 = ctx["window"]
    return stats.tokens_in_window(ctx["records"], w0, w1) / (w1 - w0)
