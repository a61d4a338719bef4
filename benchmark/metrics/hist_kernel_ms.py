"""Device time of the histogram kernel an iteration, from the trace."""
from lib import readers


def read(ctx):
    s = readers.op_seconds(ctx, readers.HIST_KERNEL)
    return None if s is None else s * 1e3 / ctx["iters_traced"]
