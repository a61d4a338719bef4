"""Device time of the row-compaction kernel an iteration, from the trace."""
from lib import readers


def read(ctx):
    s = readers.op_seconds(ctx, readers.COMPACT_KERNEL)
    return None if s is None else s * 1e3 / ctx["iters_traced"]
