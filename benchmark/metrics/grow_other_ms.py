"""Device busy an iteration (union of op intervals) less the two kernels:
split search, partition, gradients, sampling, score update, copies."""
from lib import readers


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    kernels = sum(readers.op_seconds(ctx, p) or 0.0
                  for p in (readers.HIST_KERNEL, readers.COMPACT_KERNEL))
    return (trace["busy_s"] - kernels) * 1e3 / ctx["iters_traced"]
