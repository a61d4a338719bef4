"""Least time for the traced trees' histogram work (memory-bound: bins of
the root's and each smaller child's rows, and their gradient pairs, at the
chip's HBM peak) over the histogram kernel's device time."""
from lib import readers, work


def read(ctx):
    s = readers.op_seconds(ctx, readers.HIST_KERNEL)
    if not s or not ctx["trees_traced"]:
        return None
    floor = readers.bandwidth_floor_s(
        ctx, work.hist_bytes(ctx["trees_traced"], ctx["n_features"]))
    return 100.0 * floor / s
