"""Least time for the traced moves (memory-bound: every row of the
histogram source, its bins and its value channels, read once and written
once a move, at the chip's HBM peak; lib/movework.py) over the mover's
device time. The traced moves are the trace's own `partition_move*` calls,
two a move; the rows a move is handed are the program's
`partition.rows_moved` over `partition.move_calls`. None where either is
missing."""
import re

from lib import movework, readers


def read(ctx):
    rows_a_move = movework.ratio("partition.rows_moved",
                                 "partition.move_calls", sampled=0)
    seconds = readers.op_seconds(ctx, movework.MOVE_KERNEL)
    if not seconds or rows_a_move is None:
        return None
    rx = [re.compile(p) for p in movework.MOVE_KERNEL]
    calls = sum(c for name, _s, c in ctx["trace"]["ops"]
                if any(r.search(name) for r in rx))
    floor = readers.bandwidth_floor_s(ctx, movework.move_bytes(
        calls / 2.0 * rows_a_move, ctx["n_features"]))
    return 100.0 * floor / seconds
