"""Device time of the leaf-ordered partition's mover an iteration, from the
trace: self time of the `partition_move*` events (two kernel passes a move,
one move a loop trip of the grower). None where the trace holds no such op
(a program whose mover has no name of its own, or is not on the path)."""
from lib import movework, readers


def read(ctx):
    s = readers.op_seconds(ctx, movework.MOVE_KERNEL)
    return None if s is None else s * 1e3 / ctx["iters_traced"]
