"""Least time for the traced iterations' whole work (histogram bytes plus
one pass over the per-row state, at the chip's HBM peak: memory-bound) over
their wall time. Needs no op name, so it still bounds a gain after a later
PR takes a kernel off the path."""
from lib import readers, work


def read(ctx):
    wall = ctx.get("traced_wall_s")
    if not wall or not ctx["trees_traced"]:
        return None
    floor = readers.bandwidth_floor_s(
        ctx, work.step_bytes(ctx["trees_traced"], ctx["n_features"],
                             ctx["rows"]))
    return 100.0 * floor / wall
