"""Share of the loop trips under the leaf-ordered partition whose elected
children listed more row blocks than the block table holds and took the
masked scan over the whole partition instead: the program's own
`partition.full_scans` over `partition.move_calls` (one move a trip), with
`sampled=0`, the unsampled chunk program. None where the program keeps no
such counters."""
from lib import movework


def read(ctx):
    share = movework.ratio("partition.full_scans", "partition.move_calls",
                           sampled=0)
    return None if share is None else 100.0 * share
