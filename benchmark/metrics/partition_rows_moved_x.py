"""How often the leaf-ordered partition's mover walks the table, a tree:
rows the mover was handed (the whole histogram source at every loop trip of
the grower) over the table's rows, both counted by the program itself:
`partition.rows_moved` over `partition.rows_table` of its registry, with
`sampled=0`, the unsampled chunk program. A count, not a speed. None where
the program keeps no such counters."""
from lib import movework


def read(ctx):
    return movework.ratio("partition.rows_moved", "partition.rows_table",
                          sampled=0)
