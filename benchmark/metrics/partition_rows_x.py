"""How often the table is walked to give its rows their leaves: rows the
grower's row -> leaf passes were handed (the table's and the compact
buffer's, at every loop trip and once after the loop) over the table's rows
a tree, both counted by the program itself: `partition.rows_routed` over
`partition.rows_table` of its registry, with `sampled=1`, the one chunk
program the window runs. Routing the table and the buffer at each of a
tree's 8 to 10 loop trips reads 10 to 13; the buffer (0.3 of the table) a
trip and the table once a tree reads 3.4 to 4. A count, not a speed. None
where the program keeps no such counters."""


def read(ctx):
    try:
        from lightgbm_tpu import obs
        reg = obs.registry()
        routed = reg.get("partition.rows_routed", sampled=1)
        table = reg.get("partition.rows_table", sampled=1)
    except (ImportError, AttributeError):
        return None
    if routed is None or table is None or not table.value:
        return None
    return routed.value / table.value
