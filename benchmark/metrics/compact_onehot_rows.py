"""One-hot destination rows the compaction kernel built for each block of
source rows it was handed, both counted by the program itself:
`compact.onehot_rows` over `compact.blocks` of its registry, with
`sampled=1`, the one chunk program the window runs. A source row is compared
with every destination row its block builds, and the MXU pushes as many
through, so the kernel's VPU and MXU work are both proportional to it: a
kernel that builds a block's whole write window reads the block's rows +
128; one that builds only the 128-wide destination groups the block fills
reads about 128 x (the share of rows kept x the block's rows / 128 + 1). A
count, not a speed. None where the program keeps no such counters."""


def read(ctx):
    try:
        from lightgbm_tpu import obs
        reg = obs.registry()
        rows = reg.get("compact.onehot_rows", sampled=1)
        blocks = reg.get("compact.blocks", sampled=1)
    except (ImportError, AttributeError):
        return None
    if rows is None or blocks is None or not blocks.value:
        return None
    return rows.value / blocks.value
