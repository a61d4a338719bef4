"""The histogram kernel's static one-hot layout (ops/pallas_histogram.py).

``onehot_layout`` decides, from the per-column bin counts alone, which
rows the kernel's one-hot and accumulator hold; ``dense_histograms``
carries the accumulator back to the dense ``[K, F, B, C]`` the grower
reads. Both are plain functions of static numbers, so they are held
here on the CPU; the kernel that follows them runs in interpret mode
against the XLA sums (compiled, it runs on the chip:
tests/test_multi_leaf_histogram.py).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops.pallas_histogram import (
    ONE_BLOCK_ROWS, ROW_TILE, WINDOW_ROWS, dense_histograms,
    multi_leaf_histogram, multi_leaf_histogram_xla, onehot_layout)

# the two benchmark cells' columns, from their bin_reference tables
# (Dataset.construct() on benchmark/cells/*.json's 200,000 rows)
AIRLINE = (22, 12, 31, 7, 256, 256, 30, 256, 255, 223, 229, 256, 3)
CRITEO = ((104, 256, 256, 171, 256, 256, 256, 251, 256, 16, 68, 37, 249)
          + (255,) * 18 + (24, 4, 27, 11, 5, 19, 16, 100))

CASES = {
    # name: (col_bins, num_bins, one-hot rows a column scanned)
    "airline": (AIRLINE, 256, 1952),
    "criteo": (CRITEO, 256, 7520),
    "all_full": ((256,) * 13, 256, 13 * 256),
    # shard-width padding columns hold bin 0 alone
    "one_bin_padding": ((200, 64, 256, 1, 1, 1), 256, 224 + 64 + 256 + 96),
    # more rows than one block holds: windows of 16 columns, a
    # position takes the largest count of its three windows
    "forty_columns": (((256,) * 12 + (40,) * 4) * 2 + (256,) * 8, 256,
                      3 * (12 * 256 + 4 * 64)),
    "forty_full": ((256,) * 40, 256, 3 * 4096),
    # ROW_TILE rounds past a narrow histogram's width
    "narrow_hist": ((7, 40, 3, 1), 40, 32 + 64 + 32 + 32),
}


def _row_of(lay, f, b):
    p = f % lay.f_blk
    return (f // lay.f_blk) * lay.block_rows + lay.offsets[p] + b


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_rows_scatter_and_kernel(case):
    col_bins, B, want_rows = CASES[case]
    F = len(col_bins)
    lay = onehot_layout(col_bins, B)

    # rows: whole sublane tiles, within the block budget, and what the
    # engine's hist.onehot_elems counts for a column scanned
    assert all(r % ROW_TILE == 0 and r > 0 for r in lay.rows)
    assert len(lay.rows) == lay.f_blk and lay.n_fb * lay.f_blk >= F
    assert lay.block_rows <= (ONE_BLOCK_ROWS if lay.n_fb == 1
                              else WINDOW_ROWS)
    assert lay.onehot_rows == lay.n_fb * lay.block_rows == want_rows
    assert lay.onehot_rows <= -(-F // lay.f_blk) * lay.f_blk * \
        -(-B // ROW_TILE) * ROW_TILE          # never more than dense

    # every (column, bin) the column has owns exactly one row
    owner = {}
    for f, nb in enumerate(col_bins):
        for b in range(min(nb, B)):
            assert b < lay.rows[f % lay.f_blk]
            row = _row_of(lay, f, b)
            assert row not in owner and 0 <= row < lay.onehot_rows
            owner[row] = (f, b)

    # the static scatter: a synthetic accumulator whose entry is its
    # own (row, lane) number lands at [k, f, b, c], zeros elsewhere
    # (a row no bin owns, the rounding up to ROW_TILE, meets no data)
    K, C = 3, 2
    acc = (np.arange(lay.onehot_rows * C * K, dtype=np.float32) + 1) \
        .reshape(lay.onehot_rows, C * K)
    acc[[r for r in range(lay.onehot_rows) if r not in owner]] = 0
    dense = np.asarray(dense_histograms(jnp.asarray(acc), lay, F, B, K, C))
    assert dense.shape == (K, F, B, C)
    want = np.zeros((K, F, B, C), np.float32)
    for row, (f, b) in owner.items():
        want[:, f, b, :] = acc[row].reshape(C, K).T   # lane = c * K + k
    np.testing.assert_array_equal(dense, want)

    # and the kernel builds exactly that layout: in interpret mode its
    # integer sums equal the XLA reference, bins past a count staying 0
    rng = np.random.default_rng(F)
    n = 512
    bins = np.stack([rng.integers(0, min(c, B), size=n)
                     for c in col_bins], axis=1).astype(np.uint8)
    vals = np.stack([rng.integers(-8, 9, size=n),
                     rng.integers(0, 9, size=n),
                     np.ones(n)], axis=1).astype(np.float32)
    leaf = rng.integers(0, 5, size=n).astype(np.int32)
    small = jnp.asarray(np.array([0, 3, -1, 1], np.int32))
    with pltpu.force_tpu_interpret_mode():
        h_pl = np.asarray(multi_leaf_histogram(
            jnp.asarray(bins.T.astype(np.int8)), jnp.asarray(vals.T),
            jnp.asarray(leaf), small, num_bins=B, col_bins=col_bins,
            rows_per_block=256, int_mode=True))
    h_xla = np.asarray(multi_leaf_histogram_xla(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf), small,
        num_bins=B, rows_per_block=256, precise=True))
    np.testing.assert_array_equal(h_pl, h_xla)


def test_no_counts_means_every_column_is_full():
    assert onehot_layout((64,) * 6, 64) == onehot_layout((999,) * 6, 64)
    bins = np.arange(256, dtype=np.uint8).reshape(256, 1) % 64
    args = (jnp.asarray(np.repeat(bins, 6, 1).T.astype(np.int8)),
            jnp.ones((3, 256), jnp.float32), jnp.zeros(256, jnp.int32),
            jnp.zeros(1, jnp.int32))
    with pltpu.force_tpu_interpret_mode():
        a = multi_leaf_histogram(*args, num_bins=64, rows_per_block=256)
        b = multi_leaf_histogram(*args, num_bins=64, col_bins=(64,) * 6,
                                 rows_per_block=256)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(np.asarray(a).sum()) == 256 * 6 * 3
