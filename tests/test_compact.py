"""Row-compaction primitive (ops/compact.py).

Covers: plan_compaction's destinations/positions, the XLA fallback's
exact-packing contract, width-independence (F=200 Bosch shape), the
end-to-end compacted-histogram equivalence, the Pallas kernel in
interpret mode against the NumPy oracle (the destination-group loop at
keep shares 0 to 1, empty and full blocks side by side, the tail past
the stream's end) and — in TPU mode (LGBM_TPU_TESTS=1) — Pallas-vs-XLA
equality on the chip.
"""
import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops.compact import (compact_rows, compact_rows_xla,
                                      compaction_out_cols,
                                      plan_compaction)

TPU_MODE = os.environ.get("LGBM_TPU_TESTS", "") == "1"


def _reference_compact(bins_t, vals_t, mask, out_cols):
    """NumPy oracle: exact contiguous left-pack of kept columns."""
    sel = np.nonzero(mask)[0]
    ob = np.zeros((bins_t.shape[0], out_cols), bins_t.dtype)
    ov = np.zeros((vals_t.shape[0], out_cols), np.float32)
    ob[:, :len(sel)] = bins_t[:, sel]
    ov[:, :len(sel)] = vals_t[:, sel]
    return ob, ov


def _mk(n, F, C, frac, seed=0, R=256, multiple=256):
    rng = np.random.default_rng(seed)
    bins_t = rng.integers(0, 256, size=(F, n)).astype(np.uint8) \
        .astype(np.int8)
    vals_t = rng.normal(size=(C, n)).astype(np.float32)
    mask = rng.uniform(size=n) < frac
    out_cols = compaction_out_cols(int(mask.sum()), R, multiple)
    return bins_t, vals_t, mask, out_cols


@pytest.mark.parametrize("frac", [0.0, 0.3, 1.0])
def test_plan_compaction(frac):
    n, R = 2048, 256
    rng = np.random.default_rng(1)
    mask = rng.uniform(size=n) < frac
    out_cols = compaction_out_cols(int(mask.sum()), R, 256)
    dest, algn, rem, nch = jax.tree.map(np.asarray, plan_compaction(
        jnp.asarray(mask), R, out_cols))
    stream = 0
    for b in range(n // R):
        blk = mask[b * R:(b + 1) * R]
        assert nch[b] == -(-(rem[b] + int(blk.sum())) // 128)
        assert algn[b] * 128 + rem[b] == min(
            stream, (out_cols - R - 128) // 128 * 128 + rem[b])
        assert algn[b] == min(stream // 128,
                              (out_cols - R - 128) // 128)
        stream += int(blk.sum())
        expect = np.where(blk, np.cumsum(blk) - 1, -1)
        np.testing.assert_array_equal(dest[b * R:(b + 1) * R], expect)
    assert stream + R + 128 <= out_cols + R  # out_cols bound holds


@pytest.mark.parametrize("n,F,C,frac,R", [
    (2048, 28, 3, 0.3, 256),
    (2048, 200, 4, 0.25, 256),     # Bosch width: beyond the old sort gate
    (1024, 7, 3, 0.0, 128),        # nothing kept
    (1024, 7, 3, 1.0, 128),        # everything kept
])
def test_xla_compact_matches_oracle(n, F, C, frac, R):
    bins_t, vals_t, mask, out_cols = _mk(n, F, C, frac, R=R)
    dest, algn, rem, _ = plan_compaction(jnp.asarray(mask), R, out_cols)
    ob, ov = compact_rows_xla(jnp.asarray(bins_t), jnp.asarray(vals_t),
                              dest, algn, rem, out_cols=out_cols,
                              rows_per_block=R)
    eb, ev = _reference_compact(bins_t, vals_t, mask, out_cols)
    np.testing.assert_array_equal(np.asarray(ob), eb)
    np.testing.assert_array_equal(np.asarray(ov), ev)


def test_uint16_bins_supported_off_tpu():
    """The XLA fallback compacts uint16 binned matrices (max_bin>256),
    which the sort path used to cover — dtype-generic contract."""
    n, R = 1024, 128
    rng = np.random.default_rng(5)
    bins_t = rng.integers(0, 1000, size=(5, n)).astype(np.uint16)
    vals_t = rng.normal(size=(3, n)).astype(np.float32)
    mask = rng.uniform(size=n) < 0.5
    out_cols = compaction_out_cols(int(mask.sum()), R, 128)
    dest, algn, rem, _ = plan_compaction(jnp.asarray(mask), R, out_cols)
    ob, _ = compact_rows_xla(jnp.asarray(bins_t), jnp.asarray(vals_t),
                             dest, algn, rem, out_cols=out_cols,
                             rows_per_block=R)
    eb, _ = _reference_compact(bins_t, vals_t, mask, out_cols)
    np.testing.assert_array_equal(np.asarray(ob), eb)


def test_compacted_histogram_equals_masked():
    """The compaction contract end-to-end: histogramming the compacted
    buffer (kept rows' leaf ids riding as a +1 channel) reproduces the
    masked full-scan histogram of the kept rows exactly."""
    from lightgbm_tpu.ops.pallas_histogram import multi_leaf_histogram_xla
    n, F, R, B = 2048, 6, 256, 16
    rng = np.random.default_rng(3)
    bins = rng.integers(0, B, size=(n, F)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    leaf = rng.integers(0, 4, size=n).astype(np.int32)
    mask = rng.uniform(size=n) < 0.4
    small = jnp.asarray([0, 2], jnp.int32)

    vals = np.stack([g * mask, h * mask, mask.astype(np.float32)], 1)
    ref = multi_leaf_histogram_xla(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf), small,
        num_bins=B, rows_per_block=R)

    out_cols = compaction_out_cols(int(mask.sum()), R, 256)
    vals_t = np.stack([g, h, np.ones(n, np.float32),
                       (leaf + 1).astype(np.float32)])
    dest, algn, rem, _ = plan_compaction(jnp.asarray(mask), R, out_cols)
    ob, ov = compact_rows_xla(
        jnp.asarray(bins.astype(np.int8)).T, jnp.asarray(vals_t),
        dest, algn, rem, out_cols=out_cols, rows_per_block=R)
    leaf_c = (np.asarray(ov[3]) - 1).astype(np.int32)   # tail -> -1
    vals_c = np.array(ov[:3]).T
    got = multi_leaf_histogram_xla(
        jnp.asarray(np.asarray(ob).astype(np.uint8)).T,
        jnp.asarray(vals_c), jnp.asarray(leaf_c), small,
        num_bins=B, rows_per_block=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def _wide_vals(rng, C, n):
    """float32 values over 40 binades: what a re-rounding move loses."""
    return (rng.normal(size=(C, n))
            * 10.0 ** rng.integers(-20, 20, size=(C, n))).astype(np.float32)


def _interpret_compact(bins_t, vals_t, mask, R=1024):
    """``compact_rows`` and the plan it ran on, as NumPy: in interpret
    mode on the CPU (uninitialized memory reads NaN there), compiled in
    TPU mode (on a TPU the interpreter's XLA ops drop the f32 -> bf16 ->
    f32 round trips of the significand split, which Mosaic keeps)."""
    out_cols = compaction_out_cols(int(mask.sum()), R, 1024)
    plan = plan_compaction(jnp.asarray(mask), R, out_cols)
    with (contextlib.nullcontext() if TPU_MODE
          else pltpu.force_tpu_interpret_mode()):
        ob, ov = compact_rows(jnp.asarray(bins_t), jnp.asarray(vals_t),
                              *plan, out_cols=out_cols, rows_per_block=R)
    return (np.asarray(ob), np.asarray(ov), out_cols,
            *(np.asarray(p) for p in plan))


def _block_masks(n, R):
    """Masks that put the kernel's corner cases side by side."""
    blocks = np.arange(n) // R
    # group counts 0 and 9 next to each other, and a partial group (rem
    # = 1019 % 128) carried across a block that keeps nothing
    alternate = blocks % 2 == 0
    alternate[:5] = False
    # the last block keeps 3 rows after a full one: its window's other
    # eight groups lie past the stream's end
    last3 = blocks == n // R - 2
    last3[[n - R + 7, n - R + 500, n - 1]] = True
    # nothing kept until the last block
    late = blocks == n // R - 1
    return {"alternate": alternate, "last3": last3, "late": late}


@pytest.mark.parametrize("F,C", [(13, 4), (39, 4)])
@pytest.mark.parametrize("frac", [0.0, 0.05, 0.3, 1.0])
def test_pallas_interpret_matches_oracle(F, C, frac):
    """The kernel's body on the CPU, at the benchmark cells' widths: both
    outputs bit-equal to the exact left-pack, whatever share of a
    block's destination groups the mask fills."""
    n = 8192
    seed = int(frac * 100) + F
    bins_t, _, mask, _ = _mk(n, F, C, frac, seed=seed)
    vals_t = _wide_vals(np.random.default_rng(seed), C, n)
    ob, ov, out_cols, *_ = _interpret_compact(bins_t, vals_t, mask)
    eb, ev = _reference_compact(bins_t, vals_t, mask, out_cols)
    np.testing.assert_array_equal(ob, eb)
    np.testing.assert_array_equal(ov, ev)


@pytest.mark.parametrize("case", ["alternate", "last3", "late"])
def test_pallas_interpret_block_corners(case):
    """Empty and full blocks side by side, and a window that ends far
    past the stream: the groups no block filled read zero, ``nch`` is
    the plan's own ``ceil((rem + kept) / 128)``."""
    n, R, F, C = 8192, 1024, 13, 4
    rng = np.random.default_rng(11)
    bins_t = rng.integers(1, 256, size=(F, n)).astype(np.uint8) \
        .astype(np.int8)
    vals_t = _wide_vals(rng, C, n)
    mask = _block_masks(n, R)[case]
    ob, ov, out_cols, _, algn, rem, nch = _interpret_compact(
        bins_t, vals_t, mask, R)
    eb, ev = _reference_compact(bins_t, vals_t, mask, out_cols)
    np.testing.assert_array_equal(ob, eb)
    np.testing.assert_array_equal(ov, ev)
    kept = int(mask.sum())
    assert not ob[:, kept:].any() and not ov[:, kept:].any()
    cnt = mask.reshape(-1, R).sum(axis=1)
    np.testing.assert_array_equal(nch, -(-(rem + cnt) // 128))
    if case == "alternate":
        assert set(nch[:4]) == {8, 1, 9} and rem[1] == 1019 % 128


@pytest.mark.skipif(not TPU_MODE, reason="Pallas kernel needs the TPU")
@pytest.mark.parametrize("n,F,C,frac", [
    (8192, 28, 3, 0.3),
    (8192, 200, 4, 0.25),
    (8192, 28, 3, 0.0),
    (8192, 28, 3, 1.0),
    (8192, 13, 4, 0.05),
    (8192, 39, 4, 0.3),
])
def test_pallas_matches_xla(n, F, C, frac):
    R = 1024
    # arbitrary f32 values: the kernel's bf16x3 significand-split moves
    # them BIT-EXACTLY, comparable with the f32 XLA fallback
    bins_t, vals_t, mask, out_cols = _mk(n, F, C, frac, R=R,
                                         multiple=1024)
    dest, algn, rem, nch = plan_compaction(jnp.asarray(mask), R, out_cols)
    args = (jnp.asarray(bins_t), jnp.asarray(vals_t), dest, algn, rem)
    ob, ov = compact_rows(*args, nch, out_cols=out_cols, rows_per_block=R)
    eb, ev = compact_rows_xla(*args, out_cols=out_cols,
                              rows_per_block=R)
    np.testing.assert_array_equal(np.asarray(ob), np.asarray(eb))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(ev))
