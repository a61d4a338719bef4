"""Pallas TPU kernel: fused multi-leaf histogram construction.

Reference: the CUDA histogram kernel
(src/treelearner/cuda/cuda_histogram_constructor.cu, UNVERIFIED — empty
mount, see SURVEY.md banner) builds per-leaf histograms with shared-memory
atomic adds. TPUs have no fast scatter-atomics; the MXU formulation is

    hist[k, f, b, c] = sum_r [bin(r,f) == b] * [leaf(r) == small_k] * vals[r, c]

One grid step processes a row block, a lane chunk at a time: the bin
one-hot ``[rows, chunk]`` is generated in VMEM (never staged through HBM —
the failure mode of the XLA einsum formulation) and contracted on the MXU
in ONE ``[rows, chunk] x [chunk, C*K]`` matmul. ``rows`` follows the static
per-column bin counts (``onehot_layout``): a column of 22 bins owns 32
one-hot rows, not ``num_bins``, and a bin no column has costs nothing.

The K axis is the TPU-specific trick: packing K candidate leaves' masks
into the matmul N dimension amortizes the MXU's 128-wide N padding, so one
data scan yields K leaf histograms (K*C ≈ 128 → negligible padding waste).
The batched tree grower (learner/serial.py) exploits this by expanding the
top-K leaves per round.

A call has two limits, both proportional to the one-hot rows: the VPU's
compare-and-convert of every one-hot element (int32 compares; int8/bf16
vector compares are unsupported by this target) and the MXU's
``rows x 128 x columns`` at 393 TOP/s int8 (197 TFLOP/s bf16). PERF.md §6
PR 30 has the measured calls against both.
"""
from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hist_kernel(bins_ref, vals_ref, leaf_ref, small_ref, out_ref, *,
                 rows: Tuple[int, ...], n_chan: int, chunk: int,
                 int_mode: bool = False):
    i = pl.program_id(1)      # row-block index (feature block is dim 0)
    small = small_ref[...]                               # [K, 1]
    # int_mode (use_quantized_grad): grad/hess are small integer levels,
    # so the contraction rides the MXU's 2x-rate int8 path with EXACT
    # int32 accumulation (the reference's integer-histogram design,
    # cuda_gradient_discretizer.cu; measured 1.25x/scan on v5e)
    oh_t = jnp.int8 if int_mode else jnp.bfloat16

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def scan(c, carry):
        """One lane chunk of the row block. The loop keeps the unrolled
        program (and its compile time) at one chunk's size whatever the
        row block, and the live one-hot at ``[sum(rows), chunk]``."""
        sl = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        # bins stored int8 to halve HBM traffic; wrapped values are
        # restored with & 0xFF after widening (cheap at [F, chunk])
        bins_blk = bins_ref[:, sl].astype(jnp.int32) & 0xFF   # [F, chunk]
        vals_blk = vals_ref[:, sl]                            # [C, chunk]
        if int_mode:
            vals_blk = vals_blk.astype(jnp.int32)
        in_leaf = leaf_ref[:, sl] == small                    # [K, chunk]
        # leaf-masked values, CHANNEL-major ([C*K, chunk], lane c*K+k of
        # the accumulator): each channel is one select of a sublane
        # broadcast, and the K-row pieces stack on tile boundaries (a
        # [K, C, chunk] product reshaped to [K*C, chunk] shuffles every
        # sublane: it cost as much as 2,000 one-hot rows)
        rhs = jnp.concatenate(
            [jnp.where(in_leaf, vals_blk[ch:ch + 1, :], 0).astype(oh_t)
             for ch in range(n_chan)], axis=0)

        # [sum(rows), chunk] one-hot, column by column: position p of
        # the block owns rows[p] rows (its bin count rounded up to
        # ROW_TILE, so every piece starts on an int8 sublane tile), each
        # a sublane broadcast of its row of bins against an iota. A bin
        # no column of that position has gets no row: it costs neither
        # the VPU nor the MXU anything.
        pieces = []
        for p, r in enumerate(rows):
            iota_b = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
            pieces.append((bins_blk[p:p + 1, :] == iota_b).astype(oh_t))
        onehot = jnp.concatenate(pieces, axis=0)

        out_ref[...] += jax.lax.dot_general(
            onehot, rhs, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=(jnp.int32 if int_mode
                                    else jnp.float32))  # [sum(rows), C*K]
        return carry

    jax.lax.fori_loop(0, bins_ref.shape[1] // chunk, scan, 0)


# one-hot rows of a column: its bin count rounded up to the int8
# sublane tile, so the pieces concatenate on tile boundaries
ROW_TILE = 32
# one-hot rows one feature block may hold: up to ONE_BLOCK_ROWS the
# table is a single block; a wider one is cut into windows of at most
# WINDOW_ROWS (the [rows, K*C] accumulator, its contribution and the
# streamed one-hot share the 16MB scoped-vmem budget)
ONE_BLOCK_ROWS = 8192
WINDOW_ROWS = 4096


class OneHotLayout(NamedTuple):
    """Static row layout of the kernel's one-hot and accumulator.

    ``rows[p]`` one-hot rows belong to position p of every feature
    block; column f sits at position ``f % f_blk`` of block
    ``f // f_blk``, and its bin b at row ``offsets[p] + b`` of that
    block's ``block_rows`` rows."""
    f_blk: int
    n_fb: int
    rows: Tuple[int, ...]

    @property
    def block_rows(self) -> int:
        return sum(self.rows)

    @property
    def onehot_rows(self) -> int:
        """One-hot rows built for every column of rows scanned: what
        ``hist.onehot_elems`` counts."""
        return self.n_fb * self.block_rows

    @property
    def offsets(self) -> Tuple[int, ...]:
        return tuple(itertools.accumulate((0,) + self.rows[:-1]))


@functools.lru_cache(maxsize=None)
def onehot_layout(col_bins: Tuple[int, ...], num_bins: int) -> OneHotLayout:
    """The layout for a source whose PHYSICAL column f holds bins
    ``0..col_bins[f]-1``. A table of at most ONE_BLOCK_ROWS rows is one
    block, every column with its own count. A wider one runs the 2-D
    grid over windows of ``f_blk`` columns and ONE kernel body serves
    them all, so a position takes the largest count any window has
    there (all-full columns give the dense ``f_blk x num_bins``)."""
    need = tuple(-(-min(max(int(c), 1), num_bins) // ROW_TILE) * ROW_TILE
                 for c in col_bins)
    F = len(need)
    if sum(need) <= ONE_BLOCK_ROWS:
        return OneHotLayout(F, 1, need)
    f_blk = max(1, WINDOW_ROWS // max(need))
    n_fb = -(-F // f_blk)
    rows = tuple(max(need[p::f_blk]) for p in range(f_blk))
    return OneHotLayout(f_blk, n_fb, rows)


def dense_histograms(out: jax.Array, layout: OneHotLayout, F: int,
                     num_bins: int, K: int, C: int) -> jax.Array:
    """The kernel's ``[n_fb * block_rows, C*K]`` accumulator -> the
    dense ``[K, F, num_bins, C]`` the grower reads, zeros where a column
    has no such bin: static slices and pads of the small accumulator,
    one per position of a block."""
    out = out.reshape(layout.n_fb, layout.block_rows, C * K)
    cols = []
    for off, r in zip(layout.offsets, layout.rows):
        keep = min(r, num_bins)      # ROW_TILE can round past num_bins
        cols.append(jnp.pad(out[:, off:off + keep],
                            ((0, 0), (0, num_bins - keep), (0, 0))))
    dense = jnp.stack(cols, axis=1)        # [n_fb, f_blk, num_bins, C*K]
    dense = dense.reshape(layout.n_fb * layout.f_blk, num_bins, C, K)[:F]
    return dense.transpose(3, 0, 1, 2)


# lanes of a row block one pass of the kernel's inner loop takes
LANE_CHUNK = 1024


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "col_bins",
                                    "rows_per_block", "int_mode"))
def multi_leaf_histogram(bins_t: jax.Array, vals_t: jax.Array,
                         leaf_id: jax.Array, small_ids: jax.Array, *,
                         num_bins: int,
                         col_bins: Optional[Tuple[int, ...]] = None,
                         rows_per_block: int = 2048,
                         int_mode: bool = False) -> jax.Array:
    """Histograms of K leaves in one fused scan (TPU Pallas path).

    Args:
      bins_t: ``[F, n]`` int8 FEATURE-MAJOR binned matrix (transposed once
        at setup so row blocks are lane-contiguous; uint8 values stored
        with int8 wraparound).
      vals_t: ``[C, n]`` float32 channel-major per-row values
        (grad*m, hess*m, count-mask) — bagging masks pre-applied.
      leaf_id: ``[n]`` int32 current leaf of each row.
      small_ids: ``[K]`` int32 leaf ids to histogram (-1 entries match no
        row, giving zero histograms for inactive slots).
      num_bins: static histogram width B.
      col_bins: static bin count of each of the F columns (a value of
        column f is below ``col_bins[f]``); the one-hot holds rows for
        those bins only. None: every column has ``num_bins``.

    Returns:
      ``[K, F, B, C]`` float32.
    """
    F, n = bins_t.shape
    C = vals_t.shape[0]
    K = small_ids.shape[0]
    R = rows_per_block
    assert n % R == 0, f"n={n} must be a multiple of rows_per_block={R}"
    if col_bins is None:
        col_bins = (num_bins,) * F
    if len(col_bins) != F:
        raise ValueError(
            f"col_bins names {len(col_bins)} columns, bins_t has {F}")

    # feature blocking keeps the [rows, K*C] VMEM accumulator (and the
    # transient one-hot) bounded for wide datasets (MSLR F=136+); up to
    # ONE_BLOCK_ROWS one-hot rows this is a single block. Blocked
    # (wide-F) layouts use half-size windows and, in learner/serial.py,
    # a half-size row block.
    lay = onehot_layout(tuple(col_bins), num_bins)
    F_pad = lay.n_fb * lay.f_blk
    if F_pad > F:
        bins_t = jnp.concatenate(
            [bins_t, jnp.zeros((F_pad - F, n), bins_t.dtype)])

    kernel = functools.partial(
        _hist_kernel, rows=lay.rows, n_chan=C, int_mode=int_mode,
        chunk=LANE_CHUNK if R % LANE_CHUNK == 0 else R)
    # NO input_output_aliases here (examined, round 7 — docs/perf.md
    # "Iteration floor"): the [rows, C*K] accumulator is an
    # output-only carry across the sequential row-block grid, zeroed at
    # its first step and accumulated in place in VMEM; no input
    # operand shares its shape/dtype, and threading a caller-supplied
    # zeroed buffer just to alias it would ADD an HBM zero-fill per
    # call — strictly worse than the status quo.
    out = pl.pallas_call(
        kernel,
        grid=(lay.n_fb, n // R),
        in_specs=[
            pl.BlockSpec((lay.f_blk, R), lambda j, i: (j, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, R), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R), lambda j, i: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((K, 1), lambda j, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((lay.block_rows, C * K),
                               lambda j, i: (j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((lay.onehot_rows, C * K),
                                       jnp.int32 if int_mode
                                       else jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * lay.onehot_rows * n * K * C,
            bytes_accessed=bins_t.size + vals_t.size * 4 + leaf_id.size * 4,
            transcendentals=0),
        # the device op's name, pinned: profile readers match it
        name="multi_leaf_histogram",
    )(bins_t, vals_t, leaf_id.reshape(1, n), small_ids.reshape(K, 1))
    if int_mode:
        out = out.astype(jnp.float32)
    return dense_histograms(out, lay, F, num_bins, K, C)


def multi_leaf_histogram_xla(bins: jax.Array, vals: jax.Array,
                             leaf_id: jax.Array, small_ids: jax.Array, *,
                             num_bins: int,
                             rows_per_block: int = 1024,
                             precise: bool = False) -> jax.Array:
    """XLA fallback (CPU tests / non-TPU backends): same contract via the
    einsum-based build_histogram with leaf masks packed into channels.
    ``precise`` keeps grad/hess in float32 (tpu_double_precision_hist)
    instead of the default bfloat16 operands."""
    from .histogram import build_histogram
    K = small_ids.shape[0]
    n, _F = bins.shape
    C = vals.shape[1]
    mask = (leaf_id[:, None] == small_ids[None, :]).astype(vals.dtype)
    packed = (mask[:, :, None] * vals[:, None, :]).reshape(n, K * C)
    hist = build_histogram(bins, packed, num_bins=num_bins,
                           rows_per_block=rows_per_block, precise=precise)
    F, B, _ = hist.shape
    return hist.reshape(F, B, K, C).transpose(2, 0, 1, 3)
