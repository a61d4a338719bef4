"""Pallas TPU kernel: mask-driven row compaction (stream compaction).

Reference context: LightGBM's sampled training paths scan index subsets
(``bag_data_indices_`` in goss.hpp / bagging.hpp — upstream paths
UNVERIFIED, empty mount, see SURVEY.md banner). XLA has no fast
equivalent: ``jnp.nonzero`` + computed-index gathers serialize on the
scalar unit (~1 s at 1M rows, docs/perf.md), and the round-3 substitute
— one multi-operand ``lax.sort`` — compiles superlinearly in operand
count, capping it at F≲32 packed columns.

This kernel removes both limits with the TPU's two strong units:

- per row-block, the kept rows' within-block destinations (a cheap XLA
  segmented cumsum, computed OUTSIDE the kernel) become a one-hot
  permutation matrix ``P_T[d, s] = [dest[s] + rem == d]`` generated on
  the VPU in natural [sublane=dst, lane=src] layout, ONE 128-row
  destination group at a time and only the groups the block fills
  (``plan_compaction``'s ``nch``: a block that keeps 30% of 1,024 rows
  fills 3 or 4 of its window's 9);
- a group moves everything a row carries by ONE bf16 MXU matmul with
  f32 accumulation (bins are exact in bf16; the value channels ride as
  a 3-way bf16 significand split and come out bit-exact);
- the compacted block is DMA'd to HBM at the 128-aligned floor of its
  exact stream position. The ≤127 columns of *partial* output group at
  that position are re-emitted from the predecessor's window, which is
  still in VMEM (the grid is sequential on TPU; two window slots), and
  that makes the packing EXACT — kept rows land contiguously, no
  per-block padding waste. A block's write runs under the next block's
  matmuls; the windows overlap in HBM, so each waits for the one before.

Cost is O(n·128·nch) compares + as many (F + 24)-row MAC columns — a
block's work follows the rows it KEEPS, not the window it may write —
plus a fixed part a block (grid step, operand split, DMAs). Chip
figures are in PERF.md §6 "PR 35" (the probe behind this shape: whole
window, four matmuls, head read back from HBM 3.76 us a block; this
kernel 0.84, of which 0.40 fixed; 1,024 rows a block beat 512 and 2,048);
docs/perf.md "Row compaction kernel" has the design notes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128  # TPU lane width; output DMAs land on these boundaries


def compaction_out_cols(max_selected: int, rows_per_block: int,
                        multiple: int) -> int:
    """Static output width for ``compact_rows``: the kept rows plus one
    block of write slack, rounded up to ``multiple`` (the histogram
    kernel's rows_per_block) so the compacted buffer feeds
    ``multi_leaf_histogram`` directly."""
    m = max_selected + rows_per_block + _LANE
    return -(-m // multiple) * multiple


def plan_compaction(mask: jax.Array, rows_per_block: int,
                    out_cols: int
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Within-block destinations + per-block aligned write positions.

    Args:
      mask: ``[n]`` bool/int keep mask; n % rows_per_block == 0.
      rows_per_block: source block size R.
      out_cols: static output width (``compaction_out_cols``); write
        positions are clamped so the kernel's ``R + 128``-wide writes
        stay in bounds even if the caller's ``max_selected`` bound is
        violated (clamping corrupts the tail instead of faulting —
        callers must size ``out_cols`` from a true upper bound).

    Returns:
      (dest ``[n]`` int32 within-block destination or -1 for dropped
      rows, aligned ``[nb]`` int32 block write positions in 128-lane
      GROUP units, rem ``[nb]`` int32 partial-group length at each
      block's start, nch ``[nb]`` int32 the 128-lane destination groups
      each block fills, ``ceil((rem + kept) / 128)``: all the kernel
      builds of a block's ``R / 128 + 1``).
    """
    n = mask.shape[0]
    R = rows_per_block
    nb = n // R
    mb = mask.reshape(nb, R).astype(jnp.int32)
    within = jnp.cumsum(mb, axis=1)
    cnt = within[:, -1]
    dest = jnp.where(mb > 0, within - 1, -1).reshape(n)
    stream = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(cnt)[:-1].astype(jnp.int32)])
    aligned = jnp.minimum(stream // _LANE,
                          (out_cols - R - _LANE) // _LANE)
    rem = stream - aligned * _LANE
    # (a clamped block's rem may pass 127: never past the window)
    nch = jnp.minimum(-(-(rem + cnt) // _LANE), R // _LANE + 1)
    return dest, aligned, rem, nch


def _compact_kernel(algn_ref, fill_ref, *refs, rows_per_block: int,
                    table: bool):
    if table:
        blk_ref, span_ref, base_ref, *refs = refs
    (dest_ref, bins_ref, vals_ref, bins_out, vals_out, bins_vmem, vals_vmem,
     sem_b, sem_v) = refs
    b = pl.program_id(0)
    if not table:
        _compact_step(b, algn_ref, fill_ref, dest_ref, bins_ref, vals_ref,
                      bins_out, vals_out, bins_vmem, vals_vmem, sem_b, sem_v,
                      rows_per_block=rows_per_block, piece=None,
                      last=lambda: b == pl.num_programs(0) - 1)
        return
    # a block table: step b walks piece b, the rows [lo, hi) of block
    # blk[b]; the steps past the last piece (span -1) do nothing, and
    # the last piece waits for its own write
    sp = span_ref[b]

    def last():
        nxt = span_ref[jnp.minimum(b + 1, pl.num_programs(0) - 1)]
        return (b == pl.num_programs(0) - 1) | (nxt < 0)

    @pl.when(sp >= 0)
    def _():
        _compact_step(b, algn_ref, fill_ref, dest_ref, bins_ref, vals_ref,
                      bins_out, vals_out, bins_vmem, vals_vmem, sem_b, sem_v,
                      rows_per_block=rows_per_block,
                      piece=(sp & 0xFFFF, sp >> 16, base_ref[b]), last=last)


def _compact_step(b, algn_ref, fill_ref, dest_ref, bins_ref, vals_ref,
                  bins_out, vals_out, bins_vmem, vals_vmem, sem_b, sem_v, *,
                  rows_per_block: int, piece, last):
    R = rows_per_block
    W = R + _LANE
    F_pad, C_pad = bins_ref.shape[0], vals_ref.shape[0]
    # two window slots: this block builds one while the predecessor's
    # is on its way to HBM
    slot = b % 2
    prev = 1 - slot
    a_now = algn_ref[b]
    a_prev = algn_ref[jnp.maximum(b - 1, 0)]
    # rem and nch in one SMEM word (see compact_rows)
    rem = fill_ref[b] & 0xFFFF
    nch = fill_ref[b] >> 16
    # dropped rows (dest == -1) match no destination; kept rows land
    # after the rem carried-over columns (the shift must not touch the
    # -1 sentinel, which rem > 0 would otherwise lift to a real column)
    d0 = dest_ref[...]
    if piece is not None:
        # the piece's rows alone, ranked from its first: the block's
        # kept rows before lo, and its rows of other leaves, drop out
        lo, hi, base = piece
        r = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
        d0 = jnp.where((d0 >= 0) & (r >= lo) & (r < hi), d0 - base, -1)
    dest = jnp.where(d0 >= 0, d0 + rem, -1)                 # [1, R]
    # ONE bf16 operand for everything a row carries. The bins' -128..127
    # are exact in bf16. The value channels move EXACTLY via a 3-way
    # bf16 significand split (8+8+8 >= f32's 24 mantissa bits — the
    # bf16x3 decomposition XLA itself uses for f32 emulation): a one-hot
    # product with f32 accumulation selects one chunk unrounded, and
    # the f32 chunk sum reconstructs the value bit-for-bit. A single
    # bf16 pass would RE-ROUND grads and GOSS amplification weights.
    v = vals_ref[...]
    h1 = v.astype(jnp.bfloat16).astype(jnp.float32)
    r1 = v - h1
    h2 = r1.astype(jnp.bfloat16).astype(jnp.float32)
    h3 = r1 - h2
    rows = jnp.concatenate(
        [bins_ref[...].astype(jnp.int32).astype(jnp.float32), h1, h2, h3],
        axis=0).astype(jnp.bfloat16)                # [F_pad + 3 C_pad, R]
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (_LANE, R), 0)
    bw = bins_vmem.at[slot]
    vw = vals_vmem.at[slot]

    def group(c, carry):
        # one 128-wide destination group: its slice of the one-hot
        # permutation, transposed layout [dst(sublane), src(lane)]
        col = pl.multiple_of(c * _LANE, _LANE)
        eq = (iota_d == dest - col).astype(jnp.bfloat16)    # [128, R]
        m = jax.lax.dot_general(
            rows, eq, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        bw[:, pl.ds(col, _LANE)] = m[:F_pad].astype(jnp.int32) \
            .astype(jnp.int8)
        vw[:, pl.ds(col, _LANE)] = (
            m[F_pad:F_pad + C_pad] + m[F_pad + C_pad:F_pad + 2 * C_pad]
            + m[F_pad + 2 * C_pad:])
        return carry

    # only the groups the block fills: the rest of the window keeps what
    # an earlier step left there, past the stream's end at every step
    jax.lax.fori_loop(0, nch, group, 0)
    # the predecessor's partial output group is group (off - off_prev)
    # / 128 of the window it built (the grid is sequential); at b == 0
    # that slot is uninitialized, masked off below (rem = 0). Under a
    # block table a piece may start a new run past the predecessor's
    # window: the head group then holds none of its rows, and what the
    # clipped group puts there lies outside every run
    g = jnp.clip(a_now - a_prev, 0, W // _LANE - 1)
    hcol = pl.multiple_of(g * _LANE, _LANE)
    head_ok = (jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
               < rem)
    bw[:, :_LANE] = jnp.where(
        head_ok, bins_vmem[prev, :, pl.ds(hcol, _LANE)].astype(jnp.int32),
        bw[:, :_LANE].astype(jnp.int32)).astype(jnp.int8)
    vw[:, :_LANE] = jnp.where(
        head_ok, vals_vmem[prev, :, pl.ds(hcol, _LANE)], vw[:, :_LANE])

    def write(s, group0):
        at = pl.ds(group0 * _LANE, W)
        return (pltpu.make_async_copy(bins_vmem.at[s], bins_out.at[:, at],
                                      sem_b.at[s]),
                pltpu.make_async_copy(vals_vmem.at[s], vals_out.at[:, at],
                                      sem_v.at[s]))

    # successive windows OVERLAP in HBM, so two writes are never in
    # flight together: the predecessor's ran under this block's loop
    # and has to land before this one starts
    @pl.when(b > 0)
    def _():
        for dma in write(prev, a_prev):
            dma.wait()

    mine = write(slot, a_now)
    for dma in mine:
        dma.start()

    @pl.when(last())
    def _():
        for dma in mine:
            dma.wait()


@functools.partial(jax.jit,
                   static_argnames=("out_cols", "rows_per_block", "name"))
def compact_rows(bins_t: jax.Array, vals_t: jax.Array, dest: jax.Array,
                 aligned: jax.Array, rem: jax.Array, nch: jax.Array, *,
                 out_cols: int, rows_per_block: int = 1024,
                 name: str = "compact_rows",
                 blocks: Optional[Tuple[jax.Array, jax.Array,
                                        jax.Array]] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Compact kept columns of feature-major arrays (TPU Pallas path).

    Args:
      bins_t: ``[F, n]`` int8 feature-major binned matrix.
      vals_t: ``[C, n]`` float32 channel-major per-row values (grad,
        hess, count-mask, optionally leaf_id+1 — any C). Moved
        bit-exactly (bf16x3 significand split in the kernel).
      dest / aligned / rem / nch: from ``plan_compaction`` (same
        rows_per_block).
      out_cols: static output width (``compaction_out_cols``).
      name: the device op's name (the leaf-ordered partition's mover
        passes ``partition_move``, so a trace tells its passes from
        GOSS's compaction).
      blocks: ``None`` (every block once, in order) or a table of
        PIECES the grid walks instead (scalar-prefetched, as
        ``multi_leaf_histogram(..., blocks=)``): ``(blk, span, base)``,
        each ``[P]`` int32 — piece p reads block ``blk[p]``, keeps only
        its rows ``[lo, hi)`` (``span = lo | hi << 16``; -1 past the
        last piece, a step that does nothing) and ranks them from
        ``dest - base[p]``; ``aligned`` / ``rem`` / ``nch`` are then
        per piece, and the pieces' write positions may jump forward
        between runs (ops/partition.py: the leaf-ordered mover).

    Returns:
      (``[F, out_cols]`` int8, ``[C, out_cols]`` float32): kept columns
      packed contiguously left-to-right in source order; the tail is
      zeros, so downstream histogram scans see zero contributions
      there (and a leaf_id+1 channel decodes the tail to -1). Under a
      block table only the pieces' own runs are defined, and the rows
      stay padded to a multiple of 8 (the kernel's own outputs).
    """
    F, n = bins_t.shape
    C = vals_t.shape[0]
    R = rows_per_block
    assert n % R == 0, f"n={n} must be a multiple of rows_per_block={R}"
    # whole 128-lane groups; 512 to 2048 probed on the chip (1024 best:
    # a group's compares and MXU tiles scale with R, the fixed part a
    # block with 1 / R), nothing larger
    assert R % _LANE == 0 and R <= 2048, \
        f"rows_per_block={R}: a multiple of 128, at most 2048"
    assert out_cols >= R + _LANE, "out_cols below one write window"
    nb = n // R if blocks is None else blocks[0].shape[0]
    W = R + _LANE
    # the manual output DMAs slice dim 0 whole, which Mosaic requires
    # 8-sublane aligned — pad the channel dims with zero rows
    F_pad = -(-F // 8) * 8
    C_pad = -(-C // 8) * 8
    if F_pad > F:
        bins_t = jnp.concatenate(
            [bins_t, jnp.zeros((F_pad - F, n), bins_t.dtype)])
    if C_pad > C:
        vals_t = jnp.concatenate(
            [vals_t, jnp.zeros((C_pad - C, n), vals_t.dtype)])
    # the per-block scalars live in SMEM, 1 MB in all: rem and nch share
    # a word, so a block costs 8 bytes there and 115M rows still fit (a
    # clamped block's rem, already past saving, is cut to 16 bits)
    fill = (nch << 16) | jnp.minimum(rem, 0xFFFF)
    # NO input_output_aliases on the output windows (examined, round 7
    # — docs/perf.md "Iteration floor"): out_cols != n by construction
    # (compaction_out_cols adds one block of write slack + lane
    # padding), so neither [F_pad, out_cols] output can alias its
    # [F_pad, n] input; and even at equal widths the kernel reads
    # block b's source columns AFTER earlier blocks wrote their packed
    # output left of them — in-place would clobber unread sources.
    if blocks is None:
        scalars = (aligned, fill)

        def at(b, a, f):
            return (0, b)
    else:
        scalars = (aligned, fill) + tuple(blocks)

        def at(b, a, f, blk, span, base):
            return (0, blk[b])
    out_b, out_v = pl.pallas_call(
        functools.partial(_compact_kernel, rows_per_block=R,
                          table=blocks is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((1, R), at),
                pl.BlockSpec((F_pad, R), at),
                pl.BlockSpec((C_pad, R), at),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, F_pad, W), jnp.int8),
                pltpu.VMEM((2, C_pad, W), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((F_pad, out_cols), jnp.int8),
            jax.ShapeDtypeStruct((C_pad, out_cols), jnp.float32),
        ],
        # the device op's name, pinned: profile readers match it
        name=name,
    )(*scalars, dest.reshape(1, n), bins_t, vals_t)
    if blocks is not None:
        return out_b, out_v
    # Pallas outputs are uninitialized and a window's groups past the
    # ones its block filled hold whatever an earlier step left: zero
    # everything past the stream's exact end (the last block's start
    # plus its kept rows) so downstream scans see zero contributions
    end = (aligned[-1] * _LANE + rem[-1]
           + jnp.max(dest[n - R:]) + 1)
    col_ok = (jnp.arange(out_cols, dtype=jnp.int32) < end)[None, :]
    return (jnp.where(col_ok, out_b[:F], jnp.int8(0)),
            jnp.where(col_ok, out_v[:C], jnp.float32(0.0)))


@functools.partial(jax.jit,
                   static_argnames=("out_cols", "rows_per_block"))
def compact_rows_xla(bins_t: jax.Array, vals_t: jax.Array,
                     dest: jax.Array, aligned: jax.Array,
                     rem: jax.Array, *, out_cols: int,
                     rows_per_block: int = 1024
                     ) -> Tuple[jax.Array, jax.Array]:
    """XLA scatter fallback (CPU tests / non-TPU backends): identical
    output layout to ``compact_rows`` (exact contiguous packing), any
    bins dtype, exact f32 values. Scatters serialize on TPU
    (docs/perf.md) — use only off-TPU."""
    R = rows_per_block
    stream = aligned * _LANE + rem                       # [nb] exact
    gd = jnp.where(dest >= 0,
                   jnp.repeat(stream, R) + dest,
                   out_cols).astype(jnp.int32)
    out_b = jnp.zeros((bins_t.shape[0], out_cols + 1),
                      bins_t.dtype).at[:, gd].set(bins_t, mode="drop")
    out_v = jnp.zeros((vals_t.shape[0], out_cols + 1),
                      vals_t.dtype).at[:, gd].set(vals_t, mode="drop")
    return out_b[:, :out_cols], out_v[:, :out_cols]
