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
  the VPU in natural [sublane=dst, lane=src] layout;
- the block's columns are moved by ONE MXU matmul per operand group
  (int8 for bins — wrap-exact; bf16 for value channels — exact for the
  histogram operands, which are themselves bf16/int-level downstream);
- the compacted block is DMA'd to HBM at the 128-aligned floor of its
  exact stream position. The ≤127 columns of *partial* output group at
  that position are first DMA'd back in and re-emitted (the grid is
  sequential on TPU, so the read sees the predecessor's write), which
  makes the packing EXACT — kept rows land contiguously, no per-block
  padding waste.

Cost is O(n·R) compares + O(n·R·F) int8 MACs — independent of F's
*operand packing*, so wide datasets (Bosch F=200, Criteo F=199) compact
as cheaply per byte as the Higgs shape. Measured numbers live in
docs/perf.md ("Row compaction kernel").
"""
from __future__ import annotations

import functools
from typing import Tuple

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
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
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
      block's start).
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
    return dest, aligned, rem


def _compact_kernel(algn_ref, rem_ref, dest_ref, bins_ref, vals_ref,
                    bins_out, vals_out, bins_vmem, vals_vmem,
                    bins_head, vals_head, sem_b, sem_v, sem_hb, sem_hv,
                    *, rows_per_block: int):
    b = pl.program_id(0)
    R = rows_per_block
    W = R + _LANE
    off = algn_ref[b] * _LANE
    rem = rem_ref[b]
    # read back the predecessor's partial output group at this block's
    # aligned position (sequential grid -> the write has landed); at
    # b == 0 this reads uninitialized columns, masked off below (rem=0)
    rb = pltpu.make_async_copy(
        bins_out.at[:, pl.ds(off, _LANE)], bins_head, sem_hb)
    rv = pltpu.make_async_copy(
        vals_out.at[:, pl.ds(off, _LANE)], vals_head, sem_hv)
    rb.start()
    rv.start()
    # one-hot permutation, transposed layout [dst(sublane), src(lane)]:
    # dropped rows (dest == -1) match no destination; kept rows land
    # after the rem carried-over columns (the shift must not touch the
    # -1 sentinel, which rem > 0 would otherwise lift to a real column)
    d0 = dest_ref[...]
    dest = jnp.where(d0 >= 0, d0 + rem, -1)                 # [1, R]
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (W, R), 0)
    eq = iota_d == dest                                     # [W, R]
    moved = jax.lax.dot_general(
        bins_ref[...], eq.astype(jnp.int8),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                   # [F, W]
    # value channels move EXACTLY via a 3-way bf16 significand split
    # (8+8+8 >= f32's 24 mantissa bits — the bf16x3 decomposition XLA
    # itself uses for f32 emulation): each one-hot product selects one
    # chunk unrounded, and the f32 chunk sum reconstructs the value
    # bit-for-bit. A single bf16 pass would RE-ROUND grads and GOSS
    # amplification weights; f32-HIGHEST costs +4.4 ms (measured).
    p_bf = eq.astype(jnp.bfloat16)
    v = vals_ref[...]
    h1 = v.astype(jnp.bfloat16)
    r1 = v - h1.astype(jnp.float32)
    h2 = r1.astype(jnp.bfloat16)
    h3 = (r1 - h2.astype(jnp.float32)).astype(jnp.bfloat16)
    _dn = (((1,), (1,)), ((), ()))
    vmoved = (jax.lax.dot_general(h1, p_bf, dimension_numbers=_dn,
                                  preferred_element_type=jnp.float32)
              + jax.lax.dot_general(h2, p_bf, dimension_numbers=_dn,
                                    preferred_element_type=jnp.float32)
              + jax.lax.dot_general(h3, p_bf, dimension_numbers=_dn,
                                    preferred_element_type=jnp.float32))
    rb.wait()
    rv.wait()
    head_ok = (jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
               < rem)
    zero_w = jnp.zeros((bins_head.shape[0], R), jnp.int32)
    head_b = jnp.concatenate(
        [jnp.where(head_ok, bins_head[...].astype(jnp.int32), 0),
         zero_w], axis=1)
    # signed-wrap back to the int8 storage convention (uint8 values
    # stored with wraparound; a plain astype would CLAMP 128..255)
    m8 = (moved + head_b) & 0xFF
    bins_vmem[...] = (m8 - ((m8 >> 7) << 8)).astype(jnp.int8)
    zero_vw = jnp.zeros((vals_head.shape[0], R), jnp.float32)
    vals_vmem[...] = vmoved + jnp.concatenate(
        [jnp.where(head_ok, vals_head[...], 0.0), zero_vw], axis=1)
    cb = pltpu.make_async_copy(
        bins_vmem, bins_out.at[:, pl.ds(off, W)], sem_b)
    cv = pltpu.make_async_copy(
        vals_vmem, vals_out.at[:, pl.ds(off, W)], sem_v)
    cb.start()
    cv.start()
    cb.wait()
    cv.wait()


@functools.partial(jax.jit,
                   static_argnames=("out_cols", "rows_per_block"))
def compact_rows(bins_t: jax.Array, vals_t: jax.Array, dest: jax.Array,
                 aligned: jax.Array, rem: jax.Array, *, out_cols: int,
                 rows_per_block: int = 1024
                 ) -> Tuple[jax.Array, jax.Array]:
    """Compact kept columns of feature-major arrays (TPU Pallas path).

    Args:
      bins_t: ``[F, n]`` int8 feature-major binned matrix.
      vals_t: ``[C, n]`` float32 channel-major per-row values (grad,
        hess, count-mask, optionally leaf_id+1 — any C). Moved
        bit-exactly (bf16x3 significand split in the kernel).
      dest / aligned / rem: from ``plan_compaction`` (same
        rows_per_block).
      out_cols: static output width (``compaction_out_cols``).

    Returns:
      (``[F, out_cols]`` int8, ``[C, out_cols]`` float32): kept columns
      packed contiguously left-to-right in source order; the tail is
      zeros, so downstream histogram scans see zero contributions
      there (and a leaf_id+1 channel decodes the tail to -1).
    """
    F, n = bins_t.shape
    C = vals_t.shape[0]
    R = rows_per_block
    assert n % R == 0, f"n={n} must be a multiple of rows_per_block={R}"
    # the [R+128, R] permutation's bf16 copy + the streamed operands
    # fit comfortably at R=1024 (~3.5 MB); R=2048 measured slower
    # anyway (P generation cost scales n*R)
    assert R <= 1024, f"rows_per_block={R} exceeds the VMEM-safe 1024"
    assert out_cols >= R + _LANE, "out_cols below one write window"
    nb = n // R
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
    # NO input_output_aliases on the output windows (examined, round 7
    # — docs/perf.md "Iteration floor"): out_cols != n by construction
    # (compaction_out_cols adds one block of write slack + lane
    # padding), so neither [F_pad, out_cols] output can alias its
    # [F_pad, n] input; and even at equal widths the kernel reads
    # block b's source columns AFTER earlier blocks wrote their packed
    # output left of them — in-place would clobber unread sources.
    out_b, out_v = pl.pallas_call(
        functools.partial(_compact_kernel, rows_per_block=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((1, R), lambda b, a, r: (0, b)),
                pl.BlockSpec((F_pad, R), lambda b, a, r: (0, b)),
                pl.BlockSpec((C_pad, R), lambda b, a, r: (0, b)),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            scratch_shapes=[
                pltpu.VMEM((F_pad, W), jnp.int8),
                pltpu.VMEM((C_pad, W), jnp.float32),
                pltpu.VMEM((F_pad, _LANE), jnp.int8),
                pltpu.VMEM((C_pad, _LANE), jnp.float32),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((F_pad, out_cols), jnp.int8),
            jax.ShapeDtypeStruct((C_pad, out_cols), jnp.float32),
        ],
        # the device op's name, pinned: profile readers match it
        name="compact_rows",
    )(aligned, rem, dest.reshape(1, n), bins_t, vals_t)
    # Pallas outputs are uninitialized; zero everything past the last
    # block's write window so downstream scans see zero contributions
    col_ok = (jnp.arange(out_cols, dtype=jnp.int32)
              < aligned[-1] * _LANE + W)[None, :]
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
