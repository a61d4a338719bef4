"""Leaf-ordered device row partition (tpu_hist_partition).

Reference: ``CUDADataPartition`` / ``CUDALeafSplits``
(src/treelearner/cuda/cuda_data_partition.cu, UNVERIFIED — empty mount,
see SURVEY.md banner): the reference keeps each leaf's row indices
CONTIGUOUS, so constructing the smaller child's histogram scans only
that child's rows and the sibling comes free by subtraction. Our masked
formulation scans all n rows per round; this module supplies the
structural "fewer rows" lever the round-5 trace attribution named
(docs/perf.md "Partitioned histograms").

Design (all fixed-shape, jit/while_loop/shard_map-safe):

- The binned matrix + value channels + a per-POSITION leaf-id vector
  are carried REORDERED so every leaf occupies one contiguous span,
  described by per-leaf ``(offset, count)`` tables.
- After each split batch each split leaf partitions stably INSIDE ITS
  OWN SPAN, as the reference does: the rows that stay in it (its left
  child, which keeps the parent's slot and offset) pack to the front of
  the span, the rows routed to its right child follow them, each in
  source order; the rows of every leaf the batch does not split stay
  where they are, and so do their table rows. Only the parents' and the
  new children's (offset, count) rows change.
- The move walks *pieces*: the part of one split leaf's span that lies
  in one row block, in position order (:func:`plan_split_move`). One
  in-block cumsum of the "moved" mask ranks every row among its block's
  left or right rows; a piece's left and right counts and its two
  destinations come from that cumsum at the few span boundaries.
- On TPU the move rides the ``compact_rows`` block machinery
  (ops/compact.py) under a block table of pieces: two passes (left
  rows, right rows) write each piece's rows at their final positions,
  and a blend kernel rewrites, in place, only the blocks that hold a
  row of a split leaf: their left and right runs from the two passes,
  their rows of other leaves as they were. Every other block is neither
  read nor written. Off TPU a computed-index scatter of the same
  destinations (:func:`row_dest`) is cheap and exact.
- Each growth round then histograms only the row blocks that hold a
  row of one of the K smaller children: :func:`span_blocks` lists them
  (sorted, each once) in a table of static length, and the histogram
  kernel walks that table over the partition's own buffers in place
  (``multi_leaf_histogram(..., blocks=)``: the table is scalar-
  prefetched and picks each grid step's block). A listed block's rows
  of a leaf not elected this round match no lane, as in the masked
  scan, so each row of an elected child counts once. The smaller
  children hold at most half the rows, so the table's length
  (:func:`span_block_cap`) is about n / 2R blocks; a round whose count
  channel is not a row count (bagging, weights) can list more, and
  then takes the masked full scan over the partition.

Bit-exactness: the block histogram sums exactly the same per-row terms
as the masked scan, in a different accumulation order — EXACT under
quantized gradients (integer sums are order-free; the flagship config),
float-accumulation-order-close otherwise, mirroring the GOSS
compaction contract (tests pin model-text equality under quantized and
closeness under f32).
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

i32 = jnp.int32
# the device op's name of the mover's two compaction passes, pinned:
# profile readers match it (GOSS's compaction keeps "compact_rows")
MOVE_OP = "partition_move"
# the blend that places the two passes' runs into the buffer
BLEND_OP = "partition_blend"


class MovePlan(NamedTuple):
    """One loop trip's move, from :func:`plan_split_move`.

    A *piece* is the part of one split leaf's span that lies in one row
    block; the pieces are listed in position order, so a block that
    holds rows of two split leaves is listed once for each. Side 0 is
    the left rows (the parent keeps them), side 1 the right rows.
    """
    n_left: jax.Array        # [K] rows each lane's parent keeps
    n_right: jax.Array       # [K] rows its right child takes
    touched: jax.Array       # blocks that hold a row of a split leaf
    dest: Tuple[jax.Array, jax.Array]   # [n] per side: within-block rank
    #                          among the side's rows, -1 elsewhere
    blk: jax.Array           # [P] each piece's block
    span: jax.Array          # [P] lo | hi << 16, its rows in the block;
    #                          -1 past the last piece
    base: Tuple[jax.Array, jax.Array]   # [P] per side: the side's rows
    #                          of the block before lo
    at: Tuple[jax.Array, jax.Array]     # [P] per side: where the piece's
    #                          first row goes
    cnt: Tuple[jax.Array, jax.Array]    # [P] per side: rows it sends
    cut: jax.Array           # [P] where, in the block, its right run
    #                          starts after the move (lo..hi)
    blocks: Tuple[jax.Array, jax.Array, jax.Array]  # [nb] the touched
    #                          blocks in order and each one's pieces
    #                          [p0, p1); the tail repeats the last


def plan_split_move(moved: jax.Array, offs: jax.Array, cnts: jax.Array,
                    rows_per_block: int) -> MovePlan:
    """Span-local destinations for one split batch: each split leaf's
    rows partition stably inside its own span, left rows first, right
    rows after; every other row stays where it is.

    Args:
      moved: ``[n]`` bool — True for rows that route to a RIGHT child
        this round (their leaf id changed).
      offs / cnts: ``[K]`` the split leaves' spans before the move
        (count 0 for a lane that splits nothing).
      rows_per_block: R, the mover's row block; divides n.
    """
    n = moved.shape[0]
    R = rows_per_block
    nb = n // R
    K = offs.shape[0]
    P = nb + K                  # a span adds at most one shared block
    mb = moved.reshape(nb, R).astype(i32)
    cm = jnp.cumsum(mb, axis=1).reshape(n)      # inclusive, in-block
    ex = cm - mb.reshape(n)
    r = jnp.arange(n, dtype=i32) % R
    dest = (jnp.where(moved, -1, r - ex), jnp.where(moved, ex, -1))
    # the lanes in position order, the empty ones last
    live = cnts > 0
    order = jnp.argsort(jnp.where(live, offs, n))
    a = offs[order]
    c = jnp.where(live, cnts, 0)[order]
    first = a // R
    nbk = jnp.where(c > 0, (a + c - 1) // R - first + 1, 0)
    pend = jnp.cumsum(nbk)
    pstart = pend - nbk
    q = jnp.arange(P, dtype=i32)
    # piece q belongs to the first lane whose pieces end past it
    ln = jnp.sum(pend[None, :] <= q[:, None], axis=1, dtype=i32)
    real = ln < K
    ln = jnp.minimum(ln, K - 1)
    blk = first[ln] + q - pstart[ln]
    # a step past the last piece asks for the block already fetched
    n_real = pend[-1]
    blk = jnp.where(real, blk, blk[jnp.maximum(n_real - 1, 0)])
    lo = jnp.where(real, jnp.clip(a[ln] - blk * R, 0, R), 0)
    hi = jnp.where(real, jnp.clip(a[ln] + c[ln] - blk * R, 0, R), 0)

    def moved_before(x):
        """right rows of block blk before its row x"""
        return jnp.where(x > 0, cm[jnp.clip(blk * R + x - 1, 0, n - 1)], 0)

    m_lo = moved_before(lo)
    n_r = moved_before(hi) - m_lo
    n_l = hi - lo - n_r
    cx_l = jnp.concatenate([jnp.zeros(1, i32), jnp.cumsum(n_l)])
    cx_r = jnp.concatenate([jnp.zeros(1, i32), jnp.cumsum(n_r)])
    left_s = cx_l[pend] - cx_l[pstart]
    right_s = cx_r[pend] - cx_r[pstart]
    at_l = a[ln] + cx_l[:-1] - cx_l[pstart[ln]]
    at_r = a[ln] + left_s[ln] + cx_r[:-1] - cx_r[pstart[ln]]
    # a block is handed once however many pieces share it: the blend's
    # table lists each touched block with its run of pieces, the tail
    # repeating the last of them
    head = real & jnp.concatenate([jnp.ones(1, bool), blk[1:] != blk[:-1]])
    touched = jnp.sum(head, dtype=i32)
    slot = jnp.where(head, jnp.cumsum(head) - 1, nb)
    t = jnp.arange(nb, dtype=i32)
    last = jnp.maximum(touched - 1, 0)
    t_blk = jnp.zeros(nb, i32).at[slot].set(blk, mode="drop")
    t_p0 = jnp.zeros(nb, i32).at[slot].set(q, mode="drop")
    t_p0 = jnp.where(t < touched, t_p0, t_p0[last])
    t_p1 = jnp.where(t + 1 < touched, jnp.roll(t_p0, -1), n_real)
    return MovePlan(
        n_left=jnp.zeros(K, i32).at[order].set(left_s),
        n_right=jnp.zeros(K, i32).at[order].set(right_s),
        touched=touched, dest=dest, blk=blk,
        span=jnp.where(real, lo | (hi << 16), -1),
        base=(lo - m_lo, m_lo), at=(at_l, at_r), cnt=(n_l, n_r),
        cut=jnp.clip(a[ln] + left_s[ln] - blk * R, lo, hi),
        blocks=(jnp.where(t < touched, t_blk, t_blk[last]), t_p0, t_p1))


def update_tables(off: jax.Array, cnt: jax.Array, parents: jax.Array,
                  new_ids: jax.Array, valid: jax.Array, plan: MovePlan
                  ) -> Tuple[jax.Array, jax.Array]:
    """New per-leaf (offset, count) tables after ``plan_split_move``:
    the left child keeps the parent's slot and offset, the right child
    starts after it inside the parent's old span, and every other leaf
    keeps its row of the tables.

    Args:
      off / cnt: ``[L+1]`` old tables (slot L = trash).
      parents: ``[K]`` split leaf slots (trash slot for invalid lanes).
      new_ids: ``[K]`` right-child slots (trash slot for invalid lanes).
      valid: ``[K]`` bool lane validity.
    """
    n_left = jnp.where(valid, plan.n_left, 0)
    n_right = jnp.where(valid, plan.n_right, 0)
    new_off = off.at[new_ids].set(off[parents] + n_left)
    new_cnt = cnt.at[parents].set(n_left).at[new_ids].set(n_right)
    return new_off, new_cnt


def row_dest(plan: MovePlan, rows_per_block: int) -> jax.Array:
    """``[n]`` each row's position after the move (a permutation):
    the pieces' rows where the plan sends them, every other row where
    it is — what the two kernel passes and their blend leave."""
    R = rows_per_block
    n = plan.dest[0].shape[0]
    rr = jnp.arange(R, dtype=i32)[None, :]
    lo = (plan.span & 0xFFFF)[:, None]
    hi = (plan.span >> 16)[:, None]
    src = plan.blk[:, None] * R + rr                    # [P, R]
    out = jnp.arange(n, dtype=i32)
    for side in (0, 1):
        d = plan.dest[side].reshape(n // R, R)[plan.blk]
        keep = (d >= 0) & (rr >= lo) & (rr < hi)
        to = plan.at[side][:, None] + d - plan.base[side][:, None]
        out = out.at[jnp.where(keep, src, n)].set(to, mode="drop")
    return out


def move_rows_xla(arrays: List[jax.Array], dest: jax.Array,
                  axis: int = 0) -> List[jax.Array]:
    """Apply the permutation by computed-index scatter (exact for any
    dtype). Cheap off-TPU; ON TPU computed scatters serialize
    (docs/perf.md) — use :func:`move_cols_tpu` there."""
    out = []
    for a in arrays:
        if axis == 0:
            out.append(jnp.zeros_like(a).at[dest].set(a))
        else:
            out.append(jnp.zeros_like(a).at[:, dest].set(a))
    return out


def move_cols_tpu(bins_fm: jax.Array, vals_fm: jax.Array, plan: MovePlan,
                  rows_per_block: int) -> Tuple[jax.Array, jax.Array]:
    """The same move via TWO ``compact_rows`` kernel passes
    (ops/compact.py) that walk the plan's pieces alone: pass 1 writes
    each split leaf's left rows to the front of its span, pass 2 its
    right rows after them, and a blend kernel takes those runs into the
    touched blocks of the buffer, in place (:func:`_blend`). Value
    channels move bit-exactly (the kernel's bf16x3 significand split),
    so an integer channel (e.g. leaf ids) round-trips exactly through
    float32.

    Args:
      bins_fm: ``[F, n]`` int8 feature-major binned matrix.
      vals_fm: ``[C, n]`` float32 channel-major values.
      plan: from ``plan_split_move``.
      rows_per_block: compaction block size (<= 1024, divides n).
    """
    from .compact import _LANE, compact_rows, compaction_out_cols
    n = bins_fm.shape[1]
    R = rows_per_block
    out_cols = compaction_out_cols(n, R, R)
    outs = []
    for side in (0, 1):
        algn = jnp.minimum(plan.at[side] // _LANE,
                           (out_cols - R - _LANE) // _LANE)
        rem = plan.at[side] - algn * _LANE
        nch = jnp.minimum(-(-(rem + plan.cnt[side]) // _LANE),
                          R // _LANE + 1)
        outs.append(compact_rows(
            bins_fm, vals_fm, plan.dest[side], algn, rem, nch,
            out_cols=out_cols, rows_per_block=R, name=MOVE_OP,
            blocks=(plan.blk, plan.span, plan.base[side])))
    (fb, fv), (bb, bv) = outs
    return _blend(bins_fm, vals_fm, fb, fv, bb, bv, plan, R)


def _blend_kernel(t_blk, t_p0, t_p1, p_span, p_cut, ob, ov, fb, fv, bb, bv,
                  out_b, out_v):
    t = pl.program_id(0)
    r = jax.lax.broadcasted_iota(i32, (1, ob.shape[1]), 1)

    def piece(p, code):
        lo, hi, cut = p_span[p] & 0xFFFF, p_span[p] >> 16, p_cut[p]
        code = jnp.where((r >= lo) & (r < cut), 1, code)
        return jnp.where((r >= cut) & (r < hi), 2, code)

    code = jax.lax.fori_loop(t_p0[t], t_p1[t], piece,
                             jnp.zeros(r.shape, i32))
    left, right = code == 1, code == 2
    F, C = ob.shape[0], ov.shape[0]
    out_b[...] = jnp.where(left, fb[:F].astype(i32), jnp.where(
        right, bb[:F].astype(i32), ob[...].astype(i32))).astype(jnp.int8)
    out_v[...] = jnp.where(left, fv[:C], jnp.where(right, bv[:C], ov[...]))


def _blend(bins_fm, vals_fm, fb, fv, bb, bv, plan: MovePlan, R: int):
    """The buffer after the move, written in place: each touched block
    takes its left runs from pass 1's output, its right runs from pass
    2's, and its rows of unsplit leaves as they were; every other block
    is not read."""
    F, n = bins_fm.shape
    C = vals_fm.shape[0]
    nb = n // R

    # the table's tail repeats its last block: the pipeline fetches
    # nothing new there, and the step writes the same rows again
    def at(t, t_blk, *_):
        return (0, t_blk[t])

    # the passes' outputs keep their rows padded to 8
    b_spec, v_spec = pl.BlockSpec((F, R), at), pl.BlockSpec((C, R), at)
    pad_specs = [pl.BlockSpec((fb.shape[0], R), at),
                 pl.BlockSpec((fv.shape[0], R), at)]
    return pl.pallas_call(
        _blend_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(nb,),
            in_specs=[b_spec, v_spec] + pad_specs * 2,
            out_specs=[b_spec, v_spec]),
        out_shape=[jax.ShapeDtypeStruct((F, n), jnp.int8),
                   jax.ShapeDtypeStruct((C, n), jnp.float32)],
        input_output_aliases={5: 0, 6: 1},
        name=BLEND_OP,
    )(*plan.blocks, plan.span, plan.cut, bins_fm, vals_fm, fb, fv, bb, bv)


def span_block_cap(n_rows: int, rows_per_block: int, n_spans: int) -> int:
    """``NB_max``, the static length of the block table: the smaller
    children of one split batch hold at most half the rows, and each of
    the ``n_spans`` children adds at most two partial blocks. Never more
    than the blocks the source has."""
    R = rows_per_block
    return min(n_rows // R, -(-n_rows // (2 * R)) + 2 * n_spans)


def span_blocks(offs: jax.Array, cnts: jax.Array, rows_per_block: int,
                n_blocks: int, nb_max: int) -> Tuple[jax.Array, jax.Array]:
    """The row blocks that hold a row of an elected child.

    Args:
      offs / cnts: ``[K]`` the children's spans (count 0 for a lane
        that holds no leaf).
      rows_per_block: R, the histogram kernel's row block.
      n_blocks: blocks of the source (its rows over R).
      nb_max: the table's static length (:func:`span_block_cap`).

    Returns:
      (blk ``[nb_max]`` int32 — the sorted distinct blocks, the tail
      past ``nb`` repeating the last of them so that a padded grid step
      asks for the block already fetched; nb int32 — how many there
      are, which may pass ``nb_max``: the table is then cut short and
      the caller scans the whole source instead).
    """
    R = rows_per_block
    live = cnts > 0
    # a difference array over the blocks: +1 at a span's first block,
    # -1 after its last; a dead lane adds both to the dropped slot
    first = jnp.where(live, offs // R, n_blocks)
    after = jnp.where(live, (offs + cnts - 1) // R + 1, n_blocks)
    diff = (jnp.zeros(n_blocks + 1, i32).at[first].add(1)
            .at[after].add(-1))
    cover = jnp.cumsum(diff[:n_blocks]) > 0
    nb = jnp.sum(cover, dtype=i32)
    blk = jnp.nonzero(cover, size=nb_max, fill_value=0)[0].astype(i32)
    tail = blk[jnp.clip(nb - 1, 0, nb_max - 1)]
    return jnp.where(jnp.arange(nb_max) < nb, blk, tail), nb
