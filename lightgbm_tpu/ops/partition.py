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
- After each split batch the rows of the just-split leaves two-way
  partition in ONE stable global move: rows that route to a right
  child go (stably) to the back of the array, everything else packs
  (stably) to the front. One global ``cumsum`` of the "moved" mask
  yields every row's destination — and because rows of one leaf always
  share a key, the move preserves per-leaf contiguity AND within-leaf
  source order (the stability the tests pin).
- Offsets/counts update from the same prefix sums, gathered at the
  (few) leaf boundaries — no per-row gathers.
- On TPU the move itself rides the ``compact_rows`` block machinery
  (ops/compact.py): two compaction passes (front keys, back keys), the
  back buffer rolled to its start position, one ``where`` blend. Off
  TPU a computed-index scatter is cheap and exact.
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

from typing import List, Tuple

import jax
import jax.numpy as jnp

i32 = jnp.int32
# the device op's name of the mover's two compaction passes, pinned:
# profile readers match it (GOSS's compaction keeps "compact_rows")
MOVE_OP = "partition_move"


def plan_split_move(moved: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Stable front/back destinations for one split batch.

    Args:
      moved: ``[n]`` bool — True for rows that route to a RIGHT child
        this round (their leaf id changed).

    Returns:
      (dest ``[n]`` int32 destination positions — a permutation,
      n_front int32 scalar — first back-region position,
      cum ``[n]`` int32 — inclusive prefix counts of ``moved``).
    """
    n = moved.shape[0]
    mi = moved.astype(i32)
    cum = jnp.cumsum(mi)
    exc = cum - mi                       # moved rows strictly before i
    n_front = n - cum[-1]
    iota = jnp.arange(n, dtype=i32)
    dest = jnp.where(moved, n_front + exc, iota - exc)
    return dest, n_front, cum


def prefix_at(cum: jax.Array, pos: jax.Array) -> jax.Array:
    """``# moved rows strictly before position pos`` for positions in
    ``[0, n]`` (a tiny gather — O(#leaves), not O(n))."""
    cum_p = jnp.concatenate([jnp.zeros(1, i32), cum])
    return cum_p[jnp.clip(pos, 0, cum.shape[0])]


def update_tables(off: jax.Array, cnt: jax.Array, cum: jax.Array,
                  n_front: jax.Array, parents: jax.Array,
                  new_ids: jax.Array, valid: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """New per-leaf (offset, count) tables after ``plan_split_move``.

    Every non-right-child leaf (untouched leaves, left children — which
    keep the parent's slot) shifts left by the number of moved rows
    before its old offset; right children land in the back region in
    parent-position order.

    Args:
      off / cnt: ``[L+1]`` old tables (slot L = trash).
      cum: inclusive moved-prefix from ``plan_split_move``.
      parents: ``[K]`` split leaf slots (trash slot for invalid lanes).
      new_ids: ``[K]`` right-child slots (trash slot for invalid lanes).
      valid: ``[K]`` bool lane validity.
    """
    s_all = prefix_at(cum, off)                        # [L+1]
    new_off = off - s_all
    s_par = prefix_at(cum, off[parents])               # [K]
    e_par = prefix_at(cum, off[parents] + cnt[parents])
    n_right = jnp.where(valid, e_par - s_par, 0)
    new_off = new_off.at[new_ids].set(n_front + s_par)
    new_cnt = cnt.at[parents].add(-n_right)
    new_cnt = new_cnt.at[new_ids].set(n_right)
    return new_off, new_cnt


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


def move_cols_tpu(bins_fm: jax.Array, vals_fm: jax.Array,
                  moved: jax.Array, n_front: jax.Array,
                  rows_per_block: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """The same stable front/back move via TWO ``compact_rows`` kernel
    passes (ops/compact.py): pass 1 packs the not-moved columns exactly
    to the front, pass 2 packs the moved columns, which are then rolled
    to start at ``n_front`` and blended in. Value channels move
    bit-exactly (the kernel's bf16x3 significand split), so an integer
    channel (e.g. leaf ids) round-trips exactly through float32.

    Args:
      bins_fm: ``[F, n]`` int8 feature-major binned matrix.
      vals_fm: ``[C, n]`` float32 channel-major values.
      moved / n_front: from ``plan_split_move``.
      rows_per_block: compaction block size (<= 1024, divides n).
    """
    from .compact import (compact_rows, compaction_out_cols,
                          plan_compaction)
    n = bins_fm.shape[1]
    out_cols = compaction_out_cols(n, rows_per_block, rows_per_block)
    keep_front = ~moved
    # the two masks are complements: between them the passes build
    # about R / 128 + 1 destination groups a block, not twice that
    fb, fv = compact_rows(
        bins_fm, vals_fm,
        *plan_compaction(keep_front, rows_per_block, out_cols),
        out_cols=out_cols, rows_per_block=rows_per_block, name=MOVE_OP)
    bb, bv = compact_rows(
        bins_fm, vals_fm,
        *plan_compaction(moved, rows_per_block, out_cols),
        out_cols=out_cols, rows_per_block=rows_per_block, name=MOVE_OP)
    sel = (jnp.arange(n, dtype=i32) < n_front)[None, :]
    bb_r = jnp.roll(bb[:, :n], n_front, axis=1)
    bv_r = jnp.roll(bv[:, :n], n_front, axis=1)
    return (jnp.where(sel, fb[:, :n], bb_r),
            jnp.where(sel, fv[:, :n], bv_r))




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
