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
- Each growth round then histograms only the K smaller children's
  spans: a ``lax.switch`` over a static pow2 ladder of span budgets
  keeps every shape static and the compile footprint bounded (the same
  trick as predict's batch-shape bucketing); rounds whose largest
  elected child would make ``K * budget >= n`` take a full masked-scan
  fallback branch instead (the span path can never scan MORE rows than
  the masked formulation). Rows sliced from a neighbouring leaf inside
  a span are sentinel-masked, so each row contributes exactly once.

Bit-exactness: the span histogram sums exactly the same per-row terms
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


def span_budgets(n_rows: int, n_spans: int, min_budget: int = 256
                 ) -> Tuple[int, ...]:
    """Static pow2 span-budget ladder for the ``lax.switch``: budgets S
    with ``n_spans * S < n_rows`` (a span round never scans more rows
    than the masked full scan it replaces — the caller's final branch).
    The ladder is O(log n) entries, so the compile footprint stays
    bounded exactly like predict's pow2 batch buckets."""
    budgets = []
    s = min_budget
    while s < n_rows and n_spans * s < n_rows:
        budgets.append(s)
        s *= 2
    return tuple(budgets)


def slice_spans(bins_p: jax.Array, vals_p: jax.Array, leaf_p: jax.Array,
                offs: jax.Array, cnts: jax.Array, budget: int,
                feature_major: bool
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Assemble the K children's padded row spans into one histogram
    input: K static-width ``dynamic_slice``s (starts clamped into
    range), concatenated along the row axis. Positions inside a span
    that belong to a NEIGHBOURING leaf (the pow2 padding) get leaf id
    -1, so they match no histogram lane — each row of each elected
    child contributes exactly once, and only to its own lane.
    """
    n = leaf_p.shape[0]
    K = int(offs.shape[0])
    S = int(budget)
    starts = jnp.clip(offs, 0, n - S)
    rel = jnp.arange(S, dtype=i32)
    bs, vs, ls = [], [], []
    for k in range(K):
        st = starts[k]
        if feature_major:
            bk = jax.lax.dynamic_slice(
                bins_p, (i32(0), st), (bins_p.shape[0], S))
            vk = jax.lax.dynamic_slice(
                vals_p, (i32(0), st), (vals_p.shape[0], S))
        else:
            bk = jax.lax.dynamic_slice(
                bins_p, (st, i32(0)), (S, bins_p.shape[1]))
            vk = jax.lax.dynamic_slice(
                vals_p, (st, i32(0)), (S, vals_p.shape[1]))
        lk = jax.lax.dynamic_slice(leaf_p, (st,), (S,))
        keep = (rel >= offs[k] - st) & (rel < offs[k] - st + cnts[k])
        ls.append(jnp.where(keep, lk, -1))
        bs.append(bk)
        vs.append(vk)
    axis = 1 if feature_major else 0
    return (jnp.concatenate(bs, axis=axis),
            jnp.concatenate(vs, axis=axis),
            jnp.concatenate(ls))
