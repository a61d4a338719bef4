"""Pallas TPU kernel: send every row of the table through a finished tree.

The grower (learner/serial.py) routes rows inside its loop, a batch of
splits a trip. Under GOSS's compact buffer the histograms read the
buffer's ids alone, and the table's own ids are read once a tree, by the
score update: so the loop leaves the table be and this module routes it
once, after the loop, by replaying the tree's nodes in the order they
were made. Node ``j`` split leaf ``leaf[j]``; its left child kept that
id and its right child is leaf ``j + 1``:

    leaf[r] = j + 1   where leaf[r] == nodes.leaf[j] and row r goes right

The decision is the in-loop pass's own (``apply_splits``), in integers,
and bit-equal to it: a numeric node sends a row right when its bin is
past the threshold, the NaN bin going where ``default_left`` says; a
set-split node when bit ``bin & 31`` of word ``bin >> 5`` of the node's
bitset is clear.

The kernel takes a block of ``R`` rows: the block's ``[F, R]`` int8
columns are widened ONCE into a VMEM scratch that holds each column's
rows as dense ``[R/128, 128]`` tiles, then a ``fori_loop`` over the nodes
reads its column's tiles by a dynamic index on the untiled leading axis.
The per-node tables are scalar-prefetched (SMEM), so a node costs a
handful of scalar loads and about six vector operations a register of
1,024 rows; the bitset arithmetic runs under the node's scalar
``is_cat`` only. PERF.md §6 PR 32 has the probe that chose the shape
(reading row ``f`` of a plain ``[F, R]`` scratch instead is 1.1 to 1.3
times dearer, a strided read of interleaved tiles 1.0 to 1.7).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
# rows of a block the kernel widens at a time (bounds the live [F, chunk]
# int32 temporary, not the block)
_WIDEN_CHUNK = 2048


class RouteNodes(NamedTuple):
    """A finished tree as the replay reads it: ``[N]`` int32 a field
    (N = num_leaves - 1 slots; only the first ``count`` are nodes)."""
    count: jax.Array        # [] nodes the tree has
    feature: jax.Array      # column of bins_t / bins the node reads
    threshold: jax.Array    # bin > threshold goes right
    flip_bin: jax.Array     # the one bin that goes the OTHER way (the
    #                         NaN bin when default_left disagrees with
    #                         its place in the order), or -1
    leaf: jax.Array         # the leaf the node split
    is_cat: Optional[jax.Array]     # [N] set-split node
    bitset: Optional[jax.Array]     # [N, W] int32 words, bit set = left


def route_nodes(count, split_feature, threshold_bin, default_left,
                node_leaf, feat_num_bin, feat_has_nan, is_cat=None,
                cat_bitset=None) -> RouteNodes:
    """The grower's node arrays -> ``RouteNodes``. ``apply_splits``
    reads ``where(has_nan & (bin == num_bin - 1), default_left, bin <=
    threshold)``; the same decision is ``(bin > threshold) XOR (bin ==
    flip_bin)`` with the NaN bin named only where the two disagree."""
    i32 = jnp.int32
    nan_bin = feat_num_bin[split_feature].astype(i32) - 1
    flips = (feat_has_nan[split_feature]
             & (default_left != (nan_bin <= threshold_bin)))
    return RouteNodes(
        count=jnp.asarray(count, i32),
        feature=split_feature.astype(i32),
        threshold=threshold_bin.astype(i32),
        flip_bin=jnp.where(flips, nan_bin, -1).astype(i32),
        leaf=node_leaf.astype(i32),
        is_cat=None if is_cat is None else is_cat.astype(i32),
        bitset=(None if cat_bitset is None else
                jax.lax.bitcast_convert_type(cat_bitset, i32)))


def _step(j, col, leaf, split_leaf, threshold, flip_bin, is_cat, words):
    """One node of the replay over any shape of rows: ``col`` holds the
    rows' bins of node ``j``'s column, the rest are the node's scalars;
    ``words`` is the node's bitset, a word a scalar (empty: the tree has
    no set-splits)."""
    hit = leaf == split_leaf

    def numeric(_):
        right = jnp.logical_xor(col > threshold, col == flip_bin)
        return jnp.where(hit & right, j + 1, leaf)

    if not words:
        return numeric(0)

    def in_set(_):
        idx = col >> 5
        word = jnp.zeros_like(col)
        for w, bits in enumerate(words):
            word = jnp.where(idx == w, bits, word)
        right = ((word >> (col & 31)) & 1) == 0
        return jnp.where(hit & right, j + 1, leaf)

    return jax.lax.cond(is_cat > 0, in_set, numeric, 0)


def _route_kernel(count_ref, feat_ref, thr_ref, flip_ref, leaf_ref,
                  cat_ref, bits_ref, bins_ref, out_ref, cols, *,
                  n_words: int):
    F, R = bins_ref.shape
    tiles = _WIDEN_CHUNK // _LANE
    for c in range(R // _WIDEN_CHUNK):
        # int8 wraparound storage -> the bin values, a column's rows on
        # dense tiles
        wide = bins_ref[:, c * _WIDEN_CHUNK:(c + 1) * _WIDEN_CHUNK
                        ].astype(jnp.int32) & 0xFF
        cols[0:F, c * tiles:(c + 1) * tiles, :] = wide.reshape(
            F, tiles, _LANE)

    def body(j, leaf):
        return _step(j, cols[feat_ref[j]], leaf, leaf_ref[j], thr_ref[j],
                     flip_ref[j], cat_ref[j] if n_words else None,
                     [bits_ref[j * n_words + w] for w in range(n_words)])

    out_ref[...] = jax.lax.fori_loop(
        0, count_ref[0], body, jnp.zeros(out_ref.shape, jnp.int32))


# rows a grid step takes: a node's scalar work is paid once a block, so a
# call falls with the block until about here (PERF.md §6 PR 32: 4,096 /
# 8,192 / 16,384 / 32,768 read 27.6 / 19.4 / 15.6 / 14.1 ms over 57.5M
# rows x 13 columns); the scratch is 5 MB at 39 columns
ROWS_PER_BLOCK = 32768


@functools.partial(jax.jit, static_argnames=("rows_per_block",))
def route_rows(bins_t: jax.Array, nodes: RouteNodes, *,
               rows_per_block: int = ROWS_PER_BLOCK) -> jax.Array:
    """Leaf id of every row (TPU Pallas path).

    Args:
      bins_t: ``[F, n]`` int8 feature-major binned matrix (uint8 values
        stored with wraparound).
      nodes: the tree (``route_nodes``).

    Returns:
      ``[n]`` int32: what the grower's in-loop pass leaves in
      ``leaf_id`` for the same tree.
    """
    F, n = bins_t.shape
    R = rows_per_block
    assert R % _WIDEN_CHUNK == 0, R
    n_words = 0 if nodes.bitset is None else nodes.bitset.shape[1]
    n_lanes = -(-n // _LANE) * _LANE
    if n_lanes < R:     # a table smaller than a block is one block
        n_lanes = R = -(-n // _WIDEN_CHUNK) * _WIDEN_CHUNK
    if n_lanes > n:     # never the engine's table: n_pad is whole tiles
        bins_t = jnp.pad(bins_t, ((0, 0), (0, n_lanes - n)))
    tiles = R // _LANE
    zero = jnp.zeros(1, jnp.int32)
    out = pl.pallas_call(
        functools.partial(_route_kernel, n_words=n_words),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            # a ragged last block reads past the table and its rows are
            # not written back
            grid=(pl.cdiv(n_lanes, R),),
            in_specs=[pl.BlockSpec((F, R), lambda b, *_: (0, b))],
            out_specs=pl.BlockSpec((tiles, _LANE), lambda b, *_: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((-(-F // 8) * 8, tiles, _LANE), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_lanes // _LANE, _LANE),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 2**20),
        # the device op's name, pinned: profile readers match it
        name="route_rows",
    )(nodes.count.reshape(1), nodes.feature, nodes.threshold,
      nodes.flip_bin, nodes.leaf,
      zero if nodes.is_cat is None else nodes.is_cat,
      zero if nodes.bitset is None else nodes.bitset.reshape(-1),
      bins_t)
    return out.reshape(n_lanes)[:n]


@jax.jit
def route_rows_xla(bins: jax.Array, nodes: RouteNodes) -> jax.Array:
    """The same replay as a plain loop over the nodes on the ROW-major
    ``[n, F]`` table (CPU tests / non-TPU backends; on the TPU a column
    of a row-major table is a read of the whole table, a node)."""
    def body(j, leaf):
        col = jax.lax.dynamic_index_in_dim(
            bins, nodes.feature[j], axis=1, keepdims=False
        ).astype(jnp.int32)
        return _step(j, col, leaf, nodes.leaf[j], nodes.threshold[j],
                     nodes.flip_bin[j],
                     None if nodes.is_cat is None else nodes.is_cat[j],
                     [] if nodes.bitset is None else list(nodes.bitset[j]))

    return jax.lax.fori_loop(0, nodes.count, body,
                             jnp.zeros(bins.shape[0], jnp.int32))
