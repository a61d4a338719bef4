"""Leaf-ordered device row partition (tpu_hist_partition; ops/partition.py).

Contract (mirroring the GOSS-compaction one): the partitioned path
elects and applies the SAME splits as the masked full-scan path — its
block-table histograms sum the same per-row terms in another accumulation
order, so trees are bit-identical under quantized gradients (integer
sums are order-free) and prediction-close under f32. Partition tables
must stay a valid leaf-contiguous layout after every split batch:
spans disjoint, counts summing to n, within-leaf source order stable.
"""
import dataclasses
import json
import math

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.ops import partition as part_ops

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# unit: the span-local move + table updates vs a numpy reference
# ---------------------------------------------------------------------------

def _np_reference_move(leaf, new_leaf, parents):
    """Reference semantics in plain numpy: inside each split leaf's span
    the rows that stay in it come first and the rows routed to its right
    child after them, each in source order; every other row stays where
    it is. Returns the new order (``order[j]`` = old position now at j)."""
    order = np.arange(len(leaf))
    for p in parents:
        rows = np.flatnonzero(leaf == p)
        if len(rows):
            moved = new_leaf[rows] != p
            order[rows] = np.concatenate([rows[~moved], rows[moved]])
    return order


def _plan(leaf, new_leaf, off, cnt, parents, valid, R):
    offs = jnp.asarray(off[parents])
    cnts = jnp.asarray(np.where(valid, cnt[parents], 0).astype(np.int32))
    return part_ops.plan_split_move(jnp.asarray(new_leaf != leaf), offs,
                                    cnts, R)


def _random_trip(rng, leaf, cnt, num_leaves, L, Kb):
    """One split batch over a random choice of leaves, a random share of
    each one's rows routed right (the row -> leaf pass's result)."""
    active = [lf for lf in range(num_leaves) if cnt[lf] > 1]
    k = min(Kb, len(active), L - num_leaves)
    parents = np.asarray(rng.choice(active, size=k, replace=False),
                         np.int32)
    new_ids = np.arange(num_leaves, num_leaves + k, dtype=np.int32)
    new_leaf = leaf.copy()
    for p, nid in zip(parents, new_ids):
        rows = np.flatnonzero(leaf == p)
        take = rng.random(len(rows)) < rng.uniform(0.0, 1.0)
        new_leaf[rows[take]] = nid
    return parents, new_ids, new_leaf


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_move_and_tables_invariants(seed):
    """Property-style pin over several random split batches: dest is a
    permutation equal to the span-local reference order, per-leaf spans
    stay contiguous/disjoint, offsets match the (offset, count) tables,
    counts sum to n, and within-leaf source order is preserved
    (stability)."""
    rng = np.random.default_rng(seed)
    n, L, Kb, R = 512, 31, 4, 64
    leaf = np.zeros(n, np.int32)
    off = np.zeros(L + 1, np.int32)
    cnt = np.zeros(L + 1, np.int32)
    cnt[0] = n
    # a source tag per row to verify stability across rounds
    tag = np.arange(n, dtype=np.int32)
    num_leaves = 1
    for _ in range(6):
        if num_leaves >= L - Kb:
            break
        parents, new_ids, new_leaf = _random_trip(rng, leaf, cnt,
                                                  num_leaves, L, Kb)
        k = len(parents)
        valid = np.ones(k, bool)
        plan = _plan(leaf, new_leaf, off, cnt, parents, valid, R)
        dest = np.asarray(part_ops.row_dest(plan, R))
        # dest is a permutation and matches the stable reference order
        assert sorted(dest.tolist()) == list(range(n))
        order = _np_reference_move(leaf, new_leaf, parents)
        inv = np.empty(n, np.int64)
        inv[dest] = np.arange(n)
        np.testing.assert_array_equal(inv, order)
        off2, cnt2 = part_ops.update_tables(
            jnp.asarray(off), jnp.asarray(cnt), jnp.asarray(parents),
            jnp.asarray(new_ids), jnp.asarray(valid), plan)
        off, cnt = np.asarray(off2).copy(), np.asarray(cnt2).copy()
        leaf = new_leaf[order]
        tag = tag[order]
        num_leaves += k
        # invariants: counts sum to n; every leaf's rows contiguous at
        # its table offset; stability (tags increasing within a leaf)
        assert int(cnt[:num_leaves].sum()) == n
        for lf in range(num_leaves):
            rows = np.flatnonzero(leaf == lf)
            assert len(rows) == cnt[lf]
            if len(rows):
                assert rows[0] == off[lf]
                assert rows[-1] == off[lf] + cnt[lf] - 1
                assert np.all(np.diff(tag[rows]) > 0)


def _layout(seed, n=1024, L=15, trips=3, Kb=4):
    """A leaf-ordered layout a few random trips deep, as the grower
    leaves it: (leaf per position, off, cnt, tag per position, leaves)."""
    rng = np.random.default_rng(seed)
    leaf = np.zeros(n, np.int32)
    off = np.zeros(L + 1, np.int32)
    cnt = np.zeros(L + 1, np.int32)
    cnt[0] = n
    tag = np.arange(n, dtype=np.int32)
    num_leaves = 1
    for _ in range(trips):
        parents, new_ids, new_leaf = _random_trip(rng, leaf, cnt,
                                                  num_leaves, L, Kb)
        order = _np_reference_move(leaf, new_leaf, parents)
        leaf, tag = new_leaf[order], tag[order]
        num_leaves += len(parents)
        for lf in range(num_leaves):
            rows = np.flatnonzero(leaf == lf)
            off[lf], cnt[lf] = (rows[0] if len(rows) else 0), len(rows)
    return rng, leaf, off, cnt, tag, num_leaves


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_move_leaves_unsplit_leaves_in_place(seed):
    """After one move every leaf the trip does not split keeps its
    offset, its count and its rows, position for position."""
    rng, leaf, off, cnt, tag, num_leaves = _layout(seed)
    parents, new_ids, new_leaf = _random_trip(rng, leaf, cnt, num_leaves,
                                              15, 2)
    valid = np.ones(len(parents), bool)
    plan = _plan(leaf, new_leaf, off, cnt, parents, valid, 128)
    dest = np.asarray(part_ops.row_dest(plan, 128))
    off2, cnt2 = (np.asarray(t) for t in part_ops.update_tables(
        jnp.asarray(off), jnp.asarray(cnt), jnp.asarray(parents),
        jnp.asarray(new_ids), jnp.asarray(valid), plan))
    unsplit = [lf for lf in range(num_leaves) if lf not in parents]
    assert unsplit
    for lf in unsplit:
        assert (off2[lf], cnt2[lf]) == (off[lf], cnt[lf])
        rows = np.arange(off[lf], off[lf] + cnt[lf])
        np.testing.assert_array_equal(dest[rows], rows)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_split_leaf_children_sit_in_its_span(seed):
    """A split leaf's children sit left then right inside its old span,
    each in source order: the left child keeps the parent's slot and
    offset, the right child starts where the left one ends."""
    rng, leaf, off, cnt, tag, num_leaves = _layout(seed)
    parents, new_ids, new_leaf = _random_trip(rng, leaf, cnt, num_leaves,
                                              15, 3)
    valid = np.ones(len(parents), bool)
    plan = _plan(leaf, new_leaf, off, cnt, parents, valid, 128)
    dest = np.asarray(part_ops.row_dest(plan, 128))
    off2, cnt2 = (np.asarray(t) for t in part_ops.update_tables(
        jnp.asarray(off), jnp.asarray(cnt), jnp.asarray(parents),
        jnp.asarray(new_ids), jnp.asarray(valid), plan))
    inv = np.empty_like(dest)
    inv[dest] = np.arange(len(dest))
    leaf2, tag2 = new_leaf[inv], tag[inv]
    for p, nid in zip(parents, new_ids):
        lo, hi = off[p], off[p] + cnt[p]
        n_left = int(np.sum(new_leaf[lo:hi] == p))
        assert (off2[p], cnt2[p]) == (lo, n_left)
        assert (off2[nid], cnt2[nid]) == (lo + n_left, cnt[p] - n_left)
        assert np.all(leaf2[lo:lo + n_left] == p)
        assert np.all(leaf2[lo + n_left:hi] == nid)
        assert np.all(np.diff(tag2[lo:lo + n_left]) > 0)
        assert np.all(np.diff(tag2[lo + n_left:hi]) > 0)


# split leaves' spans on a 16-block table of 256 rows, and the blocks
# the mover is handed
TOUCHED_CASES = {
    # one leaf inside block 2, one over blocks 5-7
    "inside_and_across": ([600, 1300], [100, 600], 4),
    # two leaves share block 1: it is handed once
    "shared_block": ([300, 400], [100, 200], 2),
    # the root: every block
    "root": ([0], [4096], 16),
    # a lane that splits nothing hands nothing
    "empty_lane": ([0, 2048], [0, 256], 1),
}


@pytest.mark.parametrize("case", sorted(TOUCHED_CASES))
def test_rows_moved_counts_touched_blocks(case):
    """``partition.rows_moved`` counts the rows of the blocks the mover
    is handed, a trip: the blocks that hold a row of a split leaf, each
    once, times the mover's row block."""
    offs, cnts, want = TOUCHED_CASES[case]
    n, R = 4096, 256
    moved = np.zeros(n, bool)
    moved[1::3] = True
    plan = part_ops.plan_split_move(
        jnp.asarray(moved), jnp.asarray(offs, jnp.int32),
        jnp.asarray(cnts, jnp.int32), R)
    assert int(plan.touched) == want
    # the histogram's own block lister agrees
    _, nb = part_ops.span_blocks(jnp.asarray(offs, jnp.int32),
                                 jnp.asarray(cnts, jnp.int32), R,
                                 n // R, n // R)
    assert int(nb) == want
    # a piece a (block, split leaf), so a shared block is walked twice
    live = np.asarray(plan.span) >= 0
    assert live.sum() == sum((o + c - 1) // R - o // R + 1
                             for o, c in zip(offs, cnts) if c)


# (n, R, trips, Kb, seed, right share): a layout a few trips deep, then
# the trip the kernels move
KERNEL_CASES = {
    "root": (2048, 256, 0, 1, 0, None),
    "deep": (4096, 256, 3, 4, 1, None),
    "small_leaves": (2048, 128, 4, 6, 2, None),
    "all_right": (2048, 256, 2, 3, 3, 1.0),
    "all_left": (2048, 256, 2, 3, 4, 0.0),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_move_cols_kernel_matches_xla(case):
    """The two ``partition_move`` passes walking the plan's pieces
    (``compact_rows(..., blocks=)``), with their blend, leave the buffer
    the XLA path's permutation leaves, bit for bit: int8 bins, float32
    values over 40 binades and an int32 leaf channel. In interpret mode
    on the CPU, compiled in TPU mode (LGBM_TPU_TESTS=1)."""
    import contextlib
    import os
    from jax.experimental.pallas import tpu as pltpu
    n, R, trips, Kb, seed, share = KERNEL_CASES[case]
    rng, leaf, off, cnt, _, num_leaves = _layout(seed, n=n, L=31,
                                                 trips=trips, Kb=Kb)
    parents, new_ids, new_leaf = _random_trip(rng, leaf, cnt, num_leaves,
                                              31, Kb)
    if share is not None:
        new_leaf = leaf.copy()
        for p, nid in zip(parents, new_ids):
            new_leaf[leaf == p] = nid if share else p
    # a lane that splits nothing rides along
    parents = np.append(parents, 31).astype(np.int32)
    valid = np.append(np.ones(len(parents) - 1, bool), False)
    plan = _plan(leaf, new_leaf, off, cnt, parents, valid, R)
    bins = rng.integers(-128, 128, size=(16, n)).astype(np.int8)
    vals = (rng.normal(size=(3, n))
            * 10.0 ** rng.integers(-20, 20, size=(3, n))).astype(np.float32)
    vals = np.concatenate([vals, new_leaf[None].astype(np.float32)])
    tpu = os.environ.get("LGBM_TPU_TESTS", "") == "1"
    with (contextlib.nullcontext() if tpu
          else pltpu.force_tpu_interpret_mode()):
        kb, kv = part_ops.move_cols_tpu(jnp.asarray(bins), jnp.asarray(vals),
                                        plan, R)
    xb, xv = part_ops.move_rows_xla(
        [jnp.asarray(bins), jnp.asarray(vals)],
        part_ops.row_dest(plan, R), axis=1)
    np.testing.assert_array_equal(np.asarray(kb), np.asarray(xb))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(xv))
    order = _np_reference_move(leaf, new_leaf, parents[valid])
    np.testing.assert_array_equal(np.asarray(kb), bins[:, order])


def test_grow_tree_rows_moved_is_blocks_handed():
    """The grower's ``move_rows``: whole blocks of the mover's row
    block, fewer than the whole table a trip once the tree has leaves
    the trips do not split."""
    from lightgbm_tpu.learner.serial import GrowConfig, grow_tree
    n, f = 4096, 6
    rng = np.random.default_rng(2)
    bins = jnp.asarray(rng.integers(0, 32, size=(n, f)), jnp.uint8)
    vals = jnp.asarray(np.stack([rng.normal(size=n), np.ones(n),
                                 np.ones(n)], 1), jnp.float32)
    cfg = GrowConfig(num_leaves=31, num_bins=32, rows_per_block=256,
                     leaf_batch=2, min_data_in_leaf=5, partition=True,
                     part_rpb=256)
    t, _ = grow_tree(bins, vals, jnp.full(f, 32, jnp.int32),
                     jnp.zeros(f, bool), jnp.ones(f, bool), cfg)
    moves = float(t["move_calls"])
    rows = float(t["move_rows"])
    assert moves == float(t["hist_calls"]) - 1 > 2
    assert rows % 256 == 0
    assert n <= rows < moves * n


def _random_spans(seed, n=4096, R=256, K=6):
    """K disjoint children of a random leaf layout, some lanes empty."""
    rng = np.random.default_rng(seed)
    cuts = np.sort(rng.choice(np.arange(1, n), size=2 * K, replace=False))
    edges = np.concatenate([[0], cuts, [n]])
    pick = rng.choice(len(edges) - 1, size=K, replace=False)
    offs = edges[pick]
    cnts = edges[pick + 1] - edges[pick]
    cnts[rng.random(K) < 0.3] = 0            # lanes that hold no leaf
    return n, R, offs, cnts


SPAN_CASES = {
    "random_0": _random_spans(0),
    "random_1": _random_spans(1),
    "random_2": _random_spans(2, n=8192, R=512, K=8),
    # two children share block 1, a third starts where it ends
    "adjacent_share_a_block": (1024, 256, [300, 400, 512], [100, 112, 40]),
    # a child ending at the last row, one starting at row 0
    "ends_at_the_last_row": (1024, 256, [1000, 0], [24, 3]),
    # every lane empty (-1): nothing is listed
    "empty_lanes": (1024, 256, [0, 512, 7], [0, 0, 0]),
    # more blocks than the table holds: nb passes NB_max
    "overflow": (4096, 256, [0, 2048], [2000, 2048]),
}


@pytest.mark.parametrize("case", sorted(SPAN_CASES))
def test_span_blocks_cover_each_child_row_once(case):
    """The block table lists, sorted and each once, exactly the blocks
    that hold a row of an elected child; its tail repeats the last real
    block, so a padded grid step asks for the block already fetched."""
    n, R, offs, cnts = SPAN_CASES[case]
    offs, cnts = np.asarray(offs, np.int32), np.asarray(cnts, np.int32)
    n_blocks = n // R
    nb_max = part_ops.span_block_cap(n, R, len(offs))
    if case == "overflow":
        nb_max = 8          # as a trip under bagging can: counts lie
    blk, nb = part_ops.span_blocks(jnp.asarray(offs), jnp.asarray(cnts),
                                   R, n_blocks, nb_max)
    blk, nb = np.asarray(blk), int(nb)
    want = sorted({r // R for o, c in zip(offs, cnts)
                   for r in range(o, o + c)})
    assert nb == len(want)
    assert blk.shape == (nb_max,) and blk.dtype == np.int32
    if case == "overflow":
        assert nb > nb_max
        np.testing.assert_array_equal(blk, want[:nb_max])
        return
    assert nb <= nb_max
    np.testing.assert_array_equal(blk[:nb], want)
    # every row of every child lies in exactly one listed block
    listed = blk[:nb]
    for o, c in zip(offs, cnts):
        for r in range(o, o + c):
            assert np.sum(listed == r // R) == 1
    np.testing.assert_array_equal(blk[nb:], blk[nb - 1] if nb else 0)


# ---------------------------------------------------------------------------
# grow_tree: partitioned == masked, bit-for-bit
# ---------------------------------------------------------------------------

def _grow_pair(cfg_kw, n=2048, f=6, seed=0):
    from lightgbm_tpu.learner.serial import GrowConfig, grow_tree
    rng = np.random.default_rng(seed)
    bins = jnp.asarray(rng.integers(0, 32, size=(n, f)), jnp.uint8)
    g = rng.normal(size=n).astype(np.float32)
    vals = jnp.asarray(np.stack([g, np.ones(n, np.float32),
                                 np.ones(n, np.float32)], axis=1))
    nb = jnp.full(f, 32, jnp.int32)
    hn = jnp.zeros(f, bool)
    al = jnp.ones(f, bool)
    base = dict(num_leaves=31, num_bins=32, rows_per_block=256,
                min_data_in_leaf=5)
    base.update(cfg_kw)
    cfg = GrowConfig(**base)
    outs = []
    for part in (False, True):
        t, lid = grow_tree(bins, vals, nb, hn, al,
                           dataclasses.replace(cfg, partition=part))
        outs.append((jax.tree.map(np.asarray, t), np.asarray(lid)))
    return outs


@pytest.mark.parametrize("cfg_kw", [
    {"leaf_batch": 1},
    {"leaf_batch": 8},
    {"leaf_batch": 4, "max_depth": 4},
])
def test_grow_tree_partitioned_bit_identical(cfg_kw):
    (t0, lid0), (t1, lid1) = _grow_pair(cfg_kw)
    for k in t0:
        if k in ("hist_rows", "route_rows"):
            continue
        np.testing.assert_array_equal(t0[k], t1[k], err_msg=k)
    np.testing.assert_array_equal(lid0, lid1)
    # the structural win: the partitioned tree scanned fewer rows
    assert int(t1["hist_rows"]) <= int(t0["hist_rows"])
    # and its histograms read the partition's own ids, so the table is
    # routed once, after the loop, where the masked path routes it at
    # every trip (the ids above are equal all the same)
    trips = int(t0["hist_calls"]) - 1
    assert float(t1["route_rows"]) == len(lid1)
    assert float(t0["route_rows"]) == trips * len(lid0)


def test_grow_tree_partition_falls_back_when_counts_lie():
    """Under weights the count channel is not a row count: the smaller
    child by count can hold most of the rows, list more blocks than the
    table holds, and the trip takes the masked scan over the partition.
    Trees stay the masked path's (the sums are exact in any order)."""
    from lightgbm_tpu.learner.serial import GrowConfig, grow_tree
    n, f = 2048, 4
    rng = np.random.default_rng(4)
    bins = rng.integers(0, 32, size=(n, f)).astype(np.uint8)
    few = bins[:, 0] < 4                   # an eighth of the rows
    g = np.where(few, 2.0, -1.0) + rng.integers(-1, 2, size=n)
    # the many rows weigh 1/16: their child is the smaller by count
    count = np.where(few, 1.0, 1 / 16)
    vals = jnp.asarray(np.stack([g, np.ones(n), count], 1), jnp.float32)
    cfg = GrowConfig(num_leaves=8, num_bins=32, rows_per_block=128,
                     leaf_batch=1, min_data_in_leaf=1)
    args = (jnp.asarray(bins), vals, jnp.full(f, 32, jnp.int32),
            jnp.zeros(f, bool), jnp.ones(f, bool))
    t0, lid0 = grow_tree(*args, cfg)
    t1, lid1 = grow_tree(*args, dataclasses.replace(cfg, partition=True))
    assert float(t1["full_scans"]) >= 1
    for k in t0:
        if k not in ("hist_rows", "route_rows"):
            np.testing.assert_array_equal(t0[k], t1[k], err_msg=k)
    np.testing.assert_array_equal(lid0, lid1)
    # a trip that fell back read the whole source: the root's rows and
    # each such trip's are all counted
    assert float(t1["hist_rows"]) >= n * (1 + float(t1["full_scans"]))


# ---------------------------------------------------------------------------
# engine: model-text equality across the interop matrix
# ---------------------------------------------------------------------------

def _data(n=4000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X @ rng.normal(size=f)
         + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
    return X, y


def _model_text(X, y, extra, rounds=6):
    params = {"objective": "binary", "num_leaves": 31, "verbosity": -1,
              "learning_rate": 0.3}
    params.update(extra)
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=rounds)
    return bst, bst.model_to_string()


QUANT_MATRIX = [
    ("pool", {"use_quantized_grad": True}),
    ("goss", {"data_sample_strategy": "goss", "top_rate": 0.3,
              "other_rate": 0.2, "use_quantized_grad": True}),
    ("goss_compact", {"data_sample_strategy": "goss", "top_rate": 0.3,
                      "other_rate": 0.2, "use_quantized_grad": True,
                      "tpu_goss_compact": True}),
]


@pytest.mark.parametrize("name,extra", QUANT_MATRIX,
                         ids=[m[0] for m in QUANT_MATRIX])
def test_partition_bit_exact_quantized(name, extra):
    """Quantized gradients make histogram sums integer-valued, so the
    span accumulation order cannot perturb them: model text must match
    the masked path byte-for-byte."""
    X, y = _data()
    _, m0 = _model_text(X, y, {**extra, "tpu_hist_partition": "false"})
    _, m1 = _model_text(X, y, {**extra, "tpu_hist_partition": "true"})
    assert m0 == m1


@pytest.mark.parametrize("f", [8, 40], ids=["one_block", "feature_blocked"])
def test_partition_pallas_path_bit_exact(pallas_path, f):
    """The Pallas path (interpret mode here): the histogram kernel walks
    the block table over the partition's own feature-major buffers, and
    the model is the masked path's byte for byte. At 40 columns of 256
    bins the kernel's grid is feature-blocked, and the partition carries
    its zero columns past the table's own."""
    X, y = _data(n=6000 if f == 8 else 3000, f=f)
    extra = {"use_quantized_grad": True, "tpu_leaf_batch": 2,
             "tpu_metrics": True}
    obs.enable(metrics=True)
    b0, m0 = _model_text(X, y, {**extra, "tpu_hist_partition": "false"},
                         rounds=2)
    masked = obs.registry().counter("hist.cols_scanned", sampled=0).value
    obs.reset()
    b1, m1 = _model_text(X, y, {**extra, "tpu_hist_partition": "true"},
                         rounds=2)
    assert b1.engine.use_pallas and b1.engine.hist_partition
    assert m0 == m1
    reg = obs.registry()
    part = reg.counter("hist.cols_scanned", sampled=0).value
    assert 0 < part < masked
    # the roots' rows, then whole blocks of the grower's row block (a
    # feature-blocked grid caps it at 2,048)
    R = math.gcd(b1.engine.rows_per_block, 4096 if f == 8 else 2048)
    assert (part - 2 * b1.engine.data.n_pad) % R == 0


def test_partition_close_under_f32():
    """f32 histograms may differ in accumulation order only: the GOSS
    compaction closeness contract applies."""
    X, y = _data(seed=5)
    b0, _ = _model_text(X, y, {"tpu_hist_partition": "false"})
    b1, _ = _model_text(X, y, {"tpu_hist_partition": "true"})
    np.testing.assert_allclose(b1.predict(X), b0.predict(X),
                               rtol=2e-2, atol=2e-3)


def test_partition_with_forced_splits(tmp_path):
    """Forced-split rounds bypass the scan (pool gathers) but the
    partition must keep routing their children; the whole model still
    matches the masked path exactly under quantized gradients."""
    rng = np.random.default_rng(13)
    X = rng.uniform(-1, 1, size=(3000, 4))
    y = (3.0 * X[:, 0] + 0.2 * X[:, 1]
         + rng.normal(scale=0.1, size=3000) > 0).astype(np.float64)
    fs = str(tmp_path / "forced.json")
    with open(fs, "w") as f:
        json.dump({"feature": 1, "threshold": 0.25,
                   "left": {"feature": 2, "threshold": -0.5}}, f)
    extra = {"forcedsplits_filename": fs, "use_quantized_grad": True}
    b0, m0 = _model_text(X, y, {**extra, "tpu_hist_partition": "false"},
                         rounds=4)
    _, m1 = _model_text(X, y, {**extra, "tpu_hist_partition": "true"},
                        rounds=4)
    assert m0 == m1
    used = b0.engine.train_set.used_features
    for t in b0.engine.models:
        assert used[int(np.asarray(t.split_feature)[0])] == 1


def test_partition_multiclass_quantized():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(3000, 8))
    y = ((X[:, 0] > 0).astype(int)
         + (X[:, 1] > 0.3).astype(int)).astype(np.float64)
    extra = {"objective": "multiclass", "num_class": 3,
             "use_quantized_grad": True}
    X2, y2 = X, y
    params0 = {**extra, "tpu_hist_partition": "false"}
    params1 = {**extra, "tpu_hist_partition": "true"}
    _, m0 = _model_text(X2, y2, params0, rounds=4)
    _, m1 = _model_text(X2, y2, params1, rounds=4)
    assert m0 == m1


@pytest.mark.parametrize("learner", ["data", "voting", "feature"])
def test_partition_parallel_learners(learner):
    """All three parallel learners keep per-shard partitions (tables
    and block tables are local; histogram reductions stay outside the
    table's fallback cond) — quantized trees match the masked path
    bit-for-bit on the 8-device CPU mesh."""
    X, y = _data(n=3072, seed=9)
    extra = {"tree_learner": learner, "min_data_in_leaf": 5,
             "use_quantized_grad": True}
    _, m0 = _model_text(X, y, {**extra, "tpu_hist_partition": "false"},
                        rounds=4)
    _, m1 = _model_text(X, y, {**extra, "tpu_hist_partition": "true"},
                        rounds=4)
    assert m0 == m1


# ---------------------------------------------------------------------------
# observability + compile behavior
# ---------------------------------------------------------------------------

def test_rows_scanned_metric():
    """hist.cols_scanned: masked = n_pad x realized rounds; the
    partitioned path must record strictly fewer, the roots' rows and
    then whole blocks, and no trip overflows the table.
    (leaf_batch is kept small so the table is shorter than the source
    at this test size.)"""
    X, y = _data(n=6000)
    obs.enable(metrics=True)
    obs.reset()
    extra = {"tpu_leaf_batch": 2, "tpu_metrics": True}
    b0, _ = _model_text(X, y, {**extra, "tpu_hist_partition": "false"},
                        rounds=3)
    masked = obs.registry().counter("hist.cols_scanned", sampled=0).value
    obs.reset()
    b1, _ = _model_text(X, y, {**extra, "tpu_hist_partition": "true"},
                        rounds=3)
    part = obs.registry().counter("hist.cols_scanned", sampled=0).value
    assert masked > 0 and part > 0
    assert part < masked
    n_pad = b0.engine.data.n_pad
    # masked path scans the whole padded buffer every round
    assert masked % n_pad == 0
    # the partitioned path: each tree's root once, then the blocks read
    reg = obs.registry()
    assert (part - 3 * n_pad) % b1.engine.rows_per_block == 0
    assert reg.counter("partition.full_scans", sampled=0).value == 0


def test_partition_block_table_no_recompiles():
    """The block table has one static length: once the step program is
    built, further same-shape training compiles ZERO fresh programs —
    children shrinking round over round list fewer blocks inside the
    one compiled program, never new specializations."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.debug import CompileWatch
    X, y = _data(n=2500, seed=11)
    cfg = Config({"objective": "binary", "num_leaves": 31,
                  "verbosity": -1, "tpu_leaf_batch": 2,
                  "tpu_hist_partition": "true",
                  "use_quantized_grad": True})
    eng = GBDT(cfg, lgb.Dataset(X, label=y))
    eng.train_chunk(3)
    with CompileWatch("warm partitioned training") as w:
        eng.train_chunk(3)
    w.assert_compiles(0)
