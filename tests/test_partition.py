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
# unit: the stable front/back move + table updates vs a numpy reference
# ---------------------------------------------------------------------------

def _np_reference_move(leaf, parents, rights):
    """Reference semantics in plain numpy: rows whose leaf id is a
    right child of this round move stably to the back; everything else
    packs stably to the front."""
    moved = np.isin(leaf, rights)
    order = np.concatenate([np.flatnonzero(~moved),
                            np.flatnonzero(moved)])
    return order, int((~moved).sum())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_move_and_tables_invariants(seed):
    """Property-style pin over several random split batches: dest is a
    permutation, per-leaf spans stay contiguous/disjoint, offsets match
    the (offset, count) tables, counts sum to n, and within-leaf source
    order is preserved (stability)."""
    rng = np.random.default_rng(seed)
    n, L, Kb = 512, 31, 4
    leaf = np.zeros(n, np.int32)
    off = np.zeros(L + 1, np.int32)
    cnt = np.zeros(L + 1, np.int32)
    cnt[0] = n
    # a source tag per row to verify stability across rounds
    tag = np.arange(n, dtype=np.int32)
    num_leaves = 1
    for _ in range(6):
        active = [lf for lf in range(num_leaves) if cnt[lf] > 1]
        if not active or num_leaves >= L - Kb:
            break
        k = min(Kb, len(active), L - num_leaves)
        parents = np.asarray(rng.choice(active, size=k, replace=False),
                             np.int32)
        new_ids = np.arange(num_leaves, num_leaves + k, dtype=np.int32)
        valid = np.ones(k, bool)
        # route a random subset of each parent's rows to its right child
        new_leaf = leaf.copy()
        for p, nid in zip(parents, new_ids):
            rows = np.flatnonzero(leaf == p)
            take = rng.random(len(rows)) < rng.uniform(0.2, 0.8)
            new_leaf[rows[take]] = nid
        moved = new_leaf != leaf
        dest, n_front, cum = part_ops.plan_split_move(
            jnp.asarray(moved))
        dest = np.asarray(dest)
        n_front = int(n_front)
        # dest is a permutation and matches the stable reference order
        assert sorted(dest.tolist()) == list(range(n))
        order, ref_front = _np_reference_move(new_leaf, parents,
                                              new_ids.tolist())
        assert n_front == ref_front
        inv = np.empty(n, np.int64)
        inv[dest] = np.arange(n)
        np.testing.assert_array_equal(inv, order)
        off2, cnt2 = part_ops.update_tables(
            jnp.asarray(off), jnp.asarray(cnt), cum,
            jnp.asarray(n_front, jnp.int32), jnp.asarray(parents),
            jnp.asarray(new_ids), jnp.asarray(valid))
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
