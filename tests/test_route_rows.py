"""The table's rows routed once, after the grower's loop (ops/route.py).

Under GOSS's compact buffer ``grow_tree`` leaves the table's own leaf
ids out of its loop and routes them once through the finished tree. The
ids it returns have to be the ids the in-loop pass gives (the same call
with ``lazy`` handed, which keeps that pass) and the leaf an independent
traversal of the same tree reaches (``tree_predict_binned``, whose leaf
numbering is the grower's); the Pallas kernel, in interpret mode here,
has to agree with the plain loop over nodes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

import lightgbm_tpu as lgb
from lightgbm_tpu.learner.serial import GrowConfig, grow_tree
from lightgbm_tpu.ops.predict import tree_predict_binned
from lightgbm_tpu.ops.route import route_nodes, route_rows, route_rows_xla

RPB = 256     # the grower's histogram block in these cases


def _case(name):
    """(bins [n, F] uint8, gradients [n], num_bin [F], has_nan [F],
    is_cat [F] or None, GrowConfig fields, forced table or None)."""
    rng = np.random.default_rng(11)
    n, F, B = 6 * RPB, 6, 64
    cfg = dict(num_leaves=31, num_bins=B, leaf_batch=8,
               min_data_in_leaf=5, min_sum_hessian_in_leaf=0.0)
    is_cat = forced = None
    nb = np.full(F, B, np.int32)
    hn = np.zeros(F, bool)
    if name == "one_leaf":
        cfg["min_data_in_leaf"] = n          # no split can hold it
    if name == "leaves_300":
        n = 16 * RPB
        cfg.update(num_leaves=300, leaf_batch=32, min_data_in_leaf=1)
    if name == "ragged_rows":
        n = 11 * RPB                         # 2,816: no multiple of 2,048
    if name == "set_splits":
        B = 256
        cfg.update(num_bins=B, has_categorical=True, cat_positions=(0, 1),
                   min_data_per_group=5, cat_smooth=1.0,
                   max_cat_threshold=128, min_data_in_leaf=2)
        n = 16 * RPB
        nb = np.full(F, B, np.int32)
        is_cat = np.arange(F) < 2
    bins = rng.integers(0, nb[0], size=(n, F)).astype(np.uint8)
    if name == "nan_bins":
        # a quarter of every column is missing; which side the NaN bin
        # takes is the search's choice, so both default_lefts appear
        hn[:] = True
        bins[rng.random((n, F)) < 0.25] = B - 1
    effect = rng.normal(size=(F, B))
    score = sum(effect[f, bins[:, f]] for f in range(3))
    if name == "nan_bins":
        score = score + np.where(bins[:, 0] == B - 1, 3.0, 0.0) \
            - np.where(bins[:, 1] == B - 1, 3.0, 0.0)
    g = (score + rng.normal(scale=0.3, size=n)).astype(np.float32)
    if name == "forced":
        cfg["n_forced"] = 1
        forced = (jnp.asarray([-1], jnp.int32), jnp.asarray([False]),
                  jnp.asarray([4], jnp.int32), jnp.asarray([20], jnp.int32),
                  jnp.asarray([False]),
                  jnp.zeros((1, (B + 31) // 32), jnp.uint32))
    return bins, g, nb, hn, is_cat, cfg, forced


CASES = ["numeric", "nan_bins", "set_splits", "forced", "one_leaf",
         "leaves_300", "ragged_rows"]


def _grow(name, lazy):
    """Grow one tree on a compact buffer of every third row and its
    neighbours; ``lazy`` hands an all-acquired matrix and zero
    penalties, which keeps the in-loop pass and changes no split."""
    bins, g, nb, hn, is_cat, cfg_kw, forced = _case(name)
    n, F = bins.shape
    keep = np.flatnonzero((np.arange(n) % 3 == 0)
                          | (np.random.default_rng(5).random(n) < 0.1))
    n_c = -(-len(keep) // RPB) * RPB
    bins_c = np.zeros((n_c, F), np.uint8)
    bins_c[:len(keep)] = bins[keep]
    vals_c = np.zeros((n_c, 3), np.float32)
    vals_c[:len(keep)] = np.stack(
        [g[keep], np.ones(len(keep)), np.ones(len(keep))], axis=1)
    cfg = GrowConfig(rows_per_block=RPB, hist_compact=True,
                     has_cegb_lazy=lazy, **cfg_kw)
    tree, ids = grow_tree(
        jnp.asarray(bins), jnp.asarray(vals_c), jnp.asarray(nb),
        jnp.asarray(hn), jnp.ones(F, bool), cfg,
        is_cat=None if is_cat is None else jnp.asarray(is_cat),
        compact=(jnp.asarray(bins_c), None, jnp.asarray(vals_c)),
        forced=forced,
        lazy=((jnp.ones((n, F), bool), jnp.zeros(F, jnp.float32))
              if lazy else None))
    return (bins, nb, hn), jax.tree.map(np.asarray, tree), np.asarray(ids)


def _node_leaf(tree):
    """The leaf each node split, from the finished tree: the left child
    keeps the split leaf's id, so it is the leaf a walk down the left
    children ends in."""
    left = tree["left_child"]
    out = np.zeros(len(left), np.int32)
    for j in range(int(tree["num_leaves"]) - 1):
        k = j
        while k >= 0:
            k = left[k]
        out[j] = -k - 1
    return out


@pytest.fixture(scope="module", params=CASES)
def grown(request):
    table, tree, ids = _grow(request.param, lazy=False)
    return request.param, table, tree, ids


def test_ids_after_the_loop_are_the_in_loop_ids(grown):
    name, (bins, nb, hn), tree, ids = grown
    _, tree_in, ids_in = _grow(name, lazy=True)
    # the same tree either way, so the same ids are owed
    for k in tree:
        if k not in ("route_rows", "route_final"):
            np.testing.assert_array_equal(tree[k], tree_in[k], err_msg=k)
    np.testing.assert_array_equal(ids, ids_in)
    # the table was routed once after the loop, against once a trip
    trips, n = int(tree["hist_calls"]) - 1, len(bins)
    assert int(tree["route_final"]) == 1 and int(tree_in["route_final"]) == 0
    assert tree_in["route_rows"] - tree["route_rows"] == (trips - 1) * n


def test_ids_are_the_leaves_an_independent_traversal_reaches(grown):
    name, (bins, nb, hn), tree, ids = grown
    want = {"one_leaf": 1, "leaves_300": 257}.get(name, 16)
    assert int(tree["num_leaves"]) >= want, "the case grew too small a tree"
    if name == "nan_bins":
        live = tree["default_left"][:int(tree["num_leaves"]) - 1]
        assert live.any() and not live.all()
    if name == "set_splits":
        live = np.arange(len(tree["is_cat"])) < int(tree["num_leaves"]) - 1
        assert (tree["is_cat"] & live).sum() >= 4
        assert tree["cat_bitset"].shape[1] == 8
        assert (tree["cat_bitset"][tree["is_cat"] & live, 7] != 0).any()
    if name == "forced":
        assert tree["split_feature"][0] == 4 and tree["threshold_bin"][0] == 20
    _, leaf = tree_predict_binned(
        {k: jnp.asarray(v) for k, v in tree.items()}, jnp.asarray(bins),
        jnp.asarray(nb), jnp.asarray(hn))
    np.testing.assert_array_equal(ids, np.asarray(leaf))
    assert ids.max() == int(tree["num_leaves"]) - 1


def test_kernel_in_interpret_mode_is_the_loop_over_nodes(grown):
    name, (bins, nb, hn), tree, ids = grown
    nodes = route_nodes(
        int(tree["num_leaves"]) - 1, *(jnp.asarray(tree[k]) for k in (
            "split_feature", "threshold_bin", "default_left")),
        jnp.asarray(_node_leaf(tree)), jnp.asarray(nb), jnp.asarray(hn),
        is_cat=jnp.asarray(tree["is_cat"]) if "is_cat" in tree else None,
        cat_bitset=(jnp.asarray(tree["cat_bitset"])
                    if "cat_bitset" in tree else None))
    np.testing.assert_array_equal(
        np.asarray(route_rows_xla(jnp.asarray(bins), nodes)), ids)
    bins_t = jnp.asarray(np.ascontiguousarray(bins.T).view(np.int8))
    with pltpu.force_tpu_interpret_mode():
        # blocks of 2,048 rows: the last one ragged where the row count
        # is no multiple of it (1,536, 2,816 and 4,096 rows here)
        got = route_rows(bins_t, nodes, rows_per_block=2048)
        whole = route_rows(bins_t, nodes)       # one block for the table
    np.testing.assert_array_equal(np.asarray(got), ids)
    np.testing.assert_array_equal(np.asarray(whole), ids)


def test_goss_models_are_the_masked_paths(pallas_path):
    """12 rounds of GOSS on the compact buffer, the table routed after
    the loop by the kernel: the model text of the masked path
    (``tpu_goss_compact=false``: every row in the loop), and the
    counters say how often the table was walked. Rounding to nearest:
    a stochastic draw belongs to a position of the buffer, so under it
    the two paths never grew the same forest."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.ops.compact import compaction_out_cols
    rng = np.random.default_rng(2)
    n = 20_000
    X = rng.normal(size=(n, 6))
    X[rng.random(X.shape) < 0.1] = np.nan
    y = (np.nan_to_num(X) @ rng.normal(size=6)
         + rng.normal(scale=0.5, size=n) > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "data_sample_strategy": "goss", "top_rate": 0.2,
              "other_rate": 0.1, "learning_rate": 0.3,
              "use_quantized_grad": True, "stochastic_rounding": False,
              "max_bin": 63,
              "tpu_leaf_batch": 4}
    texts = {}
    for compact in (False, True):
        obs.registry().reset()
        bst = lgb.train({**params, "tpu_goss_compact": compact},
                        lgb.Dataset(X, label=y), num_boost_round=12)
        texts[compact] = bst.model_to_string()
        reg = obs.registry()
        routed = reg.get("partition.rows_routed", sampled=1).value
        table = reg.get("partition.rows_table", sampled=1).value
        finals = reg.get("partition.final_routes", sampled=1).value
        eng = bst._engine
        n_pad, rpb = eng.data.n_pad, eng.rows_per_block
        trees = table / n_pad               # the sampled rounds' trees
        trips = reg.get("hist.calls", sampled=1).value - trees
        if compact:
            assert eng._use_goss_compact
            # the buffer at every loop trip, the table once a tree
            n_sub = compaction_out_cols(
                int(np.ceil(n_pad * 0.3)) + 8192, min(1024, rpb), rpb)
            assert finals == trees
            assert routed == trips * n_sub + trees * n_pad
        else:
            assert finals == 0
            assert routed == trips * n_pad
    assert texts[True] == texts[False]
