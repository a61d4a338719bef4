"""Serial tree learner: structural and recovery tests."""
import numpy as np
import jax.numpy as jnp

from lightgbm_tpu.learner.serial import GrowConfig, grow_tree
from lightgbm_tpu.ops.predict import tree_predict_binned


def _grow(bins, g, h, cfg, mask=None):
    n = bins.shape[0]
    if mask is None:
        mask = np.ones(n, dtype=np.float32)
    vals = np.stack([g * mask, h * mask, mask], axis=1).astype(np.float32)
    F = bins.shape[1]
    num_bin = np.full(F, int(bins.max()) + 1, dtype=np.int32)
    has_nan = np.zeros(F, dtype=bool)
    tree, leaf_id = grow_tree(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(num_bin),
        jnp.asarray(has_nan), jnp.ones(F, dtype=bool), cfg)
    return ({k: np.asarray(v) for k, v in tree.items()},
            np.asarray(leaf_id), num_bin, has_nan)


def test_perfect_split_recovery():
    # one feature perfectly separates the gradient signal
    n = 512
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 8, size=(n, 3)).astype(np.uint8)
    g = np.where(bins[:, 1] <= 3, -1.0, 1.0).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    cfg = GrowConfig(num_leaves=2, min_data_in_leaf=1, num_bins=8,
                     rows_per_block=256, min_sum_hessian_in_leaf=0.0)
    tree, leaf_id, _, _ = _grow(bins, g, h, cfg)
    assert int(tree["num_leaves"]) == 2
    assert int(tree["split_feature"][0]) == 1
    assert int(tree["threshold_bin"][0]) == 3
    # left rows got -1 grads -> positive leaf value
    assert tree["leaf_value"][0] > 0
    assert tree["leaf_value"][1] < 0
    np.testing.assert_array_equal(leaf_id, np.where(bins[:, 1] <= 3, 0, 1))


def test_leaf_counts_partition_rows():
    n = 1024
    rng = np.random.default_rng(1)
    bins = rng.integers(0, 32, size=(n, 6)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    cfg = GrowConfig(num_leaves=15, min_data_in_leaf=5, num_bins=32,
                     rows_per_block=256)
    tree, leaf_id, _, _ = _grow(bins, g, h, cfg)
    nl = int(tree["num_leaves"])
    counts = np.bincount(leaf_id, minlength=cfg.num_leaves)
    np.testing.assert_array_equal(counts[:nl],
                                  tree["leaf_count"][:nl].astype(np.int64))
    assert counts[nl:].sum() == 0
    assert counts.sum() == n
    # every used leaf respects min_data_in_leaf
    assert counts[:nl].min() >= 5


def test_leaf_id_matches_tree_traversal():
    n = 2048
    rng = np.random.default_rng(2)
    bins = rng.integers(0, 16, size=(n, 4)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    cfg = GrowConfig(num_leaves=31, min_data_in_leaf=2, num_bins=16,
                     rows_per_block=512)
    tree, leaf_id, num_bin, has_nan = _grow(bins, g, h, cfg)
    dev_tree = {k: jnp.asarray(v) for k, v in tree.items()}
    _, leaf_via_tree = tree_predict_binned(
        dev_tree, jnp.asarray(bins), jnp.asarray(num_bin),
        jnp.asarray(has_nan))
    np.testing.assert_array_equal(leaf_id, np.asarray(leaf_via_tree))


def test_max_depth_respected():
    n = 1024
    rng = np.random.default_rng(3)
    bins = rng.integers(0, 16, size=(n, 4)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    cfg = GrowConfig(num_leaves=31, max_depth=3, min_data_in_leaf=1,
                     num_bins=16, rows_per_block=256)
    tree, _, _, _ = _grow(bins, g, h, cfg)
    # depth-3 binary tree has at most 8 leaves
    assert int(tree["num_leaves"]) <= 8


def test_gain_monotone_decreasing_split_order():
    n = 2048
    rng = np.random.default_rng(4)
    bins = rng.integers(0, 16, size=(n, 4)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    cfg = GrowConfig(num_leaves=15, min_data_in_leaf=1, num_bins=16,
                     rows_per_block=512)
    tree, _, _, _ = _grow(bins, g, h, cfg)
    nl = int(tree["num_leaves"])
    gains = tree["split_gain"][:nl - 1]
    # every executed split must have strictly positive gain (the stop
    # criterion); note best-first does NOT imply globally decreasing gains
    # (a child's split can out-gain its parent's)
    assert np.all(gains > 0)


def test_bagging_mask_excludes_rows():
    n = 512
    rng = np.random.default_rng(5)
    bins = rng.integers(0, 8, size=(n, 2)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    mask = np.zeros(n, dtype=np.float32)
    mask[:256] = 1.0
    cfg = GrowConfig(num_leaves=7, min_data_in_leaf=1, num_bins=8,
                     rows_per_block=256)
    tree, leaf_id, _, _ = _grow(bins, g, h, cfg, mask=mask)
    nl = int(tree["num_leaves"])
    # leaf counts only count masked-in rows
    assert tree["leaf_count"][:nl].sum() == 256
    # but all rows get routed to leaves
    assert leaf_id.shape[0] == n
