"""GOSS physical row compaction (tpu_goss_compact).

The reference's GOSS trains each tree on the sampled subset only
(goss.hpp bag_data_indices_); the default masked formulation here scans
every row with zero weights. Compaction gathers the sampled rows into a
fixed-size buffer — the SAME sample (same RNG stream), so models must
match the masked path up to float accumulation order.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=6000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X @ rng.normal(size=f) + rng.normal(scale=0.5, size=n) > 0)
    return X, y.astype(float)


def _train(compact, n_iter=12, extra=None):
    X, y = _data()
    params = {"objective": "binary", "num_leaves": 15,
              "data_sample_strategy": "goss", "learning_rate": 0.5,
              "top_rate": 0.2, "other_rate": 0.1, "verbosity": -1,
              "tpu_goss_compact": compact}
    params.update(extra or {})
    bst = lgb.train(params, lgb.Dataset(X, label=y),
                    num_boost_round=n_iter)
    return bst, X, y


def test_compact_matches_masked_goss():
    b_mask, X, y = _train(False)
    b_comp, _, _ = _train(True)
    pm = b_mask.predict(X)
    pc = b_comp.predict(X)
    # identical sample; only histogram accumulation order differs
    np.testing.assert_allclose(pc, pm, rtol=2e-2, atol=2e-3)
    # quality must be preserved, not just close pointwise
    from lightgbm_tpu.metric import AUCMetric
    from lightgbm_tpu.config import Config
    cfg = Config({"objective": "binary"})
    am = AUCMetric(cfg).eval(pm, y, None)[0][1]
    ac = AUCMetric(cfg).eval(pc, y, None)[0][1]
    assert abs(am - ac) < 5e-3


def test_compact_with_multiclass_and_quantized():
    rng = np.random.default_rng(3)
    X = rng.normal(size=(4000, 8))
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0.3).astype(int)
    params = {"objective": "multiclass", "num_class": 3,
              "num_leaves": 15, "data_sample_strategy": "goss",
              "learning_rate": 0.5, "verbosity": -1,
              "use_quantized_grad": True,
              "tpu_goss_compact": True}
    bst = lgb.train(params, lgb.Dataset(X, label=y.astype(float)),
                    num_boost_round=8)
    pred = bst.predict(X)
    assert pred.shape == (4000, 3)
    assert np.isfinite(pred).all()
    assert (pred.argmax(1) == y).mean() > 0.7


def test_compact_engine_flag_and_fallbacks():
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    # large enough that the compacted buffer (sampled rows + write
    # slack) genuinely shrinks the scan
    X, y = _data(20000, 6)
    ds = lgb.Dataset(X, label=y)
    eng = GBDT(Config({"objective": "binary",
                       "data_sample_strategy": "goss",
                       "tpu_goss_compact": True, "verbosity": -1}), ds)
    assert eng._use_goss_compact
    # linear trees force the masked path (leaf refit needs full rows)
    ds2 = lgb.Dataset(X, label=y, params={"linear_tree": True})
    eng2 = GBDT(Config({"objective": "binary", "linear_tree": True,
                        "data_sample_strategy": "goss",
                        "tpu_goss_compact": True, "verbosity": -1}), ds2)
    assert not eng2._use_goss_compact
    # tiny datasets: the buffer bound exceeds the data -> masked path
    # (round-4 guard; the kernel's write windows can then never clamp)
    Xs, ys = _data(2000, 6)
    eng3 = GBDT(Config({"objective": "binary",
                        "data_sample_strategy": "goss",
                        "tpu_goss_compact": True, "verbosity": -1}),
                lgb.Dataset(Xs, label=ys))
    assert not eng3._use_goss_compact


def _tied_table(n):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, 4))
    # many duplicated rows -> tied gradients/hessians
    X[n // 2 - 48:] = X[:n - (n // 2 - 48)]
    y = (X[:, 0] > 0).astype(float)
    return X, y


def _goss_oracle(metric, valid, u, k_top, k_rand):
    """goss_masks as it was before the counting select, in numpy: sort,
    read the threshold, break its ties by row index, then the k
    smallest draws among the rest the same way."""
    n = len(metric)
    thresh = np.sort(metric)[np.clip(n - k_top, 0, n - 1)]
    above = (metric > thresh) & valid
    tie = (metric == thresh) & valid
    is_top = above | (tie & (np.cumsum(tie) <= k_top - above.sum()))
    rest = valid & ~is_top
    k_cap = int(min(k_rand, max(valid.sum() - k_top, 1)))
    u = np.where(rest, u, np.float32(np.inf))
    u_thresh = np.sort(u)[np.clip(k_cap - 1, 0, n - 1)]
    strictly = rest & (u < u_thresh)
    at_t = rest & (u == u_thresh)
    picked = (strictly | (at_t & (np.cumsum(at_t) <= k_cap - strictly.sum()))
              ) & (k_cap > 0)
    return is_top, picked


@pytest.mark.parametrize("step,n,top_rate,other_rate", [
    ("masked", 4096, 0.25, 0.15),
    ("compact", 32768, 0.25, 0.15),
    ("masked", 4096, 0.7, 0.3),        # rates summing to 1: every row kept
    ("masked", 4096, 0.999, 0.3),      # k_rand beyond the rest: capped
    ("masked", 4096, 1.0, 0.0),        # k_top = n (the jnp.sort branch)
    ("masked", 4096, 0.0, 1.0),        # k_top floored at 1, k_rand = n
    ("compact", 65536, 0.45, 0.3),
])
def test_goss_masks_pick_the_sorted_formulations_rows(step, n, top_rate,
                                                      other_rate):
    """The counting select changes HOW the two thresholds are found, not
    which rows they keep: ``goss_masks`` marks the rows the sort-based
    formulation marked, and the step that runs it grows its tree on
    exactly those rows (every leaf's count, not just the total)."""
    import jax
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    X, y = _tied_table(n)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "data_sample_strategy": "goss", "learning_rate": 0.5,
                  "top_rate": top_rate, "other_rate": other_rate,
                  "tpu_goss_compact": step == "compact", "verbosity": -1})
    eng = GBDT(cfg, lgb.Dataset(X, label=y))
    assert (eng._step_goss_compact is not None) == (step == "compact")
    for _ in range(3):                 # GOSS starts at round 1 / 0.5
        eng.train_one_iter()
    d = eng.data
    valid = np.asarray(d.valid_mask) > 0
    n_valid = int(valid.sum())
    assert n_valid == n
    k_top = max(1, int(n_valid * top_rate))
    k_rand = int(n_valid * other_rate)
    # what the step about to run will see: its key, its gradients
    key = jax.random.PRNGKey(cfg.objective_seed + eng.iter_)
    _, km = jax.random.split(key)
    g, h = eng.objective.get_gradients(eng.score[:, 0], d.label, d.weight)
    mask_gh, mask_count = jax.jit(eng._goss_masks)(g, h, d.valid_mask, km)
    metric = np.abs(np.asarray(g) * np.asarray(h)) * valid
    # heavy ties, or the table does not test the tie-break
    assert len(np.unique(metric[valid])) < 0.6 * n_valid
    u = np.asarray(jax.random.uniform(km, (len(metric),)))
    is_top, picked = _goss_oracle(metric, valid, u, k_top, k_rand)
    assert is_top.sum() == k_top
    assert picked.sum() == min(k_rand, n_valid - k_top)
    amp = np.float32((1.0 - top_rate) / max(other_rate, 1e-12))
    np.testing.assert_array_equal(np.asarray(mask_count) > 0,
                                  is_top | picked)
    np.testing.assert_array_equal(
        np.asarray(mask_gh),
        is_top.astype(np.float32) + picked.astype(np.float32) * amp)
    # and the step itself: each leaf holds the oracle's rows
    eng.train_one_iter()
    t = eng.models[-1]
    leaf = t.predict_leaf_raw(X)
    want = np.bincount(leaf[(is_top | picked)[:n]],
                       minlength=t.num_leaves)
    np.testing.assert_array_equal(
        np.asarray(t.leaf_count[:t.num_leaves], np.int64), want)


def test_goss_selects_exact_counts():
    """GOSS parity property (goss.hpp): exactly floor(a*n_valid) top
    rows and exactly floor(b*n_valid) random rows are selected every
    iteration (the reference static_casts, i.e. truncates), even with
    heavily tied |g*h| metrics."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    n = 4096
    X, y = _tied_table(n)
    ds = lgb.Dataset(X, label=y)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "data_sample_strategy": "goss", "learning_rate": 0.5,
                  "top_rate": 0.25, "other_rate": 0.15, "verbosity": -1})
    eng = GBDT(cfg, ds)
    for _ in range(3):
        eng.train_one_iter()
    n_valid = int(np.asarray(eng.data.valid_mask).sum())
    k_top = int(0.25 * n_valid)
    k_rand = int(0.15 * n_valid)    # engine truncates, then caps
    # engine-level check: run a GOSS iteration and inspect leaf counts
    eng.train_one_iter()
    t = eng.models[-1]
    total = float(np.sum(t.leaf_count))
    assert total == k_top + k_rand, (total, k_top, k_rand)


def test_wide_tree_matmul_and_gather_traversals_agree():
    """The num_leaves>512 gather fallback and the matmul formulation
    must route rows identically (incl. NaN-bin default direction)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import (tree_predict_binned,
                                          _tree_predict_binned_gather)
    rng = np.random.default_rng(7)
    n, F, L = 5000, 6, 64
    bins = jnp.asarray(rng.integers(0, 16, size=(n, F)).astype(np.uint8))
    # random consistent tree: node i children either deeper nodes or
    # leaves; build a left-spine tree with random features/thresholds
    lc = np.concatenate([np.arange(1, L - 1), [-L]]).astype(np.int32)
    rc = (-np.arange(1, L)).astype(np.int32)
    tree = {
        "num_leaves": jnp.asarray(L),
        "split_feature": jnp.asarray(
            rng.integers(0, F, L - 1).astype(np.int32)),
        "threshold_bin": jnp.asarray(
            rng.integers(0, 15, L - 1).astype(np.int32)),
        "default_left": jnp.asarray(rng.random(L - 1) < 0.5),
        "left_child": jnp.asarray(lc),
        "right_child": jnp.asarray(rc),
        "leaf_value": jnp.asarray(rng.normal(size=L).astype(np.float32)),
    }
    fnb = jnp.full(F, 16, jnp.int32)
    fhn = jnp.asarray(rng.random(F) < 0.5)   # some NaN-bin features
    v1, l1 = tree_predict_binned(tree, bins, fnb, fhn)
    node0 = jnp.zeros(n, jnp.int32)
    v2, l2 = _tree_predict_binned_gather(tree, bins, fnb, fhn, node0)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=0,
                               atol=0)
