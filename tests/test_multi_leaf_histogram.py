"""Batched multi-leaf histogram + leaf_batch growth equivalence tests.

Covers the round-1 gap: the batched learner path (leaf_batch > 1) and the
``multi_leaf_histogram*`` kernels had no coverage, which is how the
regression shipped. The Pallas variant is asserted equal to the XLA
variant on the chip (``LGBM_TPU_TESTS=1``, one process — see
conftest.py) and skipped otherwise (the suite runs on the fake 8-device
CPU mesh).
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.learner.serial import GrowConfig, grow_tree
from lightgbm_tpu.ops.histogram import build_histogram
from lightgbm_tpu.ops.pallas_histogram import (multi_leaf_histogram,
                                               multi_leaf_histogram_xla)
from lightgbm_tpu.ops.predict import tree_predict_binned
from test_hist_layout import AIRLINE as AIRLINE_BINS, CRITEO as CRITEO_BINS


def _data(n=2048, F=6, B=32, n_leaves=5, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, B, size=(n, F)).astype(np.uint8)
    vals = rng.normal(size=(n, 3)).astype(np.float32)
    vals[:, 2] = 1.0
    leaf_id = rng.integers(0, n_leaves, size=n).astype(np.int32)
    return bins, vals, leaf_id


def test_multi_leaf_xla_matches_single_leaf_oracle():
    """Each slot of the K-leaf batched histogram must equal the masked
    single-leaf build_histogram (the oracle-tested op)."""
    B = 32
    bins, vals, leaf_id = _data(B=B)
    small_ids = np.array([3, 0, -1, 4], dtype=np.int32)  # incl. inactive
    out = np.asarray(multi_leaf_histogram_xla(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf_id),
        jnp.asarray(small_ids), num_bins=B, rows_per_block=512))
    assert out.shape == (4, bins.shape[1], B, 3)
    for k, leaf in enumerate(small_ids):
        mask = (leaf_id == leaf).astype(np.float32)[:, None]
        ref = np.asarray(build_histogram(
            jnp.asarray(bins), jnp.asarray(vals * mask), num_bins=B,
            rows_per_block=512))
        np.testing.assert_allclose(out[k], ref, rtol=2e-2, atol=0.5)
        # count channel is exact (sums of exact 1.0s)
        np.testing.assert_array_equal(out[k, :, :, 2], ref[:, :, 2])
    # inactive slot (-1) matches no row -> zero histogram
    assert np.all(out[2] == 0.0)


# decided from the environment, not from the backend: a module that
# asks jax for its devices while it is imported initialises the backend
# in every xdist worker that collects it
requires_tpu = pytest.mark.skipif(
    os.environ.get("LGBM_TPU_TESTS", "") != "1",
    reason="Pallas TPU kernel needs a TPU backend (run with "
           "LGBM_TPU_TESTS=1 on the chip)")


@requires_tpu
@pytest.mark.parametrize(
    "F,B,rpb",
    [
        (40, 256, 2048),   # F*B = 10240 > 8192: feature-blocked grid,
                           # at the B=256 int8-roundtrip boundary
        (8, 256, 4096),    # B=256 boundary on the single-block path at
                           # the R=4096 cap
        (64, 128, 2048),   # wide-F grid at the reduced R cap
        (6, 32, 4096),     # narrow shape at the full R cap
    ])
def test_pallas_matches_xla_boundary_shapes(F, B, rpb):
    """The exact VMEM cliffs docs/perf.md documents: the feature-blocked
    grid (F*B > 8192), the 256-bin int8 round-trip boundary, and both
    rows-per-block caps — each must agree with the XLA reference."""
    bins, vals, leaf_id = _data(n=4096, F=F, B=B, seed=B + F)
    small_ids = np.array([0, 3, -1, 1], dtype=np.int32)
    bins_t = np.ascontiguousarray(bins.T).astype(np.int8)
    h_pl = np.asarray(multi_leaf_histogram(
        jnp.asarray(bins_t), jnp.asarray(vals.T), jnp.asarray(leaf_id),
        jnp.asarray(small_ids), num_bins=B, rows_per_block=rpb))
    h_xla = np.asarray(multi_leaf_histogram_xla(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf_id),
        jnp.asarray(small_ids), num_bins=B, rows_per_block=rpb))
    np.testing.assert_allclose(h_pl, h_xla, rtol=2e-2, atol=0.5)
    np.testing.assert_array_equal(h_pl[..., 2], h_xla[..., 2])


# per-column bin counts: the two benchmark cells' tables, a 1-bin column,
# all full
RAGGED = {
    "airline": (AIRLINE_BINS, 4096),
    "criteo": (CRITEO_BINS, 4096),
    "criteo_grid": (CRITEO_BINS + (256,) * 9, 2048),   # 48 columns: grid
    "one_bin": ((256, 1, 90, 1, 256), 4096),
    "all_full": ((256,) * 13, 4096),
}


def _ragged_data(col_bins, n, seed):
    rng = np.random.default_rng(seed)
    bins = np.stack([rng.integers(0, c, size=n) for c in col_bins],
                    axis=1).astype(np.uint8)
    lv = np.stack([rng.integers(-16, 17, size=n),
                   rng.integers(0, 17, size=n),
                   np.ones(n)], axis=1).astype(np.float32)
    leaf_id = rng.integers(0, 40, size=n).astype(np.int32)
    return bins, lv, leaf_id


@requires_tpu
@pytest.mark.parametrize("case", sorted(RAGGED))
def test_pallas_ragged_counts_match_xla(case):
    """One-hot rows only for the bins a column has: int mode bit-equal
    to the XLA sums (zeros where a column has no such bin), f32 mode
    within the bf16 tolerance, and equal to the same call without the
    counts."""
    col_bins, rpb = RAGGED[case]
    bins, lv, leaf_id = _ragged_data(col_bins, 1 << 15, len(col_bins))
    small = np.arange(32, dtype=np.int32)
    small[[5, 17]] = -1
    args = (jnp.asarray(np.ascontiguousarray(bins.T).astype(np.int8)),
            jnp.asarray(lv.T), jnp.asarray(leaf_id), jnp.asarray(small))
    h_xla = np.asarray(multi_leaf_histogram_xla(
        jnp.asarray(bins), jnp.asarray(lv), jnp.asarray(leaf_id),
        jnp.asarray(small), num_bins=256, rows_per_block=1024,
        precise=True))
    for int_mode in (True, False):
        h = np.asarray(multi_leaf_histogram(
            *args, num_bins=256, col_bins=col_bins, rows_per_block=rpb,
            int_mode=int_mode))
        h_dense = np.asarray(multi_leaf_histogram(
            *args, num_bins=256, rows_per_block=2048, int_mode=int_mode))
        if int_mode:
            np.testing.assert_array_equal(h, h_xla)
            np.testing.assert_array_equal(h, h_dense)
        else:
            np.testing.assert_allclose(h, h_xla, rtol=2e-2, atol=0.5)
            np.testing.assert_allclose(h, h_dense, rtol=2e-2, atol=0.5)
    for f, nb in enumerate(col_bins):
        assert not h[:, f, nb:].any()
    assert float(np.abs(h).sum()) > 0


@pytest.mark.parametrize("learner,want_bins", [
    ("serial", (255, 255, 255, 6, 255, 255, 41)),
    # a 1-bin column pads the width to the 8 shards of the scatter
    ("data", (255, 255, 255, 6, 255, 255, 41, 1)),
    # one program serves every shard's column slice: a position takes
    # the largest count any shard has there
    ("feature", (255,)),
])
def test_model_text_is_equal_with_and_without_counts(pallas_path,
                                                     monkeypatch,
                                                     learner, want_bins):
    """The grower reads the dense [K, F, B, C] either way, and integer
    histograms are the same sums: a forest grown with the kernel's
    ragged layout equals, byte for byte, the one grown with every
    column at num_bins. On every learner that runs the kernel."""
    import dataclasses

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    if learner != "serial" and jax.device_count() < 2:
        pytest.skip("one device: the engine runs the serial learner")
    rng = np.random.default_rng(5)
    n, rounds = (6000, 5) if learner == "serial" else (2048, 2)
    X = rng.normal(size=(n, 7)).astype(np.float32)
    X[:, 3] = rng.integers(0, 5, size=n)
    X[:, 6] = rng.integers(0, 40, size=n)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.2 * X[:, 3]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "learning_rate": 0.5, "data_sample_strategy": "goss",
              "tpu_fuse_iters": 2, "use_quantized_grad": True,
              "tpu_leaf_batch": 4, "tree_learner": learner}
    texts, col_bins = [], []
    for dense in (False, True):
        if dense:
            ragged_cfg = GBDT._make_grow_cfg
            monkeypatch.setattr(
                GBDT, "_make_grow_cfg",
                lambda self: dataclasses.replace(
                    ragged_cfg(self), hist_col_bins=()))
        bst = lgb.train(params, lgb.Dataset(X, label=y),
                        num_boost_round=rounds)
        assert bst.engine.use_pallas and bst.engine.grow_cfg.int_hist
        col_bins.append(bst.engine.grow_cfg.hist_col_bins)
        texts.append(bst.model_to_string())
    assert col_bins == [want_bins, ()]
    assert texts[0] == texts[1]


@requires_tpu
def test_pallas_matches_xla():
    B = 64
    bins, vals, leaf_id = _data(n=4096, F=8, B=B, seed=1)
    small_ids = np.array([0, 2, -1, 1, 4, -1, 3, -1], dtype=np.int32)
    bins_t = np.ascontiguousarray(bins.T).astype(np.int8)
    h_pl = np.asarray(multi_leaf_histogram(
        jnp.asarray(bins_t), jnp.asarray(vals.T), jnp.asarray(leaf_id),
        jnp.asarray(small_ids), num_bins=B, rows_per_block=1024))
    h_xla = np.asarray(multi_leaf_histogram_xla(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(leaf_id),
        jnp.asarray(small_ids), num_bins=B, rows_per_block=1024))
    np.testing.assert_allclose(h_pl, h_xla, rtol=2e-2, atol=0.5)
    np.testing.assert_array_equal(h_pl[..., 2], h_xla[..., 2])


def _grow(bins, g, h, cfg):
    n, F = bins.shape
    mask = np.ones(n, dtype=np.float32)
    vals = np.stack([g * mask, h * mask, mask], axis=1).astype(np.float32)
    num_bin = np.full(F, int(bins.max()) + 1, dtype=np.int32)
    has_nan = np.zeros(F, dtype=bool)
    tree, leaf_id = grow_tree(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(num_bin),
        jnp.asarray(has_nan), jnp.ones(F, dtype=bool), cfg)
    return ({k: np.asarray(v) for k, v in tree.items()},
            np.asarray(leaf_id), num_bin, has_nan)


@pytest.mark.parametrize("kb", [4, 16])
def test_leaf_batch_equivalent_fully_grown(kb):
    """When growth stops by min_data/gain (not the leaf cap), the batched
    expansion must find the same tree as exact leaf-wise order: same split
    multiset, same per-row leaf values."""
    n = 1024
    rng = np.random.default_rng(7)
    bins = rng.integers(0, 8, size=(n, 4)).astype(np.uint8)
    g = (bins[:, 0] * 0.5 - bins[:, 1] + 0.1 * rng.normal(size=n)) \
        .astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    base = dict(num_leaves=63, min_data_in_leaf=50, num_bins=8,
                rows_per_block=256, min_gain_to_split=1e-3)
    t1, l1, num_bin, has_nan = _grow(bins, g, h,
                                     GrowConfig(leaf_batch=1, **base))
    tk, lk, _, _ = _grow(bins, g, h, GrowConfig(leaf_batch=kb, **base))
    assert int(t1["num_leaves"]) == int(tk["num_leaves"])
    nl = int(t1["num_leaves"])
    splits1 = sorted(zip(t1["split_feature"][:nl - 1],
                         t1["threshold_bin"][:nl - 1]))
    splitsk = sorted(zip(tk["split_feature"][:nl - 1],
                         tk["threshold_bin"][:nl - 1]))
    assert splits1 == splitsk
    # per-row predicted values identical up to bf16 histogram noise
    np.testing.assert_allclose(t1["leaf_value"][l1], tk["leaf_value"][lk],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kb", [1, 4, 16])
def test_leaf_batch_counts_partition(kb):
    n = 2048
    rng = np.random.default_rng(8)
    bins = rng.integers(0, 16, size=(n, 5)).astype(np.uint8)
    g = rng.normal(size=n).astype(np.float32)
    h = np.ones(n, dtype=np.float32)
    cfg = GrowConfig(num_leaves=31, min_data_in_leaf=5, num_bins=16,
                     rows_per_block=512, leaf_batch=kb)
    tree, leaf_id, num_bin, has_nan = _grow(bins, g, h, cfg)
    nl = int(tree["num_leaves"])
    counts = np.bincount(leaf_id, minlength=cfg.num_leaves)
    np.testing.assert_array_equal(
        counts[:nl], tree["leaf_count"][:nl].astype(np.int64))
    assert counts[nl:].sum() == 0
    assert counts[:nl].min() >= 5
    # leaf_id agrees with traversal of the emitted tree
    dev_tree = {k: jnp.asarray(v) for k, v in tree.items()}
    _, leaf_via_tree = tree_predict_binned(
        dev_tree, jnp.asarray(bins), jnp.asarray(num_bin),
        jnp.asarray(has_nan))
    np.testing.assert_array_equal(leaf_id, np.asarray(leaf_via_tree))


def test_gbdt_quality_stable_across_leaf_batch():
    """End-to-end: tpu_leaf_batch in {1, 16} reach the same held-out AUC
    band on a fixed binary dataset."""
    import lightgbm_tpu as lgb
    rng = np.random.default_rng(11)
    n, f = 3000, 10
    X = rng.normal(size=(n, f))
    w = rng.normal(size=f)
    y = ((X @ w + 0.5 * X[:, 0] * X[:, 1]
          + rng.normal(scale=0.5, size=n)) > 0).astype(np.float64)
    aucs = {}
    for kb in (1, 16):
        ds = lgb.Dataset(X[:2400], label=y[:2400])
        vs = ds.create_valid(X[2400:], label=y[2400:])
        res = {}
        lgb.train({"objective": "binary", "num_leaves": 31,
                   "metric": "auc", "tpu_leaf_batch": kb,
                   "verbosity": -1}, ds, num_boost_round=20,
                  valid_sets=[vs], callbacks=[lgb.record_evaluation(res)])
        aucs[kb] = res["valid_0"]["auc"][-1]
    assert aucs[1] > 0.9 and aucs[16] > 0.9
    assert abs(aucs[1] - aucs[16]) < 0.02
