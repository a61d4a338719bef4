"""Out-of-core streaming engine (boosting/streaming.py, VERDICT r4
item 3): host-resident bins, level-wise streamed growth.

The reference trains any dataset that fits host RAM
(``dataset_loader.cpp`` two-round + row-wise bin storage, SURVEY §2.1,
UNVERIFIED); the streaming engine is this framework's equivalent for
data whose binned matrix exceeds HBM.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _data(n=20_000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
    return X, y


BASE = {"objective": "binary", "num_leaves": 16, "max_depth": 4,
        "verbosity": -1, "min_data_in_leaf": 20}


def test_streaming_block_count_invariant():
    """Training must be BIT-identical no matter how the rows are cut
    into streamed blocks — the accumulated histograms are exact sums."""
    X, y = _data()
    texts = []
    for blk in (30_000, 2_048):
        bst = lgb.train(dict(BASE, tpu_streaming="true",
                             tpu_stream_block_rows=blk),
                        lgb.Dataset(X, label=y), num_boost_round=8)
        texts.append(bst.model_to_string())
    assert texts[0] == texts[1]


def test_streaming_close_to_resident():
    """At a complete depth (num_leaves = 2^max_depth) level-wise and
    best-first growth choose from the same split sets; models may
    differ on float near-ties but quality must match the resident
    engine."""
    X, y = _data(seed=3)
    accs = {}
    for mode in ("true", "false"):
        bst = lgb.train(dict(BASE, tpu_streaming=mode),
                        lgb.Dataset(X, label=y), num_boost_round=10)
        pred = bst.predict(X)
        accs[mode] = np.mean((pred > 0.5) == y)
        assert np.isfinite(pred).all()
    assert abs(accs["true"] - accs["false"]) < 0.01


def test_streaming_model_roundtrip_and_missing(tmp_path):
    """NaN routing (default_left) + v4 text round-trip from the
    streaming engine."""
    X, y = _data(seed=5)
    X[::7, 0] = np.nan          # informative missingness on the main
    y[::7] = 1.0                 # feature
    bst = lgb.train(dict(BASE, tpu_streaming="true"),
                    lgb.Dataset(X, label=y), num_boost_round=6)
    p = bst.predict(X)
    mf = tmp_path / "m.txt"
    bst.save_model(str(mf))
    p2 = lgb.Booster(model_file=str(mf)).predict(X)
    np.testing.assert_allclose(p, p2, rtol=1e-6, atol=1e-9)
    assert np.mean((p > 0.5) == y) > 0.8


def test_streaming_regression_weighted():
    rng = np.random.default_rng(9)
    X = rng.normal(size=(8_000, 6))
    y = X[:, 0] * 2 + X[:, 1] ** 2 + rng.normal(scale=0.1, size=8_000)
    w = rng.uniform(0.5, 2.0, size=8_000)
    bst = lgb.train(dict(BASE, objective="regression",
                         tpu_streaming="true",
                         tpu_stream_block_rows=2_048),
                    lgb.Dataset(X, label=y, weight=w),
                    num_boost_round=20)
    mse = float(np.mean((bst.predict(X) - y) ** 2))
    assert mse < np.var(y) * 0.3


def test_streaming_feature_fraction_and_l1():
    X, y = _data(seed=11)
    bst = lgb.train(dict(BASE, tpu_streaming="true",
                         feature_fraction=0.6, lambda_l1=0.5,
                         lambda_l2=2.0),
                    lgb.Dataset(X, label=y), num_boost_round=10)
    assert np.mean((bst.predict(X) > 0.5) == y) > 0.8


def test_streaming_rejects_unsupported():
    # GOSS / bagging / quantized gradients are streaming-supported now
    # (PR 7, the sharded streamed path); the structured-constraint
    # features and non-row-sharding learners still gate out
    X, y = _data(n=2_000)
    from lightgbm_tpu.utils.log import LightGBMError
    for extra in ({"num_class": 3, "objective": "multiclass"},
                  {"linear_tree": True},
                  {"boosting": "dart"},
                  {"tree_learner": "voting"},
                  {"monotone_constraints": [1] * 10}):
        with pytest.raises(LightGBMError):
            lgb.train(dict(BASE, tpu_streaming="true", **extra),
                      lgb.Dataset(X, label=y.astype(float)),
                      num_boost_round=2)


def test_streaming_sklearn_surface():
    """The sklearn wrapper composes with the streaming engine (predict
    goes through the host model path)."""
    X, y = _data(seed=13)
    clf = lgb.LGBMClassifier(n_estimators=8, num_leaves=16, max_depth=4,
                             verbosity=-1, tpu_streaming="true")
    clf.fit(X, y)
    assert (clf.predict(X) == y).mean() > 0.8


def test_streaming_valid_eval_and_early_stopping():
    """Valid-set metrics + early-stopping callbacks compose with the
    streaming engine (valid sets evaluate via the host model over raw
    features; training metric reads the device-resident score)."""
    X, y = _data(n=30_000, seed=21)
    ds = lgb.Dataset(X[:24_000], label=y[:24_000])
    vs = ds.create_valid(X[24_000:], label=y[24_000:])
    evals = {}
    bst = lgb.train(dict(BASE, metric="auc", tpu_streaming="true",
                         is_provide_training_metric=True),
                    ds, num_boost_round=10,
                    valid_sets=[vs], valid_names=["val"],
                    callbacks=[lgb.record_evaluation(evals),
                               lgb.early_stopping(5, verbose=False)])
    aucs = evals["val"]["auc"]
    assert len(aucs) == 10 and aucs[-1] > aucs[0] > 0.5
    assert "training" in evals           # device-score train metric
    assert bst.best_iteration >= 1


def test_streaming_compatible_never_routes_fatal_configs():
    """_streaming_compatible must be a SUBSET of what StreamingGBDT
    accepts: auto-routing a config into its _no() fatals would turn a
    train() that the resident engine handles into a crash (round-5 review:
    use_quantized_grad and bare cegb_tradeoff were missing gates;
    PR 7 lifted the quantization gate — explicit use_quantized_grad is
    now streaming-compatible and must construct, not fatal)."""
    from lightgbm_tpu.boosting import _streaming_compatible
    from lightgbm_tpu.config import Config
    cfg = Config(dict(BASE, cegb_tradeoff=2.0))
    assert not _streaming_compatible(cfg)
    assert _streaming_compatible(Config(dict(BASE,
                                             use_quantized_grad=True)))
    # the resident engine still trains the incompatible config fine,
    # and the now-compatible one trains on the STREAMING engine
    X, y = _data(n=2_000)
    lgb.train(dict(BASE, cegb_tradeoff=2.0), lgb.Dataset(X, label=y),
              num_boost_round=2)
    lgb.train(dict(BASE, use_quantized_grad=True, tpu_streaming="true"),
              lgb.Dataset(X, label=y), num_boost_round=2)


def test_streaming_extra_trees_binds():
    """extra_trees must actually randomize streamed thresholds (it
    used to silently fall back to plain GBDT: find_best_split skips
    the filter when extra_u is None — round-5 review)."""
    X, y = _data(n=8_000, seed=5)
    def train(extra_trees, seed=1):
        return lgb.train(dict(BASE, tpu_streaming="true",
                              extra_trees=extra_trees, seed=seed),
                         lgb.Dataset(X, label=y),
                         num_boost_round=4).model_to_string()
    plain = train(False)
    extra = train(True)
    # one random threshold per (node, feature) must change the trees
    assert extra != plain
    # and a different seed draws different thresholds
    assert train(True, seed=2) != extra
    # while the same seed reproduces exactly
    assert train(True, seed=2) == train(True, seed=2)


def test_streaming_sparse_valid_rejected():
    """scipy-sparse raw valid features fail early with the standard
    unsupported message instead of crashing mid-eval on len(sparse)
    (round-5 review)."""
    pytest.importorskip("scipy")
    import scipy.sparse as sp
    from lightgbm_tpu.utils.log import LightGBMError
    X, y = _data(n=4_000)
    ds = lgb.Dataset(X[:3_000], label=y[:3_000])
    vs = lgb.Dataset(sp.csr_matrix(X[3_000:]), label=y[3_000:],
                     reference=ds)
    with pytest.raises(LightGBMError, match="sparse"):
        lgb.train(dict(BASE, tpu_streaming="true"), ds,
                  num_boost_round=2, valid_sets=[vs],
                  valid_names=["val"])
