"""Pre-snapshot smoke gate (VERDICT r4 item 1).

Round 4 shipped a one-line NameError in ``GBDT.predict`` that failed
111/249 tests and blanked the round's benchmark because no end-to-end
train+predict ran before the snapshot. This file is the cheap gate:
train + predict on dense AND scipy-sparse input in-session, model
round-trip through the v4 text format, and sklearn predict — the four
surfaces that NameError took down. It runs in seconds, as part of
tier-1.

Reference behavior being pinned: ``Booster.predict`` over dense/CSR
inputs (upstream ``python-package/lightgbm/basic.py`` predict paths,
SURVEY.md §3.5).
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _toy(n=400, f=12, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + rng.normal(scale=0.1, size=n)
         > 0.3).astype(np.float64)
    return X, y


def test_train_predict_dense_and_sparse_in_session(tmp_path):
    scipy_sparse = pytest.importorskip("scipy.sparse")
    X, y = _toy()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=5)

    p_dense = bst.predict(X)
    assert p_dense.shape == (X.shape[0],)
    assert np.all((p_dense >= 0) & (p_dense <= 1))

    Xs = scipy_sparse.csr_matrix(X)
    p_sparse = bst.predict(Xs)
    np.testing.assert_allclose(p_sparse, p_dense, rtol=1e-6)

    # raw_score + pred_leaf surfaces (both crashed at r4 HEAD)
    raw = bst.predict(X, raw_score=True)
    assert raw.shape == (X.shape[0],)
    leaves = bst.predict(X, pred_leaf=True)
    assert leaves.shape[0] == X.shape[0] and leaves.dtype == np.int32

    # model round-trip: text-format predict must match in-session
    mf = tmp_path / "model.txt"
    bst.save_model(str(mf))
    bst2 = lgb.Booster(model_file=str(mf))
    np.testing.assert_allclose(bst2.predict(X), p_dense, rtol=1e-5,
                               atol=1e-7)


def test_sklearn_predict_in_session():
    X, y = _toy(seed=5)
    clf = lgb.LGBMClassifier(n_estimators=5, num_leaves=7, verbosity=-1)
    clf.fit(X, y)
    proba = clf.predict_proba(X)
    assert proba.shape == (X.shape[0], 2)
    acc = (clf.predict(X) == y).mean()
    assert acc > 0.7
