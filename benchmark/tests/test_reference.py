"""The reference's two traversals (the C++ loop built at run time and the
numpy one it falls back to) say the same, missing values included."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from lib import reference  # noqa: E402


def _tree():
    # node 0: f0 <= 0.5, missing none; node 1: f1 <= -0.2, missing nan,
    # default left; node 2: f2 <= 0.0, missing zero, default right;
    # node 3: f0 <= -1.0, missing nan, default right
    return {"num_leaves": 5,
            "split_feature": np.array([0, 1, 2, 0]),
            "threshold": np.array([0.5, -0.2, 0.0, -1.0]),
            "decision_type": np.array([0, 2 | (2 << 2), 1 << 2, 2 << 2]),
            "left_child": np.array([1, 3, -3, -1]),
            "right_child": np.array([2, -2, -4, -5]),
            "leaf_value": np.arange(5, dtype=np.float64)}


def test_native_and_numpy_traversals_agree():
    rng = np.random.default_rng(5)
    X = rng.normal(size=(20_000, 3)).astype(np.float32)
    X[rng.random(X.shape) < 0.2] = np.nan
    X[rng.random(X.shape) < 0.2] = 0.0
    tree = _tree()
    by_numpy = reference.route(tree, np.ascontiguousarray(X.T))
    assert set(by_numpy) == {0, 1, 2, 3, 4}
    if reference._native() is None:
        return      # no compiler here: numpy is the only traversal
    assert (reference.leaves(tree, X) == by_numpy).all()
    raw = reference.predict_raw([tree, tree], X, threads=2, block_rows=4096)
    assert (raw == 2.0 * by_numpy).all()


def test_auc_counts_ties_at_half():
    y = np.array([0, 0, 1, 1.0])
    assert reference.auc(y, np.array([0.1, 0.4, 0.4, 0.9])) == 0.875
