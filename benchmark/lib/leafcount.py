"""Exact leaf counts, for training without sampling: every row counts once,
so a tree's `leaf_count` has to be the number of training rows that the
plain reference itself sends to that leaf. (`reference.follow_window`
measures a count only against the draw's variance, and reads 0.0 where
nothing is drawn.) Imports nothing of the program."""
from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from lib import reference


def leaf_count_gap(trees: list[dict], X: np.ndarray,
                   block_rows: int = 1 << 20,
                   threads: int | None = None) -> int:
    """Largest |leaf_count - rows the reference routes to the leaf| over
    the leaves of `trees`, X the whole training table (row-major float32)."""
    n = X.shape[0]
    blocks = range(0, n, block_rows)

    def one(lo: int) -> list[np.ndarray]:
        xb = X[lo:lo + block_rows]
        return [np.bincount(reference.leaves(t, xb),
                            minlength=t["num_leaves"]) for t in trees]

    with cf.ThreadPoolExecutor(reference._threads(threads)) as ex:
        parts = list(ex.map(one, blocks))
    gap = 0
    for k, t in enumerate(trees):
        rows = sum(p[k] for p in parts)
        got = t["leaf_count"][:t["num_leaves"]]
        gap = max(gap, int(np.max(np.abs(got - rows))))
    return gap
