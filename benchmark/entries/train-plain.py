"""Entry `train-plain`: a batch retrain with no validation set and no
sampling. It IS entry `train-fused` (its `prepare`, its `drive`: the same
warm rounds, probe, one `train_chunk(R)` window, hold-out scores and
follower, here through the follower's plain branch) plus the one reading
that path lacks: with no sampling a leaf's row count is exact, and
`leaf_count_gap` holds the followed trees' `leaf_count` to the rows the
reference itself sends to each leaf (lib/leafcount.py).
"""
import os

from lib import leafcount, reference
from lib.harness import judge, load_module

_fused = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "train-fused.py"))


class _KeepTrees:
    """lib/reference.py as `train-fused` sees it, with the trees it parsed
    out of the window's model kept for the one further reading."""

    def __init__(self):
        self.trees = None

    def __getattr__(self, name):
        return getattr(reference, name)

    def parse_model(self, text):
        self.trees = reference.parse_model(text)
        return self.trees


prepare = _fused.prepare


def run(h) -> dict:
    prep = prepare(h)
    return drive(h, prep, prep.pop("params"), free=True)


def drive(h, prep: dict, params: dict, free: bool = False) -> dict:
    kept = _fused.reference = _KeepTrees()
    r = _fused.drive(h, prep, params, free=free)
    before = r["window"]["before"]
    follow = int(h.cell["correct"]["follow_trees"])
    gap = leafcount.leaf_count_gap(kept.trees[before:before + follow],
                                   prep["X"], prep["spec"].block_rows)
    limit = h.cell["correct"]["limits"]["leaf_count_gap"]
    r["numbers"]["leaf_count_gap"] = (gap, limit)
    r["correct"] = bool(r["correct"] and judge(r["numbers"]))
    return r
