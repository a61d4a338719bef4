"""The driver's own entry points (``__graft_entry__.py``): the
single-chip step that ``entry()`` hands out compiles and runs, and
``dryrun_multichip`` passes one case at a time on two of the suite's
fake CPU devices. Each case carries its own assertions (finite metrics
for the three resident learners; for ``streaming`` the sharded model
byte-equal to the single-shard one with one collective a level)."""
import pathlib
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    start = np.asarray(args[0])
    trees, _leaf_id, score, *_ = fn(*args)
    score = np.asarray(score)
    assert score.shape == start.shape and np.isfinite(score).all()
    # one boosting step grew a tree and moved the scores off their start
    assert int(np.asarray(trees["num_leaves"]).max()) > 1
    assert not np.array_equal(score, start)


@pytest.mark.parametrize("case", ["data", "voting", "feature",
                                  "streaming"])
def test_dryrun_multichip_case(case, capsys):
    graft.dryrun_multichip(2, only=(case,))
    out = capsys.readouterr().out
    assert f"dryrun_multichip(2) [{case}]" in out and "OK" in out
