"""`correct` of the unsampled cell has to come out false for the control
and for a fault of a training step, through the follower's plain branch:
tests/test_correct.py's cases for the path it does not reach (its faults
sit in the sampled chunk program). CPU, 600,000 rows of the configuration's
own table, the partition asked for by name (the chip engages it by itself).

The control is the program's own path one precision below the one the
configuration states: num_grad_quant_bins 2 for 4.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from lib.harness import Harness, load_module  # noqa: E402

ROWS = 600_000
CELL = "criteo-tb-1700m.train-plain"
# At a test's size a leaf of a 255-leaf tree holds a few thousand rows and
# the split search has fitted more of the rounding's noise than at the
# cell's size, so the sound level of leaf_sum_noise stands higher and is
# held to a limit read at this size (PERF.md section 6 has the readings).
LIMITS_AT_THIS_SIZE = {"leaf_sum_noise": 4.0}


@pytest.fixture
def faults():
    f = load_module(os.path.join(HERE, "control_plain_chip.py")).Faults()
    yield f
    f.lift()


def _run(seed=7, params=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    h = Harness(ROOT, BENCH, bench, workload, seed, seconds=1.0, trace=False,
                rehearse_rows=ROWS, need_chip=False)
    assert h.look_for_chip()
    h.config["params"].update(dict(params or {}, tpu_hist_partition="true"))
    h.cell["correct"]["limits"].update(LIMITS_AT_THIS_SIZE)
    h.cell.update(warm_rounds=10, min_window_iters=5)
    entry = load_module(os.path.join(BENCH, "entries",
                                     h.cell["entry"] + ".py"))
    result = entry.run(h)
    h.print_numbers(result)
    return result


def _over(result):
    return sorted(k for k, (v, lim) in result["numbers"].items()
                  if v is None or not v <= lim)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["numbers"]
    assert r["failed"] == 0
    assert r["numbers"]["leaf_count_gap"][0] == 0


def test_control_one_precision_lower_is_not_correct():
    r = _run(params={"use_quantized_grad": True, "num_grad_quant_bins": 2})
    assert not r["correct"]
    assert _over(r) == ["leaf_sum_noise"], r["numbers"]


def test_fault_half_of_the_batch_left_out(faults):
    """Every second row gives no gradient; the leaves' sums are taken
    over the rest."""
    faults.fault = "half_batch"
    r = _run()
    assert not r["correct"]
    assert "leaf_sum_noise" in _over(r), r["numbers"]
