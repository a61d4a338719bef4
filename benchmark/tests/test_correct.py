"""`correct` has to come out false for the control and for each fault a
training cell can have. Each case skips the harness's look for a chip and
drives the rest of a run (entries/train-fused.py) on the CPU at a size a
test can hold, with the timed path broken underneath: the faults sit in
the sampled (GOSS) chunk program alone, the one the window runs
(tests/control_chip.py plants the same ones on the chip).

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
CELL = "airline-115m.train-goss"
# At a test's size the split search fits more of the sampling and rounding
# noise than at the cell's 57.5M rows, so the leaf statistics of a SOUND run
# stand higher (2.5 and 0.5 here against 0.5 and 0.1 on the chip) and are
# held to limits read at this size: sound, control and faults keep their
# order and their distance (PERF.md section 6 has the readings).
LIMITS_AT_THIS_SIZE = {"leaf_sum_noise": 5.0, "leaf_count_noise": 3.0}


@pytest.fixture
def faults():
    f = load_module(os.path.join(HERE, "control_chip.py")).WindowFaults()
    yield f
    f.lift()


def _run(seed=7, params=None):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    h = Harness(ROOT, BENCH, bench, workload, seed, seconds=1.0, trace=False,
                rehearse_rows=ROWS, need_chip=False)
    assert h.look_for_chip()
    h.config["params"].update(params or {})
    h.cell["correct"]["limits"].update(LIMITS_AT_THIS_SIZE)
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


def test_control_one_precision_lower_is_not_correct():
    r = _run(params={"use_quantized_grad": True, "num_grad_quant_bins": 2})
    assert not r["correct"]
    assert _over(r) == ["leaf_sum_noise"], r["numbers"]


def test_fault_state_returned_unchanged(faults):
    """Every iteration of a sampled chunk sees the scores the chunk began
    with: the scan's carry comes back as it went in."""
    faults.fault = "stale_state"
    r = _run()
    assert not r["correct"]
    assert "leaf_sum_noise" in _over(r), r["numbers"]


def test_fault_half_of_the_batch_left_out(faults):
    """Every second row gives no gradient in the sampled chunk program;
    the leaves' means are taken over the rest."""
    faults.fault = "half_batch"
    r = _run()
    assert not r["correct"]
    assert "leaf_sum_noise" in _over(r), r["numbers"]
    assert "leaf_count_noise" in _over(r), r["numbers"]


def test_fault_an_answer_altered_where_it_is_produced(monkeypatch):
    import numpy as np
    from lightgbm_tpu.basic import Booster
    real = Booster.predict

    def predict(self, data, *a, **kw):
        out = np.array(real(self, data, *a, **kw))
        out[len(out) // 2] += 1e-3
        return out

    monkeypatch.setattr(Booster, "predict", predict)
    r = _run()
    assert not r["correct"]
    assert _over(r) == ["predict_gap"], r["numbers"]
