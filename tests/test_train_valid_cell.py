"""The benchmark's `train-valid` entry on the CPU: its round-by-round loop
is the loop `lgb.train(valid_sets=...)` runs (the same model text, the same
evaluations), every round's reported AUC is the plain reference's own (a
float64 traversal of that many trees over the hold-out), a validation score
that misses a tree is found by `eval_gap`, and the harness finds the cell's
files and rehearses it.
"""
import json
import os
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [BENCH]

from lib import datagen, reference  # noqa: E402
from lib.harness import load_module  # noqa: E402

CELL = "airline-115m.train-valid"
PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.3,
          "data_sample_strategy": "goss", "top_rate": 0.2, "other_rate": 0.1,
          "metric": "auc", "verbosity": -1}
WARM, MORE = 4, 6      # GOSS samples from round 1 / 0.3 = 3 on


@pytest.fixture(scope="module")
def entry():
    return load_module(os.path.join(BENCH, "entries", "train-valid.py"))


@pytest.fixture(scope="module")
def tables():
    with open(os.path.join(BENCH, "configs", "airline-115m.json")) as f:
        spec = datagen.Spec(json.load(f)["data"])
    X, y = datagen.generate(spec, 30_000, 7, datagen.STREAM_TRAIN)
    Xh, yh = datagen.generate(spec, 8_000, 7, datagen.STREAM_HOLDOUT)
    return X, y, Xh, yh


def _sets(tables):
    X, y, Xh, yh = tables
    ds = lgb.Dataset(X, label=y, params=PARAMS)
    return ds, lgb.Dataset(Xh, label=yh, reference=ds)


def _callbacks(log):
    return [lgb.record_evaluation(log), lgb.early_stopping(50,
                                                           verbose=False)]


@pytest.fixture(scope="module")
def by_loop(entry, tables):
    """Warm rounds by `lgb.train`, the rest by the entry's loop."""
    ds, dv = _sets(tables)
    log = {}
    cbs = _callbacks(log)
    bst = lgb.train(PARAMS, ds, num_boost_round=WARM, valid_sets=[dv],
                    callbacks=cbs, keep_training_booster=True)
    done = entry.rounds(bst, PARAMS, MORE, cbs, end_iteration=1 << 30)
    assert done == MORE
    return bst, log


def _trees_text(bst):
    return bst.model_to_string().split("end of trees")[0]


def test_the_entrys_loop_is_lgb_trains_loop(by_loop, tables):
    ds, dv = _sets(tables)
    log = {}
    whole = lgb.train(PARAMS, ds, num_boost_round=WARM + MORE,
                      valid_sets=[dv], callbacks=_callbacks(log))
    bst, log_loop = by_loop
    assert not whole.engine.can_fuse_iters()
    assert _trees_text(bst) == _trees_text(whole)
    assert log_loop["valid_0"]["auc"] == log["valid_0"]["auc"]
    assert len(log["valid_0"]["auc"]) == WARM + MORE


def test_every_rounds_auc_is_the_references(by_loop, tables):
    bst, log = by_loop
    _X, _y, Xh, yh = tables
    trees = reference.parse_model(bst.model_to_string())
    assert len(trees) == WARM + MORE
    for k, reported in enumerate(log["valid_0"]["auc"], start=1):
        want = reference.auc(yh, reference.predict_raw(trees[:k], Xh))
        assert abs(reported - want) < 1e-6, (k, reported, want)
    # a validation score that misses one tree reads another AUC
    short = reference.auc(yh, reference.predict_raw(trees[:-2] + trees[-1:],
                                                    Xh))
    assert abs(log["valid_0"]["auc"][-1] - short) > 1e-5


def test_the_loop_counts_its_validation_work(by_loop):
    from lightgbm_tpu import obs
    scored = obs.registry().get("valid.rows_scored")
    calls = obs.registry().get("eval.calls")
    assert scored is not None and scored.value >= (WARM + MORE) * 8_000
    assert calls is not None and calls.value >= WARM + MORE


def test_early_stopping_ends_the_entrys_loop(entry, tables):
    ds, dv = _sets(tables)
    log = {}
    cbs = [lgb.record_evaluation(log), lgb.early_stopping(1, verbose=False)]
    bst = lgb.train(dict(PARAMS, learning_rate=3.0), ds, num_boost_round=2,
                    valid_sets=[dv], callbacks=cbs,
                    keep_training_booster=True)
    done = entry.rounds(bst, PARAMS, 30, cbs, end_iteration=1 << 30)
    assert done < 30


def test_cell_files_load_and_the_cell_rehearses_on_the_cpu(capfd):
    run = load_module(os.path.join(BENCH, "run.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "airline-115m" and cell["chips"] == 1
    with open(os.path.join(BENCH, "cells", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["entry"] == "train-valid"
    assert traffic["expect"]["fused"] is False
    assert "eval_gap" in traffic["correct"]["limits"]
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "valid_eval_ms")
    assert metric["workloads"] == [CELL]
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "0", "--rehearse", "60000"])
    assert rc == 3
    out, err = capfd.readouterr()
    assert not out.strip()
    assert "REHEARSAL" in err and "eval_gap" in err
