"""A count- and rate-encoded click log in miniature (13 count columns, 26
id columns and one crossed pair, each read through a fixed history table
as its click rate and its impressions: 67 float columns) through the
benchmark's `train-plain` entry: unsampled fused chunks at 255 leaves with
the leaf-ordered partition forced on, against the plain reference's
follower (every leaf's rows exactly, its sums within the rounding, the
hold-out scores against a float64 traversal), and a fault that has to come
out not correct. Also the generator's own promises, the exact leaf-count
reading, and the harness's CPU rehearsal of the cell.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path[:0] = [BENCH]

from lib import ctrgen, leafcount, movework, reference  # noqa: E402
from lib.harness import Harness, load_module  # noqa: E402

CELL = "criteo-tb-1700m.train-plain"
ROWS = 20_000
CARDS = (3, 24, 633, 5_683, 200_003)

MINI_DATA = {
    "generator": "ctrgen", "block_rows": 8_192,
    "counts": [{"name": f"I{j + 1}", "mu": 0.3 + 0.6 * j, "sigma": 1.3,
                "missing": (0.45, 0.0, 0.2)[j % 3]} for j in range(13)],
    "ids": [{"name": f"C{j + 1}", "cardinality": CARDS[j % 5] + j,
             "zipf": (1.5, 1.2, 1.1, 0.9, 1.05)[j % 5],
             "missing": (0.0, 0.0, 0.12, 0.0, 0.03)[j % 5]}
            for j in range(26)],
    "crosses": [["C2", "C3"]],
    "history": {"seed": 1700, "rows": 30_000},
    "label": {"seed": 2013, "id_terms": 12, "count_terms": 4,
              "interactions": 3, "count_weight": 0.5, "strength": 2.1,
              "bias": -1.5}}
MINI_PARAMS = {
    "objective": "binary", "num_leaves": 255, "max_bin": 255,
    "learning_rate": 0.3, "tpu_fuse_iters": 2, "verbosity": -1,
    # the chip engages it by itself past 2^20 rows; the CPU has to ask
    "tpu_hist_partition": "true"}
MINI_CELL = {
    "entry": "train-plain", "warm_rounds": 2, "min_window_iters": 2,
    "auc_trees": 4, "holdout_rows": 4_000, "holdout_seed": 20130624,
    "bin_reference": {"rows": 10_000, "seed": 1700},
    "correct": {"follow_trees": 2, "limits": {
        "predict_gap": 1e-5, "root_rows_gap": 0, "trees_missing": 0,
        "leaf_count_noise": 1.0,
        # at this size a leaf holds some tens of rows and the split search
        # has fitted the rounding's noise: a sound run reads 2 to 4 where
        # the cell's 16M rows read under 1 (CPU, PR 36)
        "leaf_sum_noise": 8.0, "leaf_count_gap": 0}}}


@pytest.fixture(scope="module")
def spec():
    return ctrgen.Spec(MINI_DATA)


@pytest.fixture(scope="module")
def entry():
    return load_module(os.path.join(BENCH, "entries", "train-plain.py"))


@pytest.fixture(scope="module")
def table(entry):
    """One miniature table and its Dataset, to be driven several ways."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    h = Harness(ROOT, BENCH, bench, workload, 7, seconds=0.01, trace=False,
                rehearse_rows=ROWS, need_chip=False)
    h.config = {"rows": ROWS, "params": dict(MINI_PARAMS), "data": MINI_DATA,
                "precision": {"num_grad_quant_bins": 4}}
    h.cell = json.loads(json.dumps(MINI_CELL))
    assert h.look_for_chip()
    prep = entry.prepare(h)
    return h, prep, prep.pop("params")


def _over(result):
    return sorted(k for k, (v, lim) in result["numbers"].items()
                  if v is None or not v <= lim)


# ---- the system against the plain reference ---------------------------------
def test_unsampled_partitioned_chunks_agree_with_the_plain_follower(
        entry, table):
    h, prep, params = table
    r = entry.drive(h, prep, params)
    assert r["correct"], r["numbers"]
    path = r["window"]["path"]
    assert path["fused"] and path["hist_partition"] and path["quantized"]
    assert not path["goss_compact"]
    assert prep["X"].shape[1] == 67
    # every leaf of the followed trees holds exactly the rows the
    # reference sends it, and the trees are grown to the cell's leaves
    assert r["numbers"]["leaf_count_gap"] == (0, 0)
    assert r["numbers"]["leaf_count_noise"][0] == 0.0
    assert max(t["leaves"] for t in r["window"]["followed"]) > 60
    # the mover's work is counted, on the unsampled program's label
    from lightgbm_tpu import obs
    moved = obs.registry().get("partition.rows_moved", sampled=0)
    calls = obs.registry().get("partition.move_calls", sampled=0)
    assert moved.value > 0 and calls.value > 0
    # a move is handed the whole (padded) table
    assert ROWS <= moved.value / calls.value < ROWS + 4096


def test_fault_half_of_the_rows_give_no_gradient(entry, table, monkeypatch):
    import jax.numpy as jnp
    from lightgbm_tpu.objective import Binary
    real = Binary.get_gradients

    def get_gradients(obj, score, label, weight):
        g, h = real(obj, score, label, weight)
        keep = (jnp.arange(g.shape[0]) % 2 == 0).astype(g.dtype)
        return g * keep, h * keep

    monkeypatch.setattr(Binary, "get_gradients", get_gradients)
    h, prep, params = table
    r = entry.drive(h, prep, params)
    assert not r["correct"]
    assert "leaf_sum_noise" in _over(r), r["numbers"]


def test_leaf_count_gap_reads_a_miscounted_leaf(spec):
    import lightgbm_tpu as lgb
    X, y = ctrgen.generate(spec, 6_000, 3, ctrgen.STREAM_TRAIN)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, lgb.Dataset(X, label=y),
                    num_boost_round=2)
    trees = reference.parse_model(bst.model_to_string())
    assert leafcount.leaf_count_gap(trees, X, block_rows=1_000) == 0
    trees[1]["leaf_count"][3] += 7
    assert leafcount.leaf_count_gap(trees, X, block_rows=1_000) == 7


def test_move_bytes_reads_and_writes_a_row_once():
    # 67 one-byte bins and four float32 channels a row, in and out
    assert movework.move_bytes(1_000, 67) == 2 * 1_000 * (67 + 16)


# ---- the generator ----------------------------------------------------------
def test_ctrgen_same_seed_same_table_whatever_the_threads(spec):
    a = ctrgen.generate(spec, 30_000, 5, ctrgen.STREAM_TRAIN, threads=1)
    b = ctrgen.generate(spec, 30_000, 5, ctrgen.STREAM_TRAIN, threads=3)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_ctrgen_streams_and_seeds_differ_and_the_history_is_fixed(spec):
    a = ctrgen.generate(spec, 20_000, 5, ctrgen.STREAM_TRAIN)
    for seed, stream in ((6, ctrgen.STREAM_TRAIN), (5, ctrgen.STREAM_HOLDOUT),
                         (5, ctrgen.STREAM_BINS)):
        b = ctrgen.generate(spec, 20_000, seed, stream)
        assert not np.array_equal(a[0], b[0], equal_nan=True)
    # the history comes from the file's seed alone: another Spec of the
    # same file holds the same tables, whatever the run's seed
    again = ctrgen.Spec(MINI_DATA)
    for t0, t1 in zip(spec.history, again.history):
        np.testing.assert_array_equal(t0, t1)
    moved = ctrgen.Spec(dict(MINI_DATA, history={"seed": 1701,
                                                 "rows": 30_000}))
    assert not np.array_equal(spec.history[4], moved.history[4],
                              equal_nan=True)


def test_ctrgen_columns_are_what_the_file_says(spec):
    X, y = ctrgen.generate(spec, 60_000, 9, ctrgen.STREAM_TRAIN)
    assert X.dtype == np.float32 and X.shape == (60_000, 67)
    assert spec.n_features == 67 and len(spec.names) == 67
    assert spec.names[13:17] == ["C1_ctr", "C1_cnt", "C2_ctr", "C2_cnt"]
    assert spec.names[-2:] == ["C2xC3_ctr", "C2xC3_cnt"]
    # counts: whole numbers, missing at the stated share
    assert abs(np.isnan(X[:, 0]).mean() - 0.45) < 0.02
    assert not np.isnan(X[:, 1]).any()
    seen = X[:, 1][~np.isnan(X[:, 1])]
    np.testing.assert_array_equal(seen, np.floor(seen))
    for g in range(27):
        rate, cnt = X[:, 13 + 2 * g], X[:, 14 + 2 * g]
        # a category the history never saw: NaN and 0, and only there
        np.testing.assert_array_equal(np.isnan(rate), cnt == 0)
        ok = ~np.isnan(rate)
        assert ((rate[ok] >= 0) & (rate[ok] <= 1)).all()
        np.testing.assert_array_equal(cnt, np.floor(cnt))
        # a rate is clicks over impressions of whole numbers
        clicks = rate[ok].astype(np.float64) * cnt[ok]
        assert np.abs(clicks - np.round(clicks)).max() < 1e-2
    # the column of 200,000 ids has categories the 30,000 history rows
    # never saw; the column of 3 has none
    assert np.isnan(X[:, 13 + 2 * 4]).mean() > 0.05
    assert not np.isnan(X[:, 13]).any()
    # the rate columns carry the signal: a frequent category's rate is
    # its own label mean, within chance
    c1 = X[:, 13]
    for v in np.unique(c1):
        rows = c1 == v
        if rows.sum() > 5_000:
            assert abs(y[rows].mean() - v) < 0.03


def test_ctrgen_unseen_category_reads_nan_and_zero(spec):
    # the crossed pair's table is as large as both columns allow, and a
    # pair the history never saw is looked up like any other
    tab = spec.history[-1]
    assert tab.shape == ((CARDS[1] + 1 + 1) * (CARDS[2] + 2 + 1), 2)
    never = tab[:, 1] == 0
    assert never.any() and np.isnan(tab[never, 0]).all()
    assert not np.isnan(tab[~never, 0]).any()


# ---- the harness finds the cell's files --------------------------------------
def test_cell_files_load_and_the_cell_rehearses_on_the_cpu(capfd):
    run = load_module(os.path.join(BENCH, "run.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = next(c for c in bench["configs"] if c["name"] == "criteo-tb-1700m")
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    assert config["params"]["num_leaves"] == 255
    assert "data_sample_strategy" not in config["params"]
    assert not [k for k in config["params"] if k.startswith("tpu_")
                and k != "tpu_fuse_iters"]
    assert len(config["data"]["counts"]) == 13
    assert len(config["data"]["ids"]) == 26
    assert len(config["data"]["crosses"]) == 1
    assert config["rows"] >= 12_000_000 and config["reduced"] == ["rows"]
    metrics = {m["name"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert {"partition_move_ms", "partition_move_roofline",
            "partition_rows_moved_x", "partition_scan_pct"} <= metrics
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "0", "--rehearse", "8000"])
    assert rc == 3
    out, err = capfd.readouterr()
    assert not out.strip()
    assert "REHEARSAL" in err and "leaf_count_gap = 0" in err
