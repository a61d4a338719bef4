"""chip_smoke.py — train -> predict -> explain on one TPU chip, through
the public API, checked phase by phase.

    python chip_smoke.py                  # one chip, the driver's run
    python chip_smoke.py --rows 10500000  # the Higgs-10M size
    python chip_smoke.py --chips 4        # ONLY the data-parallel phase
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --rows 65536

Shape: the BASELINE flagship at full width — 28 dense features,
max_bin=255, num_leaves=127, objective=binary, learning_rate=0.1 — on
Higgs-like data made from ``--seed``. One process (a chip belongs to
one process), public entry points only: ``lgb.Dataset``, ``lgb.train``,
``Booster.predict`` / ``update`` / ``save_model``,
``lgb.Booster(model_file=)``; what it reads off ``Booster.engine`` is
read, never set.

Every phase prints one JSON record and FAILS THE RUN when its check
fails: the exception leaves through ``main`` (non-zero exit, no ``ok``
line). The last line of a passing run is the contract's device line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero at once. ``--rehearse`` is the
CPU rehearsal of the control flow: engine-side TPU assertions are
relaxed, the Pallas kernels run in interpret mode, the last line says
``"ok": false`` and the exit code is 3 — a CPU run can never print the
``ok`` line.

Timings here are smoke timings with the device kind beside them; they
are not benchmark numbers and go into no table as such.
"""
import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import Optional

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
N_FEATURES = 28
BASE_PARAMS = {"objective": "binary", "num_leaves": 127, "max_bin": 255,
               "learning_rate": 0.1, "verbosity": -1}
DEFAULT_ROWS = 1_048_576
# Holdout-AUC floors at the default size: the CPU rehearsal's AUC at the
# same seed, rows and rounds (--rehearse at seed 0, 1,048,576 rows,
# 100,000 held out, PR 21: train_goss_quant 0.87484 at 26 rounds,
# train_plain 0.82389 at 11) minus 0.005 — CPU and TPU may flip
# near-tied splits (ops/split.py), so models are not compared byte for
# byte across backends. Other seeds and smaller sizes only get the
# sanity floor; more rows at equal rounds have not lowered the AUC.
AUC_FLOORS = {"train_goss_quant": 0.86984, "train_plain": 0.81889}
AUC_SANITY_FLOOR = 0.70
TIMING_NOTE = "smoke timing, not a benchmark"


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def synth_higgs(n, f, seed):
    """A Higgs-like table: normal columns, a label from a linear score
    with one product and one absolute-value term, plus noise."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    logit = (X @ w * 0.5 + 0.8 * X[:, 0] * X[:, 1]
             + 0.5 * np.abs(X[:, 2]) - 0.4)
    y = (logit + rng.normal(scale=1.0, size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


def auc(y, p) -> float:
    """Rank-sum AUC with average ranks for ties."""
    _, inv, cnt = np.unique(p, return_inverse=True, return_counts=True)
    rank = (np.cumsum(cnt) - (cnt - 1) / 2.0)[inv]
    n_pos = float((y > 0).sum())
    n_neg = float(len(y)) - n_pos
    return float((rank[y > 0].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


@dataclasses.dataclass
class Run:
    """What the phases of one run share."""

    rows: int
    holdout: int
    seed: int
    on_tpu: bool              # False only off the chip (--rehearse)
    device_kind: str = ""
    X: Optional[np.ndarray] = None
    y: Optional[np.ndarray] = None
    X_ho: Optional[np.ndarray] = None
    y_ho: Optional[np.ndarray] = None
    ds: object = None         # the constructed lgb.Dataset
    bst: object = None        # the GOSS+quantized model (predict/explain)

    def make_data(self) -> None:
        X, y = synth_higgs(self.rows + self.holdout, N_FEATURES,
                           self.seed)
        self.X, self.X_ho = X[:self.rows], X[self.rows:]
        self.y, self.y_ho = y[:self.rows], y[self.rows:]

    def auc_floor(self, phase: str) -> float:
        return (AUC_FLOORS[phase]
                if self.rows >= DEFAULT_ROWS and self.seed == 0
                else AUC_SANITY_FLOOR)


def run_phase(run: Run, name: str, fn, *args, **kwargs) -> None:
    """One phase, one JSON line; a failed check leaves as its
    exception."""
    t0 = time.time()
    body = fn(run, *args, **kwargs)
    print(json.dumps({"phase": name, "ok": True, **body,
                      "seconds": round(time.time() - t0, 2),
                      "device_kind": run.device_kind,
                      "timing": TIMING_NOTE}), flush=True)


# ---------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------
def phase_device(run: Run) -> dict:
    import jax

    from lightgbm_tpu.utils.hbm import hbm_bytes_limit
    devs = jax.devices()
    run.device_kind = devs[0].device_kind
    limit = hbm_bytes_limit()
    if run.on_tpu:
        check(devs[0].platform == "tpu", f"platform {devs[0].platform}")
        check(limit is not None,
              "hbm_bytes_limit() is None on the chip: the HBM gates "
              "(tpu_streaming=auto, serve shard/cache caps) are blind")
    return {"platform": devs[0].platform, "count": len(devs),
            "hbm_bytes_limit": limit, "jax": jax.__version__}


def phase_kernels(run: Run, rows: int = 1 << 17) -> dict:
    """The three Pallas kernels against their XLA references at the
    flagship shape — the content of the on-chip test groups
    (tests/test_multi_leaf_histogram.py, tests/test_compact.py), run
    where it can run. Off the chip they run in interpret mode."""
    import contextlib

    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.ops.compact import (compact_rows, compact_rows_xla,
                                          compaction_out_cols,
                                          plan_compaction)
    from lightgbm_tpu.ops.pallas_histogram import (
        multi_leaf_histogram, multi_leaf_histogram_xla)
    from lightgbm_tpu.ops.route import (route_nodes, route_rows,
                                        route_rows_xla)
    F, B, K, R = N_FEATURES, 256, 32, 4096
    check(rows % R == 0, f"kernel rows {rows} not a multiple of {R}")
    pallas = (contextlib.nullcontext if run.on_tpu
              else pltpu.force_tpu_interpret_mode)
    rng = np.random.default_rng(run.seed)
    bins = rng.integers(0, B, size=(rows, F)).astype(np.uint8)
    bins_t = jnp.asarray(np.ascontiguousarray(bins.T).astype(np.int8))
    leaf = jnp.asarray(rng.integers(0, 40, size=rows).astype(np.int32))
    small = np.arange(K, dtype=np.int32)
    small[[5, 17]] = -1                       # inactive lanes
    small = jnp.asarray(small)

    def both(vals, int_mode):
        with pallas():
            h_pl = np.asarray(multi_leaf_histogram(
                bins_t, jnp.asarray(vals.T), leaf, small, num_bins=B,
                rows_per_block=R, int_mode=int_mode))
        h_xla = np.asarray(multi_leaf_histogram_xla(
            jnp.asarray(bins), jnp.asarray(vals), leaf, small,
            num_bins=B, rows_per_block=1024, precise=int_mode))
        return h_pl, h_xla

    # f32 mode (bf16 operands): the tolerance the on-chip tests use
    vals = rng.normal(size=(rows, 3)).astype(np.float32)
    vals[:, 2] = 1.0
    h_pl, h_xla = both(vals, False)
    np.testing.assert_allclose(h_pl, h_xla, rtol=2e-2, atol=0.5)
    np.testing.assert_array_equal(h_pl[..., 2], h_xla[..., 2])
    f32_err = float(np.abs(h_pl - h_xla).max())
    # int8 mode (quantized-gradient levels): exact
    lv = np.stack([rng.integers(-16, 17, size=rows),
                   rng.integers(0, 17, size=rows),
                   np.ones(rows)], axis=1).astype(np.float32)
    h_pl, h_xla = both(lv, True)
    np.testing.assert_array_equal(h_pl, h_xla)
    check(float(np.abs(h_pl).sum()) > 0, "int8 histogram is all zero")
    # ragged per-column bin counts (the benchmark cells' tables: 1,952
    # and 7,520 one-hot rows where full columns take 3,328 and 9,984):
    # bit-equal to the XLA sums, zeros where a column has no such bin
    for col_bins in (
            (22, 12, 31, 7, 256, 256, 30, 256, 255, 223, 229, 256, 3),
            (104, 256, 256, 171, 256, 256, 256, 251, 256, 16, 68, 37, 249)
            + (255,) * 18 + (24, 4, 27, 11, 5, 19, 16, 100)):
        rb = np.stack([rng.integers(0, c, size=rows) for c in col_bins],
                      axis=1).astype(np.uint8)
        with pallas():
            h_pl = np.asarray(multi_leaf_histogram(
                jnp.asarray(np.ascontiguousarray(rb.T).astype(np.int8)),
                jnp.asarray(lv.T), leaf, small, num_bins=B,
                col_bins=col_bins, rows_per_block=R, int_mode=True))
        h_xla = np.asarray(multi_leaf_histogram_xla(
            jnp.asarray(rb), jnp.asarray(lv), leaf, small, num_bins=B,
            rows_per_block=1024, precise=True))
        np.testing.assert_array_equal(h_pl, h_xla)
        check(float(np.abs(h_pl).sum()) > 0, "ragged histogram all zero")

    # row compaction: bit-equal at F=28, C=3, keep shares 0.3 (GOSS's:
    # a block fills 3 or 4 of its 9 destination groups) and 1.0 (all 9:
    # what the partition move's passes can see)
    Rc = 1024
    v3 = jnp.asarray(rng.normal(size=(3, rows)).astype(np.float32))
    onehot_rows = {}
    for share in (0.3, 1.0):
        mask = rng.uniform(size=rows) < share
        out_cols = compaction_out_cols(int(mask.sum()), Rc, 1024)
        dest, algn, rem, nch = plan_compaction(jnp.asarray(mask), Rc,
                                               out_cols)
        with pallas():
            ob, ov = compact_rows(bins_t, v3, dest, algn, rem, nch,
                                  out_cols=out_cols, rows_per_block=Rc)
            ob, ov = np.asarray(ob), np.asarray(ov)
        eb, ev = compact_rows_xla(bins_t, v3, dest, algn, rem,
                                  out_cols=out_cols, rows_per_block=Rc)
        np.testing.assert_array_equal(ob, np.asarray(eb))
        np.testing.assert_array_equal(ov, np.asarray(ev))
        onehot_rows[share] = 128.0 * float(np.mean(np.asarray(nch)))
    check(onehot_rows[0.3] < 0.5 * onehot_rows[1.0] <= 0.5 * (Rc + 128),
          f"compact_rows builds {onehot_rows} one-hot rows a block")

    # the table routed through a finished tree of 127 leaves (node j
    # split a leaf made before it; half the nodes set-splits over 256
    # bins, NaN bins on both sides): bit-equal to the loop over nodes
    n_nodes = 126
    feat = rng.integers(0, F, size=n_nodes)
    nodes = route_nodes(
        n_nodes, jnp.asarray(feat),
        jnp.asarray(rng.integers(0, B, size=n_nodes)),
        jnp.asarray(rng.random(n_nodes) < 0.5),
        jnp.asarray(rng.integers(0, np.arange(n_nodes) + 1)),
        jnp.full(F, B, jnp.int32), jnp.asarray(rng.random(F) < 0.5),
        is_cat=jnp.asarray(rng.random(n_nodes) < 0.5),
        cat_bitset=jnp.asarray(rng.integers(
            0, 2**32, size=(n_nodes, B // 32), dtype=np.uint64
        ).astype(np.uint32)))
    tail = rows - R     # no multiple of the kernel's block: a ragged one
    with pallas():
        ids = np.asarray(route_rows(bins_t[:, :tail], nodes))
    np.testing.assert_array_equal(
        ids, np.asarray(route_rows_xla(jnp.asarray(bins[:tail]), nodes)))
    leaves = int(len(np.unique(ids)))
    check(leaves > 64, "route_rows reached few leaves")
    return {"rows": rows, "shape": {"F": F, "B": B, "K": K},
            "pallas": "compiled" if run.on_tpu else "interpret",
            "hist_f32_max_abs_err": f32_err, "hist_int8": "exact",
            "hist_int8_ragged": "exact",
            "compact_rows": "bit-equal",
            "compact_onehot_rows": onehot_rows[0.3],
            "route_rows": "bit-equal",
            "route_rows_leaves": leaves}


def phase_ingest(run: Run) -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu import native
    run.make_data()
    native_ok = native.binning() is not None   # warns once if g++ failed
    t0 = time.time()
    ds = lgb.Dataset(run.X, label=run.y, params=dict(BASE_PARAMS))
    ds.construct()
    ing = ds.device_ingested()
    if ing is not None:
        import jax
        jax.block_until_ready(ing.bins)
    construct_s = time.time() - t0
    path = ("device" if ing is not None
            else "native" if native_ok else "python")
    if run.on_tpu and run.rows >= 65_536:
        check(path == "device",
              f"{run.rows} rows on a TPU binned on the {path} path; "
              f"device ingest was expected (io/dataset.py)")
    # bit-identity with the host binner on a 65,536-row slice
    m = min(65_536, run.rows)
    host = lgb.Dataset(run.X[:m], label=run.y[:m], reference=ds,
                       params={**BASE_PARAMS,
                               "tpu_ingest_device": "false"})
    host.construct()
    check(host.device_ingested() is None, "host arm binned on device")
    got = (np.asarray(ing.bins[:m]) if ing is not None
           else ds.binned[:m])
    np.testing.assert_array_equal(got, host.binned)
    run.ds = ds
    return {"rows": run.rows, "path": path,
            "native_library": "built" if native_ok else "FAILED to "
            "build (Python fallback; see the warning on stderr)",
            "construct_s": round(construct_s, 2),
            "host_slice_rows": m, "bins_equal_host": True}


def _engine_record(run: Run, bst, want_int_hist: bool,
                   want_partition: Optional[bool] = None) -> dict:
    """What the ENGINE says it runs (not what the params asked for)."""
    eng = bst.engine
    rec = {"engine": type(eng).__name__,
           "use_pallas": bool(eng.use_pallas),
           "int_hist": bool(eng.grow_cfg.int_hist),
           "hist_partition": bool(eng.hist_partition),
           "learner": eng.learner_type}
    check(rec["engine"] == "GBDT",
          f"routed to {rec['engine']}, not the resident engine")
    if want_partition is not None:
        check(rec["hist_partition"] == want_partition,
              f"hist_partition={rec['hist_partition']}")
    if run.on_tpu:
        check(rec["use_pallas"], "use_pallas is false on the chip")
        check(rec["int_hist"] == want_int_hist,
              f"int_hist={rec['int_hist']}, wanted {want_int_hist}")
    return rec


def _donation_probe(run: Run, bst) -> bool:
    """One more public ``update()`` with a reference to the score held:
    a donated carry is deleted at dispatch."""
    stale = bst.engine.score
    bst.update()
    # train() pinned best_iteration to its own last round and predict()
    # stops there by default: count the probe's tree in
    bst.best_iteration = bst.current_iteration()
    donated = bool(stale.is_deleted())
    if run.on_tpu:
        check(donated, "the score carry was not donated on the chip")
    return donated


def _model_record(run: Run, bst, phase: str) -> dict:
    n_leaves = [int(t.num_leaves) for t in bst.engine.models]
    check(min(n_leaves) > 1, f"a tree has one leaf: {n_leaves}")
    pred = bst.predict(run.X_ho)
    check(pred.shape == (len(run.X_ho),), f"predict shape {pred.shape}")
    check(bool(np.isfinite(pred).all()), "non-finite predictions")
    a = auc(run.y_ho, pred)
    floor = run.auc_floor(phase)
    check(a >= floor, f"holdout AUC {a:.5f} below the floor {floor}")
    return {"trees": len(n_leaves), "min_leaves": min(n_leaves),
            "max_leaves": max(n_leaves), "holdout_auc": round(a, 5),
            "auc_floor": floor}


def phase_train_goss_quant(run: Run, rounds: int = 25) -> dict:
    """GOSS + quantized gradients (what both benchmark cells train
    with). Chunks of 5 fused iterations, so the fused ``lax.scan`` step
    runs before GOSS starts (round 1/learning_rate) and after; the
    donation probe's ``update()`` then runs the per-iteration GOSS
    step."""
    import lightgbm_tpu as lgb
    params = {**BASE_PARAMS, "data_sample_strategy": "goss",
              "use_quantized_grad": True, "tpu_fuse_iters": 5}
    t0 = time.time()
    bst = lgb.train(params, run.ds, num_boost_round=rounds)
    train_s = time.time() - t0
    rec = _engine_record(run, bst, want_int_hist=True)
    rec["goss_compact"] = bool(bst.engine._use_goss_compact)
    rec["carries_donated"] = _donation_probe(run, bst)
    rec.update(_model_record(run, bst, "train_goss_quant"))
    check(rec["trees"] == rounds + 1, f"{rec['trees']} trees")
    run.bst = bst
    return {"rounds": rounds + 1, "train_s_with_compile": round(train_s, 2),
            **rec}


def phase_train_plain(run: Run, rounds: int = 10,
                      pair_rounds: int = 5) -> dict:
    """Full rows, f32 gradients (the bf16 kernel), per-iteration steps;
    then the partitioned-histogram pair, whose models must be byte-equal
    under quantized gradients (on TPU the move is two compact_rows
    passes)."""
    import lightgbm_tpu as lgb
    t0 = time.time()
    bst = lgb.train({**BASE_PARAMS, "use_quantized_grad": False},
                    run.ds, num_boost_round=rounds)
    train_s = time.time() - t0
    rec = _engine_record(run, bst, want_int_hist=False)
    rec["carries_donated"] = _donation_probe(run, bst)
    rec.update(_model_record(run, bst, "train_plain"))
    del bst
    models = {}
    for part in ("true", "false"):
        b = lgb.train({**BASE_PARAMS, "use_quantized_grad": True,
                       "tpu_hist_partition": part},
                      run.ds, num_boost_round=pair_rounds)
        _engine_record(run, b, want_int_hist=True,
                       want_partition=part == "true")
        models[part] = b.model_to_string()
        del b
    check(models["true"] == models["false"],
          "tpu_hist_partition=true and =false grew different models")
    return {"rounds": rounds + 1, "train_s_with_compile": round(train_s, 2),
            **rec, "partition_pair_rounds": pair_rounds,
            "partition_models_byte_equal": True}


def phase_predict(run: Run, tmpdir: str) -> dict:
    import lightgbm_tpu as lgb
    from lightgbm_tpu import native
    from lightgbm_tpu.utils.debug import CompileWatch
    bst, X = run.bst, run.X_ho
    p_dev = bst.predict(X)
    p_scan = bst.predict(X, tpu_predict_parallel_trees=False)
    np.testing.assert_array_equal(p_dev, p_scan)
    # the host model (f64): the C ABI on the model text, or (no g++)
    # the Python HostModel a loaded booster predicts with. The device
    # sums f32 leaf values and runs the sigmoid in f32 on the chip
    # (1.3e-6 off the f64 answer there, 1.3e-7 on the CPU: PR 21).
    if native.c_api() is not None:
        host_kind = "c_abi"
        p_host = native.CBooster(
            model_str=bst.model_to_string()).predict(X)
    else:
        host_kind = "python_host_model"
        p_host = lgb.Booster(
            model_str=bst.model_to_string()).predict(X)
    p_host = np.asarray(p_host).ravel()
    d_host = float(np.abs(p_dev - p_host).max())
    check(d_host <= 1e-5, f"device vs host model: {d_host}")
    # warm one pow2 bucket, then other sizes inside it compile nothing
    n1 = min(10_000, len(X))
    bucket = 1 << (n1 - 1).bit_length()
    others = [n for n in (bucket // 2 + 1, n1 - 1)
              if bucket // 2 < n <= min(bucket, len(X))]
    check(len(others) == 2, f"no second batch size inside {bucket}")
    bst.predict(X[:n1])
    with CompileWatch("warm predict bucket") as w:
        for n in others:
            bst.predict(X[:n])
    w.assert_compiles(0)
    path = os.path.join(tmpdir, "model.txt")
    bst.save_model(path)
    # save -> load from text -> predict: the file holds the whole
    # model (against the host model, f64 both), and the loaded booster
    # answers as the device does (the host-model bound)
    p_rt = lgb.Booster(model_file=path).predict(X)
    d_rt = float(np.abs(p_host - p_rt).max())
    check(d_rt <= 1e-6, f"save -> load -> predict vs host: {d_rt}")
    d_rt_dev = float(np.abs(p_dev - p_rt).max())
    check(d_rt_dev <= 1e-5, f"save -> load -> predict vs device: "
                            f"{d_rt_dev}")
    return {"rows": len(X), "host_model": host_kind,
            "device_vs_host_max_diff": d_host,
            "parallel_trees_equals_scan": True,
            "warm_bucket": bucket, "warm_sizes": others,
            "warm_compiles": w.compiles, "roundtrip_max_diff": d_rt,
            "roundtrip_vs_device_max_diff": d_rt_dev}


def phase_explain(run: Run, tmpdir: str, rows: int = 4096) -> dict:
    """Device SHAP (f32 on the chip): local accuracy against the raw
    score, and agreement with the host path (a loaded booster's f64
    TreeSHAP on the host CPU device)."""
    import lightgbm_tpu as lgb
    bst, X = run.bst, run.X_ho[:rows]
    contrib = bst.predict(X, pred_contrib=True)
    check(contrib.shape == (len(X), N_FEATURES + 1),
          f"contrib shape {contrib.shape}")
    check(bool(np.isfinite(contrib).all()), "non-finite contributions")
    raw = bst.predict(X, raw_score=True)
    d_sum = float(np.abs(contrib.sum(axis=1) - raw).max())
    check(d_sum <= 1e-3, f"contributions do not sum to the raw score: "
                         f"{d_sum}")
    path = os.path.join(tmpdir, "model.txt")
    bst.save_model(path)
    host = lgb.Booster(model_file=path).predict(
        X, pred_contrib=True, contrib_force_f64=True)
    d_host = float(np.abs(contrib - host).max())
    check(d_host <= 1e-3, f"device vs host SHAP: {d_host}")
    return {"rows": len(X), "sum_vs_raw_max_diff": d_sum,
            "device_vs_host_max_diff": d_host}


def phase_data_parallel(run: Run, rounds: int = 10,
                        n_devices: int = 4) -> dict:
    """``--chips 4``: tree_learner=data over all devices against the
    serial learner on one, quantized gradients with deterministic
    rounding — predictions must be EXACTLY equal (integer histograms
    reduce exactly; stochastic rounding draws per shard)."""
    import jax

    import lightgbm_tpu as lgb
    check(jax.device_count() == n_devices,
          f"{jax.device_count()} devices, wanted {n_devices}")
    run.make_data()
    base = {**BASE_PARAMS, "use_quantized_grad": True,
            "stochastic_rounding": False}
    preds = {}
    rec = {}
    for learner in ("data", "serial"):
        params = {**base, "tree_learner": learner}
        ds = lgb.Dataset(run.X, label=run.y, params=dict(params))
        t0 = time.time()
        bst = lgb.train(params, ds, num_boost_round=rounds)
        rec[f"{learner}_train_s_with_compile"] = round(
            time.time() - t0, 2)
        eng = bst.engine
        check(type(eng).__name__ == "GBDT", type(eng).__name__)
        check(eng.learner_type == learner,
              f"tree_learner={learner} ran as {eng.learner_type}")
        if run.on_tpu:
            check(eng.use_pallas and eng.grow_cfg.int_hist,
                  f"{learner}: use_pallas={eng.use_pallas} "
                  f"int_hist={eng.grow_cfg.int_hist}")
        if learner == "data":
            check(eng.mesh is not None
                  and eng.mesh.devices.size == n_devices,
                  f"mesh {eng.mesh}")
            shards = eng.data.bins.addressable_shards
            devs = {s.device for s in shards}
            n_pad = eng.data.bins.shape[0]
            check(len(devs) == n_devices,
                  f"binned rows sit on {len(devs)} device(s)")
            check(all(s.data.shape[0] == n_pad // n_devices
                      for s in shards),
                  f"shard rows {[s.data.shape[0] for s in shards]} of "
                  f"{n_pad}")
            rec.update(mesh_devices=int(eng.mesh.devices.size),
                       shard_rows=n_pad // n_devices,
                       shard_devices=sorted(str(d) for d in devs))
        n_leaves = [int(t.num_leaves) for t in eng.models]
        check(min(n_leaves) > 1, f"{learner}: one-leaf tree {n_leaves}")
        preds[learner] = bst.predict(run.X_ho)
        check(bool(np.isfinite(preds[learner]).all()),
              f"{learner}: non-finite predictions")
        del bst, ds
    np.testing.assert_array_equal(preds["data"], preds["serial"])
    a = auc(run.y_ho, preds["data"])
    check(a >= AUC_SANITY_FLOOR, f"holdout AUC {a:.5f}")
    return {"rows": run.rows, "rounds": rounds, **rec,
            "predictions_exactly_equal_serial": True,
            "holdout_auc": round(a, 5)}


class CompileStats:
    """Seconds spent in the backend compiler and persistent-cache
    traffic, from jax.monitoring, for the ``cache`` record."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.hits = 0
        self.requests = 0

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def __enter__(self) -> "CompileStats":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(
            self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def phase_cache(run: Run, stats: CompileStats, entries_before: int,
                wall_s: float) -> dict:
    import jax
    path = jax.config.jax_compilation_cache_dir
    check(bool(path), "no persistent compilation cache is configured")
    after = _cache_entries(path)
    check(after > 0, f"the compile cache at {path} is empty after a run")
    return {"jax_compilation_cache_dir": path,
            "entries_before": entries_before, "entries_after": after,
            "backend_compile_s": round(stats.compile_s, 2),
            "cache_requests": stats.requests, "cache_hits": stats.hits,
            "wall_s": round(wall_s, 2)}


# ---------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS)
    ap.add_argument("--holdout", type=int, default=100_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: ONLY the data-parallel phase and the serial "
                         "run it is compared with, on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of the control flow: never "
                         "prints the ok line, exits 3")
    args = ap.parse_args(argv)
    t_start = time.time()

    # the persistent compile cache: where JAX_COMPILATION_CACHE_DIR
    # says, else one fixed place in the checkout — set before jax is
    # imported so jax reads it itself, with every program cached
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                          "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
                          "-1")
    if args.rehearse and args.chips > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.chips}").strip()
    import jax

    import lightgbm_tpu  # noqa: F401  (fails here without the program)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); this "
              f"script checks the program on the chip and has no CPU "
              f"mode that can pass", file=sys.stderr)
        return 2

    run = Run(rows=args.rows, holdout=args.holdout, seed=args.seed,
              on_tpu=dev.platform == "tpu")
    entries_before = _cache_entries(jax.config.jax_compilation_cache_dir)
    with CompileStats() as stats, \
            tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmpdir:
        run_phase(run, "device", phase_device)
        if args.chips == 4:
            run_phase(run, "data_parallel", phase_data_parallel,
                      n_devices=4)
        else:
            run_phase(run, "kernels", phase_kernels)
            run_phase(run, "ingest", phase_ingest)
            run_phase(run, "train_goss_quant", phase_train_goss_quant)
            run_phase(run, "train_plain", phase_train_plain)
            run_phase(run, "predict", phase_predict, tmpdir)
            run_phase(run, "explain", phase_explain, tmpdir)
        run_phase(run, "cache", phase_cache, stats, entries_before,
                  time.time() - t_start)
    ok = run.on_tpu and not args.rehearse
    print(json.dumps({
        "ok": ok,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
