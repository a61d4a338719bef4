"""Entry `train-fused`: a batch retrain with no validation set.

Set-up makes the table from the seed, builds the Dataset on the device
(bin boundaries from the cell's fixed `bin_reference` table, so that the
seed changes the rows and not the programs), and drives `lgb.train`
through `warm_rounds` rounds (its fused branch; past GOSS's
1 / learning_rate un-sampled rounds, so every program the window uses is
compiled or loaded), then one more chunk through the window's own call,
timed, which gives the seconds an iteration. The window hands the SAME
booster to `Booster.engine.train_chunk(R)`, the call `engine.train`'s
fused branch makes, and ends when the scores are on the device. Then the
hold-out is scored by `Booster.predict` with a fixed tree count, the
memory peak is read, the program's state is dropped, and the plain
reference follows the first trees the window grew (lib/reference.py).

`prepare` (table, Dataset) and `drive` (warm rounds, window, reference)
are apart so that tests/control_chip.py can drive one table several ways.
"""
import gc
import time

import numpy as np

import os

from lib import reference
from lib.harness import judge, load_module


def _sizes(h):
    rows = int(h.config["rows"])
    holdout = int(h.cell["holdout_rows"])
    if h.rehearse_rows:
        rows, holdout = h.rehearse_rows, max(h.rehearse_rows // 8, 1000)
    return rows, holdout


def _path_taken(ds, engine) -> dict:
    """Which of the program's paths this run is on, read and never set."""
    g = getattr(engine, "grow_cfg", None)
    return {"engine": type(engine).__name__,
            "device_ingest": ds.device_ingested() is not None,
            "fused": bool(engine.can_fuse_iters()),
            "pallas": bool(getattr(engine, "use_pallas", False)),
            "int_hist": bool(getattr(g, "int_hist", False)),
            "quantized": bool(engine.config.use_quantized_grad),
            "hist_partition": bool(getattr(engine, "hist_partition", False)),
            "goss_compact": bool(getattr(engine, "_use_goss_compact", False))}


def prepare(h) -> dict:
    """The table and hold-out from the seed, and the constructed Dataset."""
    import jax
    import lightgbm_tpu as lgb

    cell, cfg = h.cell, h.config
    datagen = load_module(os.path.join(
        h.here, "lib", cfg["data"].get("generator", "datagen") + ".py"))
    spec = datagen.Spec(cfg["data"])
    rows, holdout_rows = _sizes(h)
    params = dict(cfg["params"])
    if h.rehearse_rows and rows < 500_000:
        # the chip's cells are past the program's auto-quantize line; a
        # rehearsal below it asks for the same path by name
        params["use_quantized_grad"] = True
    with h.span("datagen"):
        X, y = datagen.generate(spec, rows, h.seed, datagen.STREAM_TRAIN)
        Xh, yh = datagen.generate(
            spec, holdout_rows, int(cell.get("holdout_seed", h.seed)),
            datagen.STREAM_HOLDOUT)
    with h.span("ingest"):
        # the program's loader of its native binner races with itself when
        # the threaded bin search is the first to ask for it, and the loser
        # leaves the whole process on the 13x slower Python fallback (PR 25):
        # ask once, from one thread, before the search does
        from lightgbm_tpu import native
        native_binner = native.binning() is not None
        ref = None
        if "bin_reference" in cell:
            # per-feature bin counts are constants of the engine's programs
            # and move with the sample the boundaries are found on: a fixed
            # table of the same law gives every seed the same programs
            br = cell["bin_reference"]
            Xr, yr = datagen.generate(spec, int(br["rows"]), int(br["seed"]),
                                      datagen.STREAM_BINS)
            ref = lgb.Dataset(Xr, label=yr, params=dict(
                params, tpu_ingest_device=False)).construct()
        ds = lgb.Dataset(X, label=y, params=params, reference=ref)
        ds.construct()
        ing = ds.device_ingested()
        if ing is not None:
            jax.block_until_ready([a for a in (ing.bins, ing.bins_t)
                                   if a is not None])
    return {"spec": spec, "rows": rows, "params": params, "X": X, "y": y,
            "Xh": Xh, "yh": yh, "ds": ds, "native_binner": native_binner}


def run(h) -> dict:
    prep = prepare(h)
    return drive(h, prep, prep.pop("params"), free=True)


def drive(h, prep: dict, params: dict, free: bool = False) -> dict:
    """Warm rounds, probe, window, hold-out scores and the reference, on a
    prepared table. `free` drops the Dataset before the reference runs."""
    import jax
    import lightgbm_tpu as lgb

    cell, cfg = h.cell, h.config
    spec, rows, ds = prep["spec"], prep["rows"], prep["ds"]
    X, y, Xh, yh = prep["X"], prep["y"], prep["Xh"], prep["yh"]
    native_binner = prep["native_binner"]
    chunk = int(params["tpu_fuse_iters"])
    warm = int(cell["warm_rounds"])
    auc_trees = int(cell["auc_trees"])
    follow = int(cell["correct"]["follow_trees"])
    mem_after_ingest = h.memory_peak_bytes()
    with h.span("warm"):
        bst = lgb.train(params, ds, num_boost_round=warm,
                        keep_training_booster=True)
        jax.block_until_ready(bst.engine.score)
    engine = bst.engine
    path = dict(_path_taken(ds, engine), native_binner=native_binner)
    expect = cell.get("expect", {})
    off = {k: (path.get(k), v) for k, v in expect.items() if path.get(k) != v}
    if off and not h.rehearse_rows:
        raise SystemExit(f"the program stood down from the path this cell "
                         f"times (got, expected): {off}")
    with h.span("probe"):
        t0 = time.perf_counter()
        engine.train_chunk(chunk)
        jax.block_until_ready(engine.score)
        iter_s = (time.perf_counter() - t0) / chunk
    R = max(int(cell["min_window_iters"]),
            chunk * int(round(h.seconds / (chunk * iter_s))))
    before = bst.current_iteration()
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - h.t_start
    n_setup_compiles, compile_s = h.compiles_between(h.t_start, t_setup_end)

    # ---- the window -------------------------------------------------------
    traced = None
    traced_iters = (before, before)
    t0 = time.perf_counter()
    if not h.trace:
        with h.span("window/train_chunk"):
            engine.train_chunk(R)
    else:
        # the same work as R / chunk dispatches; the profiler is on the
        # second and third of them, ONE call as the window makes it, so
        # that the host's work between two chunks lies inside the trace
        n_traced = min(2, R // chunk)
        if R // chunk > n_traced:
            with h.span("window/train_chunk"):
                engine.train_chunk(chunk)
        traced_iters = (bst.current_iteration(),
                        bst.current_iteration() + n_traced * chunk)
        with h.profiler(window="window/traced") as traced:
            with h.span("window/traced"):
                engine.train_chunk(n_traced * chunk)
                jax.block_until_ready(engine.score)
        rest = before + R - traced_iters[1]
        if rest:
            with h.span("window/train_chunk"):
                engine.train_chunk(rest)
    with h.span("window/sync"):
        jax.block_until_ready(engine.score)
    t1 = time.perf_counter()
    window_s = t1 - t0
    n_window_compiles, _ = h.compiles_between(t0, t1)
    done = bst.current_iteration() - before

    # ---- what the timed path produced ---------------------------------------
    with h.span("predict"):
        pred = np.asarray(bst.predict(Xh, num_iteration=auc_trees),
                          np.float64)
    memory_peak = h.memory_peak_bytes()
    model_text = bst.model_to_string()
    del bst, engine, ds
    if free:
        prep.pop("ds")
    gc.collect()

    # ---- the plain reference ------------------------------------------------
    t_ref = time.perf_counter()
    trees = reference.parse_model(model_text)
    limits = cell["correct"]["limits"]
    numbers = {}
    ref_p = 1.0 / (1.0 + np.exp(-reference.predict_raw(trees[:auc_trees], Xh)))
    numbers["predict_gap"] = float(np.max(np.abs(pred - ref_p)))
    want = reference.expected_root_rows(rows, params, len(trees))
    got = [int(t["internal_count"][0]) if t["num_leaves"] > 1 else -1
           for t in trees]
    numbers["root_rows_gap"] = int(max(abs(a - b) for a, b in zip(got, want)))
    numbers["trees_missing"] = (before + R) - len(trees)
    followed = reference.follow_window(
        trees, before, follow, X, y, params, cfg["precision"],
        block_rows=spec.block_rows)
    for k in ("leaf_count_noise", "leaf_sum_noise"):
        numbers[k] = followed[k]
    numbers = {k: (v, limits[k]) for k, v in numbers.items()}
    reference_s = time.perf_counter() - t_ref

    ctx = {
        "harness": h, "spans": dict(h.spans), "trace": None,
        "iters_traced": traced_iters[1] - traced_iters[0], "trees_traced": trees[traced_iters[0]:
                                                      traced_iters[1]],
        "n_features": spec.n_features, "rows": rows,
        "compile_s": compile_s, "compiles_in_window": n_window_compiles,
        "memory_peak_bytes": memory_peak,
        "memory_limit_bytes": h.memory_limit_bytes(),
        "device_kind": h.device.get("kind"),
    }
    if traced is not None:
        ctx["trace"] = traced.get("reduced")
        ctx["traced_wall_s"] = h.spans["window/traced"]
    return {
        "correct": judge(numbers) and done == R,
        "attempted": R, "failed": R - done,
        "end_to_end": {
            "train_iter_ms": window_s / R * 1e3,
            "holdout_auc": reference.auc(yh, pred),
            "setup_s": setup_s,
        },
        "memory_peak_bytes": memory_peak,
        "numbers": numbers,
        "ctx": ctx,
        "window": {
            "iters": R, "window_s": window_s, "probe_iter_s": iter_s,
            "setup_compiles": n_setup_compiles, "setup_compile_s": compile_s,
            "window_compiles": n_window_compiles,
            "memory_peak_after_ingest": mem_after_ingest,
            "reference_s": reference_s, "spans": {k: round(v, 3) for k, v
                                                  in h.spans.items()},
            "path": path, "rows": rows, "before": before,
            "followed": followed["per_tree"],
            "reference_seconds": followed["seconds"],
            "top_ops": (ctx["trace"] or {}).get("ops", [])[:30],
        },
    }
