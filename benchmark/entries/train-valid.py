"""Entry `train-valid`: training with a validation set, a metric every round
and early stopping: what `lgb.train(valid_sets=...)` runs, which never
takes the fused branch.

Set-up is `train-fused`'s `prepare` (table, Dataset on the device, bin
boundaries from the cell's fixed table); the cell's hold-out becomes the
validation Dataset (`reference=` the training set). Warm rounds are ONE
`lgb.train(..., valid_sets=[dv], callbacks=[early_stopping,
record_evaluation], keep_training_booster=True)` call, past GOSS's
1 / learning_rate un-sampled rounds. The probe and the window then run, on
that booster and with those two callbacks, the loop `engine.train` runs
round by round (`rounds` below: `Booster.update()`, inside it the
valid-score update, then `Booster.eval_valid()`, then the callbacks with a
`CallbackEnv`); tests/test_train_valid_cell.py holds `rounds` to
`engine.train`'s own loop (the same model text, the same evaluations). The
window ends when the scores are on the device. Then the hold-out is scored
by `Booster.predict` with a fixed tree count, and the plain reference
follows the first trees the window grew and recomputes the metric the
program reported at the window's first and last round (`eval_gap`).
"""
import gc
import os
import time

import numpy as np

from lib import reference
from lib.harness import judge, load_module

_fused = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "train-fused.py"))
_SPANS = ("train/valid_update", "train/eval", "train/update",
          "train/fetch_trees")


def prepare(h) -> dict:
    """`train-fused`'s table and Dataset, and the hold-out as the
    validation Dataset, constructed against the training set's bins."""
    import lightgbm_tpu as lgb
    prep = _fused.prepare(h)
    with h.span("ingest"):
        prep["dv"] = lgb.Dataset(prep["Xh"], label=prep["yh"],
                                 reference=prep["ds"]).construct()
    return prep


def rounds(bst, params: dict, n: int, callbacks: list,
           end_iteration: int) -> int:
    """`n` rounds of the loop `engine.train` runs with a validation set
    (engine.py, "for it in range(start_iter, num_boost_round)"), on a
    booster that has its validation set. Returns the rounds done: fewer
    than `n` where early stopping ended the loop."""
    from lightgbm_tpu import callback as callback_mod
    from lightgbm_tpu import obs
    start = bst.current_iteration()
    for it in range(start, start + n):
        with obs.span("train/round", round=it):
            with obs.span("train/update"):
                bst.update()
            with obs.span("train/eval"):
                results = bst.eval_valid()
            env = callback_mod.CallbackEnv(
                model=bst, params=params, iteration=it, begin_iteration=0,
                end_iteration=end_iteration, evaluation_result_list=results)
            try:
                for cb in callbacks:
                    cb(env)
            except callback_mod.EarlyStopException:
                return it + 1 - start
    return n


def _span_sums() -> dict:
    """Seconds the program's own host spans have recorded so far."""
    from lightgbm_tpu import obs
    out = {}
    for name in _SPANS:
        hist = obs.registry().get(name)
        if hist is not None:
            out[name] = float(hist.sum)
    return out


def run(h) -> dict:
    prep = prepare(h)
    return drive(h, prep, prep.pop("params"), free=True)


def drive(h, prep: dict, params: dict, free: bool = False) -> dict:
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs

    cell, cfg = h.cell, h.config
    spec, rows, ds, dv = prep["spec"], prep["rows"], prep["ds"], prep["dv"]
    X, y, Xh, yh = prep["X"], prep["y"], prep["Xh"], prep["yh"]
    params = dict(params, metric="auc")
    warm = int(cell["warm_rounds"])
    probe = int(cell["probe_rounds"])
    n_traced = int(cell["traced_rounds"])
    auc_trees = int(cell["auc_trees"])
    follow = int(cell["correct"]["follow_trees"])
    mem_after_ingest = h.memory_peak_bytes()
    log: dict = {}
    callbacks = [lgb.record_evaluation(log),
                 lgb.early_stopping(int(cell["early_stopping_rounds"]),
                                    verbose=False)]
    # no round of this run is the loop's last: the window's length is
    # not known before the probe
    end_iteration = 1 << 30
    with h.span("warm"):
        bst = lgb.train(params, ds, num_boost_round=warm, valid_sets=[dv],
                        callbacks=callbacks, keep_training_booster=True)
        jax.block_until_ready(bst.engine.score)
    engine = bst.engine
    path = dict(_fused._path_taken(ds, engine),
                native_binner=prep["native_binner"])
    expect = cell.get("expect", {})
    off = {k: (path.get(k), v) for k, v in expect.items() if path.get(k) != v}
    if off and not h.rehearse_rows:
        raise SystemExit(f"the program stood down from the path this cell "
                         f"times (got, expected): {off}")
    with h.span("probe"):
        t0 = time.perf_counter()
        rounds(bst, params, probe, callbacks, end_iteration)
        jax.block_until_ready(engine.score)
        iter_s = (time.perf_counter() - t0) / probe
    R = max(int(cell["min_window_iters"]),
            5 * int(round(h.seconds / (5 * iter_s))))
    before = bst.current_iteration()
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - h.t_start
    n_setup_compiles, compile_s = h.compiles_between(h.t_start, t_setup_end)

    # ---- the window -------------------------------------------------------
    # R rounds in stretches of (rounds, profiled). With --trace 1 the
    # profiler is on rounds 5 to 5 + n_traced, with the program's own
    # metrics on for them so that its host spans are summed
    # (lightgbm_tpu.obs: histograms of the span names)
    stretches = [(R, False)]
    if h.trace:
        lead = min(5, max(R - n_traced, 0))
        n_traced = min(n_traced, R - lead)
        stretches = [(lead, False), (n_traced, True),
                     (R - lead - n_traced, False)]
    traced = None
    traced_iters = (before, before)
    program_spans = None
    done = 0
    t0 = time.perf_counter()
    for n, profiled in stretches:
        if not n:
            continue
        if not profiled:
            with h.span("window/rounds"):
                got = rounds(bst, params, n, callbacks, end_iteration)
        else:
            traced_iters = (before + done, before + done + n)
            was_on = obs.enabled()
            obs.enable(metrics=True)
            spans0 = _span_sums()
            with h.profiler(window="window/traced") as traced:
                with h.span("window/traced"):
                    got = rounds(bst, params, n, callbacks, end_iteration)
                    jax.block_until_ready(engine.score)
            program_spans = {k: v - spans0.get(k, 0.0)
                             for k, v in _span_sums().items()}
            if not was_on:
                obs.disable()
        done += got
        if got < n:         # early stopping ended the loop
            break
    with h.span("window/sync"):
        jax.block_until_ready(engine.score)
    t1 = time.perf_counter()
    window_s = t1 - t0
    n_window_compiles, _ = h.compiles_between(t0, t1)

    # ---- what the timed path produced ---------------------------------------
    with h.span("predict"):
        pred = np.asarray(bst.predict(Xh, num_iteration=auc_trees),
                          np.float64)
    memory_peak = h.memory_peak_bytes()
    model_text = bst.model_to_string()
    reported = list(log.get("valid_0", {}).get("auc", []))
    counters = {name: getattr(obs.registry().get(name), "value", None)
                for name in ("valid.rows_scored", "eval.calls")}
    del bst, engine, ds, dv
    if free:
        prep.pop("ds")
        prep.pop("dv")
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
    # the metric the program reported at the window's first and last
    # round against the reference's own, from a float64 traversal of as
    # many trees over the hold-out
    gaps = []
    for n_trees in (before + 1, before + R):
        if n_trees > len(trees) or n_trees > len(reported):
            gaps.append(None)
            continue
        ref_auc = reference.auc(yh, reference.predict_raw(trees[:n_trees],
                                                          Xh))
        gaps.append(abs(reported[n_trees - 1] - ref_auc))
    numbers["eval_gap"] = (None if None in gaps else float(max(gaps)))
    followed = reference.follow_window(
        trees, before, follow, X, y, params, cfg["precision"],
        block_rows=spec.block_rows)
    for k in ("leaf_count_noise", "leaf_sum_noise"):
        numbers[k] = followed[k]
    numbers = {k: (v, limits[k]) for k, v in numbers.items()}
    reference_s = time.perf_counter() - t_ref

    ctx = {
        "harness": h, "spans": dict(h.spans), "trace": None,
        "iters_traced": traced_iters[1] - traced_iters[0],
        "trees_traced": trees[traced_iters[0]:traced_iters[1]],
        "n_features": spec.n_features, "rows": rows,
        "compile_s": compile_s, "compiles_in_window": n_window_compiles,
        "memory_peak_bytes": memory_peak,
        "memory_limit_bytes": h.memory_limit_bytes(),
        "device_kind": h.device.get("kind"),
        "program_spans": program_spans,
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
            "reported_auc": [reported[before], reported[-1]]
            if len(reported) > before else [],
            "program_spans": program_spans, "counters": counters,
            "followed": followed["per_tree"],
            "reference_seconds": followed["seconds"],
            "top_ops": (ctx["trace"] or {}).get("ops", [])[:30],
        },
    }
