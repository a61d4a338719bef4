"""On the chip, at a cell's own shape: what the program's own tracing shows
and what it costs. One table from the seed (the cell's entry makes it), then

  ingest   a profiler session round `Dataset.construct()`, reduced by the
           program's reader (`lightgbm_tpu/obs/trace_attr.py`) with the
           `lgbm/dataset/construct` span as the window: the ingest spans,
           the device's idle gaps named by them, the `ingest.*` counters;
  train    the operator's flow, twice: `lgb.train(params)` (compiles or
           loads every program), then `lgb.train(params, tpu_profile_dir=)`
           on a fresh booster, whose dump the program reduces itself; the
           by-layer table, the named gaps and the `hist.*` / `goss.*`
           counters are printed;
  cost     the same booster's `train_chunk(N)` to `block_until_ready`, by
           turns with everything off, with `tpu_metrics` + `tpu_trace_dir`
           on, and inside a profiler session: ms an iteration of each turn.
           Every turn starts from the same scores and iteration number, so
           all of them grow the SAME N trees (the loop trips of a tree, 8 to
           10 here, move an iteration by more than tracing does). The last
           profiler turn's dump, N sampled iterations and nothing else, is
           the steady-state by-layer table (<out>/steady.txt).

Prints one JSON line a phase, and writes what `scripts/trace_attr.py`
prints for the dumps to <out>/ingest.txt, train.txt and steady.txt. The dumps
themselves go unless --keep-dumps (the ingest dump grows with the chunks).
Not run by the benchmark.

    python benchmark/tests/trace_chip.py --workload <cell> --seed N \
        [--rows R] [--rounds 25] [--cost-iters 10] [--turns 3] [--out DIR]
"""
import argparse
import contextlib
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from lib.harness import Harness, load_module  # noqa: E402


def _counters(prefixes) -> dict:
    from lightgbm_tpu import obs
    out = {}
    for m in obs.registry().metrics():
        if m.name.startswith(prefixes) and hasattr(m, "value"):
            key = m.name + "".join(f"{{{k}={v}}}" for k, v
                                   in sorted(m.labels.items()))
            out[key] = m.value
    return out


def _brief(res: dict, top: int = 12) -> dict:
    """The part of an attribution that goes on a line."""
    if not res.get("found"):
        return res
    keep = {k: res[k] for k in ("source", "window", "wall_ms", "busy_ms",
                                "n_devices", "iters") if k in res}
    keep["layers"] = res.get("layers")
    keep["idle_gaps"] = res.get("idle_gaps", [])[:top]
    keep["spans"] = res.get("spans")
    keep["ops"] = [[o["name"], round(o["ms"], 3), o["calls"], o.get("scope")]
                   for o in res["ops"][:top]]
    return keep


def _cli_table(dump: str, out_txt: str, *cli_args: str) -> None:
    """What the operator sees: scripts/trace_attr.py's own output."""
    cli = load_module(os.path.join(ROOT, "scripts", "trace_attr.py"))
    with open(out_txt, "w") as f, contextlib.redirect_stdout(f):
        cli.main([dump, "--top", "25", *cli_args])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--cost-iters", type=int, default=10)
    ap.add_argument("--turns", type=int, default=3)
    ap.add_argument("--keep-dumps", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "trace_chip"))
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
    h = Harness(ROOT, BENCH, bench, workload, args.seed, 1.0, False,
                rehearse_rows=args.rows)
    if not h.look_for_chip():
        return 2
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import trace_attr

    os.makedirs(args.out, exist_ok=True)
    quiet = jax.profiler.ProfileOptions()
    quiet.python_tracer_level = 0         # datagen is Python; not the point
    entry = load_module(os.path.join(BENCH, "entries",
                                     h.cell["entry"] + ".py"))

    # ---- ingest ---------------------------------------------------------
    d_ingest = os.path.join(args.out, "prof_ingest")
    jax.profiler.start_trace(d_ingest, profiler_options=quiet)
    try:
        prep = entry.prepare(h)
    finally:
        jax.profiler.stop_trace()
    params, ds = prep.pop("params"), prep["ds"]
    res = trace_attr.attribute(d_ingest, window="lgbm/dataset/construct")
    _cli_table(d_ingest, os.path.join(args.out, "ingest.txt"),
               "--window", "lgbm/dataset/construct")
    print(json.dumps({"phase": "ingest", "device": h.device,
                      "rows": prep["rows"], "ingest_s": h.spans["ingest"],
                      "counters": _counters(("ingest.",)),
                      "attribution": _brief(res)}), flush=True)

    # ---- train: the operator's flow ------------------------------------
    t0 = time.perf_counter()
    bst = lgb.train(params, ds, num_boost_round=args.rounds)
    jax.block_until_ready(bst.engine.score)
    first_s = time.perf_counter() - t0
    del bst
    gc.collect()
    obs.reset()
    d_train = os.path.join(args.out, "prof_train")
    t0 = time.perf_counter()
    bst = lgb.train(dict(params, tpu_profile_dir=d_train), ds,
                    num_boost_round=args.rounds,
                    keep_training_booster=True)
    jax.block_until_ready(bst.engine.score)
    second_s = time.perf_counter() - t0
    res = trace_attr.attribute(d_train, iters=args.rounds)
    _cli_table(d_train, os.path.join(args.out, "train.txt"),
               "--iters", str(args.rounds))
    print(json.dumps({"phase": "train", "rounds": args.rounds,
                      "first_train_s": first_s, "profiled_train_s": second_s,
                      "counters": _counters(("hist.", "goss.")),
                      "gauges": _counters(("train.",)), "attribution": _brief(res, 20)}),
          flush=True)

    # ---- cost of tracing ------------------------------------------------
    import jax.numpy as jnp
    engine = bst.engine
    n = args.cost_iters
    it0, n_trees = engine.iter_, len(engine.models)
    score0 = jnp.array(engine.score, copy=True)
    calls = []

    def turn() -> float:
        # back to the same state: the same keys, the same n trees
        engine.score = jnp.array(score0, copy=True)
        engine.iter_ = it0
        del engine.models[n_trees:]
        engine._invalidate_forest_cache()
        jax.block_until_ready(engine.score)
        c0 = obs.counter("hist.calls", sampled=1).value
        t = time.perf_counter()
        engine.train_chunk(n)
        jax.block_until_ready(engine.score)
        dt = time.perf_counter() - t
        calls.append(obs.counter("hist.calls", sampled=1).value - c0)
        return dt / n * 1e3

    turn()                                   # settle
    ms = {"off": [], "obs_on": [], "profiler": []}
    d_cost = os.path.join(args.out, "prof_cost")
    for _ in range(args.turns):
        ms["off"].append(turn())
        obs.enable(metrics=True, trace_dir=os.path.join(args.out, "spans"))
        ms["obs_on"].append(turn())
        obs.disable()
        shutil.rmtree(d_cost, ignore_errors=True)
        jax.profiler.start_trace(d_cost)
        try:
            ms["profiler"].append(turn())
        finally:
            jax.profiler.stop_trace()
    obs.export_chrome_trace()
    _cli_table(d_cost, os.path.join(args.out, "steady.txt"),
               "--iters", str(n), "--window", "lgbm/train/fused_chunk")
    print(json.dumps({"phase": "cost", "iters_a_turn": n,
                      "train_iter_ms": ms, "hist_calls_a_turn": calls,
                      "steady": _brief(trace_attr.attribute(
                          d_cost, iters=n, window="lgbm/train/fused_chunk")),
                      "trees": bst.current_iteration()}), flush=True)
    if not args.keep_dumps:
        for d in (d_ingest, d_train, d_cost):
            shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
