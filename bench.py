"""Benchmark: boosting iters/sec on synthetic Higgs-like data.

Driver contract: print ONE JSON line
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.

Config mirrors BASELINE.json's flagship headline: Higgs-10M, binary
classification, 28 dense features, num_leaves=127, max_bin=255. The
dataset is synthesized (no network in this environment; Higgs itself
is a download). Default 10M rows with GOSS + quantized gradients —
both reference-native speed features (goss.hpp + the gradient
discretizer) — which reach a BETTER held-out AUC than plain full-row
f32 scans at this shape (10M: 0.9467 vs 0.9433; 1M at equal 90
rounds: 0.9514 vs 0.9478 — measured round 4). For continuity with
rounds 1-3 the same run also times the higgs-1M PLAIN configuration
and embeds it in the metric string (``plain1m=...``), so protocol
changes can never masquerade as speedups.

Protocol (round-4 revision, addressing ADVICE r3):
- the model trains warmup+iters rounds with warmup = iters + 10 for
  EVERY config (GOSS needs the +10 to get past its unsampled first
  1/learning_rate rounds; plain keeps the same total so AUCs are
  at identical round counts);
- held-out AUC is measured at that fixed round count, comparable
  across configs and rounds;
- then THREE equal timed windows re-run the same chunk length and the
  MEDIAN is reported (tagged ``median-of-3`` in the metric string; a
  single window can catch a host stall — 5.3 vs 16.6 it/s were seen
  back-to-back in the earlier chip runs — and best-of-N would bias
  up).

Quality guards: (1) the main holdout AUC above; (2) a second guard
dataset (``synth_guard``) with strong interactions, 10% NaNs and two
categorical columns, trained at 200k rows — its AUC collapses if
categorical splits or missing-value routing regress (measured on the
v5e: 0.868 with categorical handling, 0.836 with categoricals treated
numeric; the 0.85 floor sits between).
The main synthetic is near-linearly separable (holdout AUC ~0.95 where
real Higgs sits ~0.845, BASELINE.md) and cannot catch those paths;
the guard exists for exactly that. Neither guard can catch
regressions confined to ranking/multiclass/DART paths — those live in
benchmarks/suite.py.

Extra flags (defaults reproduce the driver run):
  --rows N --holdout N --iters N --leaf-batch K --hist-mode pool|rebuild
  --plain (full-row f32 scans; also disables quantization)
  --goss/--quant (re-enable pieces after --plain; last wins)
  --no-guard2 / --no-plain1m (skip the secondary sections)

vs_baseline: BASELINE.md holds NO verified reference numbers (empty
mount). The ballpark comparator is CPU hist-LightGBM ~1.0 it/s at
Higgs-1M (BASELINE.md recollection), scaled linearly to 0.1 at 10M
and doubled for GOSS (~2x per the NeurIPS'17 ablations) -> 0.2
iters/sec for the default config. All UNVERIFIED; vs_baseline > 1
means faster than that recollection of CPU LightGBM.
"""
import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

N_FEATURES = 28
NUM_LEAVES = 127
MAX_BIN = 255
# UNVERIFIED ballparks, see module docstring + BASELINE.md
CPU_LIGHTGBM_BASELINE = {
    (True, 1_000_000): 2.0,     # (goss, rows): CPU GOSS at 1M
    (False, 1_000_000): 1.0,    # CPU plain hist at 1M
    (True, 10_000_000): 0.2,
    (False, 10_000_000): 0.1,
}


def synth_higgs(n, f, seed=0):
    """Higgs-like: mixture of informative kinematic-ish features.
    UNCHANGED since round 1 (headline continuity) — near-linear, no
    NaNs/categoricals; see synth_guard for those paths."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    logit = (X @ w * 0.5 + 0.8 * X[:, 0] * X[:, 1]
             + 0.5 * np.abs(X[:, 2]) - 0.4)
    y = (logit + rng.normal(scale=1.0, size=n) > 0).astype(np.float64)
    return X.astype(np.float64), y


def synth_guard(n, seed=7):
    """Categorical/NaN/interaction guard dataset: 10 numeric features
    (pairwise interactions dominate), one 12-way and one 40-way
    categorical with target-dependent effects, 10% NaNs in half the
    numeric columns (informative missingness)."""
    rng = np.random.default_rng(seed)
    Xn = rng.normal(size=(n, 10)).astype(np.float64)
    c1 = rng.integers(0, 12, size=n)
    c2 = rng.integers(0, 40, size=n)
    eff1 = rng.normal(size=12)[c1] * 1.2
    eff2 = rng.normal(size=40)[c2] * 0.8
    logit = (1.0 * Xn[:, 0] * Xn[:, 1] + 0.9 * Xn[:, 2] * Xn[:, 3]
             - 0.7 * Xn[:, 4] * np.abs(Xn[:, 5]) + eff1 + eff2)
    # informative missingness: NaN rows carry signal
    for j in range(5):
        miss = rng.uniform(size=n) < 0.10
        logit = logit + np.where(miss, 0.6, 0.0)
        Xn[miss, j] = np.nan
    y = (logit + rng.normal(scale=1.0, size=n) > 0).astype(np.float64)
    X = np.column_stack([Xn, c1.astype(np.float64),
                         c2.astype(np.float64)])
    return X, y


def peak_hbm_gib():
    import jax
    peak = (jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use")
    return None if peak is None else round(peak / 2**30, 2)


def _snap_gauge(snap, name):
    """Read one gauge value back out of an obs snapshot dict (the
    metric line below is composed from the SNAPSHOT, not from local
    variables, so the numbers in BENCH_*.json and in --metrics-json can
    never disagree)."""
    for m in snap["metrics"]:
        if m["name"] == name and not m.get("labels"):
            return m.get("value")
    return None


def _snap_total(snap, name):
    """Sum of one counter over its label sets in an obs snapshot."""
    vals = [m.get("value") or 0.0 for m in snap["metrics"]
            if m["name"] == name]
    return sum(vals) if vals else None


def run_config(X, y, X_ho, y_ho, params, iters, warmup, windows=3,
               cat_features="auto", measure_predict=True):
    """Train warmup+iters rounds, AUC there, then median of N timed
    windows of the same chunk length."""
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.metric import AUCMetric

    # split timers (VERDICT r4): construct_s is the host-side binning
    # (native C++ since r4, 13.5x); engine_init_s is GBDT.__init__ —
    # device upload of the bin matrices + score/partition init — which
    # dominates at 10M. perf.md reports the same decomposition.
    t0 = time.time()
    ds = lgb.Dataset(X, label=y, categorical_feature=cat_features)
    ds.construct()
    construct_s = time.time() - t0
    cfg = Config(params)
    t0 = time.time()
    eng = GBDT(cfg, ds)
    engine_init_s = time.time() - t0
    # warm the REMAINDER first (it absorbs GOSS's unsampled first
    # 1/lr rounds), then one full timed-length chunk: that second call
    # is the one that compiles the fused scan the windows reuse —
    # running it after the GOSS activation boundary matters, else the
    # fused GOSS chunk would first compile inside timed window 1
    first = (warmup - iters) if warmup > iters else min(iters, warmup)
    t0 = time.time()
    eng.train_chunk(first)
    jax.block_until_ready(eng.score)
    first_chunk_s = time.time() - t0
    # time-to-first-iteration: construct + engine init + the first
    # (compile-inclusive) boosting dispatch — the serving-relevant
    # startup cost a production retrain pays on EVERY job. The first
    # chunk runs a few real iterations too; at cold-compile scale that
    # overcount is noise, and warm-cache runs shrink it to exactly
    # those iterations.
    bin_time = (construct_s, engine_init_s,
                construct_s + engine_init_s + first_chunk_s)
    if warmup > iters:
        eng.train_chunk(min(iters, warmup))
        jax.block_until_ready(eng.score)
    # --profile-dir: jax.profiler trace around the FIRST timed window
    # (the steady state, matching the r5 attribution protocol), then
    # the raw-XSpace attribution feeds train.copy_share /
    # train.wall_busy_gap_ms — read back off the one snapshot below
    prof_dir = str(getattr(cfg, "tpu_profile_dir", "") or "").strip()
    if prof_dir:
        jax.profiler.start_trace(prof_dir)
    rates = []
    t0 = time.time()
    eng.train_chunk(iters)
    jax.block_until_ready(eng.score)
    window_s = time.time() - t0
    rates.append(iters / window_s)
    if prof_dir:
        # wall measured BEFORE stop_trace: writing the dump to disk is
        # not part of the traced window's wall time
        jax.profiler.stop_trace()
        from lightgbm_tpu.obs.trace_attr import profile_gauges
        profile_gauges(prof_dir, iters=iters, wall_ms=window_s * 1e3)
    # held-out AUC at the fixed warmup+iters round count (equal across
    # configs), between the timed windows so it inflates none of them
    pred = eng.predict(X_ho)
    auc = AUCMetric(cfg).eval(pred, y_ho, None)[0][1]
    # serving throughput (the inference engine's steady state: cached
    # device forest + bucketed batch shapes; benchmarks/predict_bench.py
    # has the full grid): median rows/sec over repeat 10k-row predicts,
    # after the warm call above — main config only, the continuity/
    # guard runs discard it
    predict_rps = None
    shap_rps = None
    if measure_predict:
        n_pred = min(10_000, len(X_ho))
        eng.predict(X_ho[:n_pred])                # warm this bucket
        pred_rates = []
        for _ in range(3):
            t0 = time.time()
            eng.predict(X_ho[:n_pred])
            pred_rates.append(n_pred / (time.time() - t0))
        predict_rps = statistics.median(pred_rates)
        # explain throughput (device SHAP: cached path tables + the
        # same bucketed shapes; docs/perf.md "Device SHAP") — a small
        # subset, SHAP programs are O(depth) heavier than predicts
        n_shap = min(8_000, len(X_ho))
        eng.predict_contrib(X_ho[:n_shap])        # tables + compile
        shap_rates = []
        for _ in range(3):
            t0 = time.time()
            eng.predict_contrib(X_ho[:n_shap])
            shap_rates.append(n_shap / (time.time() - t0))
        shap_rps = statistics.median(shap_rates)
    for _ in range(windows - 1):
        t0 = time.time()
        eng.train_chunk(iters)
        jax.block_until_ready(eng.score)
        rates.append(iters / (time.time() - t0))
    from lightgbm_tpu import obs as _obs
    _obs.set_gauge("bench.hist_partition",
                   float(getattr(eng, "hist_partition", False)),
                   force=True)
    return statistics.median(rates), auc, bin_time, predict_rps, shap_rps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--holdout", type=int, default=None)
    ap.add_argument("--iters", type=int, default=40)
    # warmup matches the timed chunk length (+10 so GOSS gets past its
    # unsampled first 1/lr rounds) for EVERY config -> equal-round AUCs
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--leaf-batch", type=int, default=None)
    ap.add_argument("--hist-mode", choices=["pool", "rebuild"],
                    default=None)
    class _Plain(argparse.Action):
        def __call__(self, parser, ns, values, option_string=None):
            ns.goss = ns.quant = False   # parse-time: later flags win
    ap.add_argument("--quant", action="store_true", default=True)
    ap.add_argument("--no-quant", dest="quant", action="store_false")
    ap.add_argument("--goss", action="store_true", default=True)
    ap.add_argument("--plain", action=_Plain, nargs=0,
                    help="full-row f32 scans (disables GOSS + quant; "
                         "a later --goss/--quant re-enables that piece)")
    ap.add_argument("--precise", action="store_true",
                    help="tpu_double_precision_hist (f32 histograms)")
    ap.add_argument("--partition", choices=["auto", "true", "false"],
                    default="auto",
                    help="leaf-ordered row partition "
                         "(tpu_hist_partition; docs/perf.md "
                         "'Partitioned histograms'): histograms scan "
                         "only the elected children's row spans")
    ap.add_argument("--ingest", choices=["auto", "device", "host"],
                    default="auto",
                    help="bin-assignment path for Dataset.construct "
                         "(tpu_ingest_device; docs/perf.md 'Ingest')")
    ap.add_argument("--compile-cache", type=str, default="",
                    help="persistent XLA compile cache dir "
                         "(tpu_compile_cache_dir; default "
                         "<repo>/.jax_cache, and "
                         "JAX_COMPILATION_CACHE_DIR wins over both): "
                         "a second run reloads programs instead of "
                         "recompiling — watch ttfi_s collapse")
    ap.add_argument("--no-donate", dest="donate", action="store_false",
                    default=True,
                    help="disable boosting-carry buffer donation "
                         "(tpu_donate=false) — the A/B arm for the "
                         "loop-state %%copy squeeze (docs/perf.md "
                         "'Iteration floor'); the metric line tags "
                         "donate=off")
    ap.add_argument("--profile-dir", type=str, default="",
                    help="jax.profiler trace dir for the first timed "
                         "window (tpu_profile_dir); the raw-XSpace "
                         "attribution (scripts/trace_attr.py) feeds "
                         "copy_share= / wall_busy_gap_ms= on the "
                         "metric line")
    ap.add_argument("--no-guard2", dest="guard2", action="store_false",
                    default=True)
    ap.add_argument("--no-plain1m", dest="plain1m",
                    action="store_false", default=True)
    ap.add_argument("--smoke", action="store_true",
                    help="pre-snapshot gate mode (scripts/check.sh): "
                         "single window, skip plain1m + guard2")
    ap.add_argument("--stream-rows", type=int, default=200_000,
                    help="rows for the streamed-training probe "
                         "(tpu_streaming=true, sharded over local "
                         "devices when >1; docs/perf.md 'Streamed x "
                         "sharded'). Emits stream_shards= / "
                         "stream_rows_per_sec= / allreduce_bytes= on "
                         "the metric line; 0 disables")
    ap.add_argument("--no-stream-overlap", dest="stream_overlap",
                    action="store_false", default=True,
                    help="run the streamed probe with "
                         "tpu_stream_overlap=false (synchronous "
                         "per-block dispatch) — the A/B arm for the "
                         "collective-hiding pipeline (docs/perf.md "
                         "'Communication/compute overlap'); the "
                         "metric line tags overlap=off")
    ap.add_argument("--metrics-json", type=str, default="",
                    help="append one obs metrics-snapshot JSONL line "
                         "(docs/observability.md schema) to PATH; also "
                         "enables tpu_metrics collection for the run")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="serve live GET /metrics | /metrics.json | "
                         "/healthz | /readyz on 127.0.0.1:PORT for the "
                         "duration of the run (tpu_metrics_port "
                         "semantics; scrape a long bench mid-flight)")
    args = ap.parse_args()
    if args.smoke:
        args.windows = 1
        args.plain1m = args.guard2 = False
        # keep the pre-snapshot gate fast: the streamed probe still
        # runs (the gate is where its trajectory lands) but smaller
        args.stream_rows = min(args.stream_rows, 100_000)
    if args.holdout is None:
        args.holdout = max(100_000, args.rows // 20)

    # every result names the device it ran on, on a line of its own
    # BEFORE the metric line (the driver reads the last line). A
    # measurement needs the chip: without one only --smoke runs (the
    # CPU rehearsal of scripts/check.sh, which checks that the path
    # works and the line parses), and its metric is named for what it
    # is so a CPU timing can never be read as a device number.
    import jax
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "device_count": len(jax.devices())}), flush=True)
    if dev.platform != "tpu" and not args.smoke:
        sys.exit(f"bench.py measures on a TPU and found "
                 f"{dev.platform!r}; only --smoke (the CPU rehearsal) "
                 f"runs without one")
    if args.warmup is None:
        args.warmup = args.iters + 10

    X, y = synth_higgs(args.rows + args.holdout, N_FEATURES)
    X, X_ho = X[:args.rows], X[args.rows:]
    y, y_ho = y[:args.rows], y[args.rows:]
    params = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "max_bin": MAX_BIN, "learning_rate": 0.1,
              "verbosity": -1}
    if args.leaf_batch is not None:
        params["tpu_leaf_batch"] = args.leaf_batch
    if args.hist_mode is not None:
        params["tpu_hist_mode"] = args.hist_mode
    # explicit either way: tpu_auto_quantize would otherwise flip the
    # un-set case back on at >=500k rows, making --no-quant a no-op
    params["use_quantized_grad"] = bool(args.quant)
    if args.goss:
        params["data_sample_strategy"] = "goss"
    if args.precise:
        params["tpu_double_precision_hist"] = True
    if args.ingest != "auto":
        params["tpu_ingest_device"] = ("true" if args.ingest == "device"
                                       else "false")
    params["tpu_hist_partition"] = args.partition
    if not args.donate:
        params["tpu_donate"] = "false"
    if args.profile_dir:
        params["tpu_profile_dir"] = args.profile_dir
    # the persistent compile cache sits where JAX_COMPILATION_CACHE_DIR
    # says; without it, at one fixed place inside the checkout (the
    # path is part of the cache key: a directory that moves never hits)
    cache = args.compile_cache or (
        "" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
        else os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".jax_cache"))
    if cache:
        params["tpu_compile_cache_dir"] = cache
    from lightgbm_tpu import obs
    if args.metrics_json:
        obs.enable(metrics=True)
    if args.metrics_port:
        # live mid-run scraping: rolling SLO gauges + heartbeats on a
        # localhost endpoint (the same plane tpu_metrics_port serves)
        from lightgbm_tpu.obs.server import start_server
        obs.enable(metrics=True, slo=True)
        start_server(args.metrics_port)

    ips, auc, bin_time, predict_rps, shap_rps = run_config(
        X, y, X_ho, y_ho, params, args.iters, args.warmup, args.windows)
    # headline measurements become forced obs gauges, and the metric
    # line below reads them back from ONE snapshot — the snapshot is
    # the authority, the printed line a view of it (same keys as ever,
    # so BENCH_*.json parsing is unchanged)
    obs.set_gauge("bench.iters_per_sec", ips, force=True)
    obs.set_gauge("bench.holdout_auc", auc, force=True)
    obs.set_gauge("bench.construct_s", bin_time[0], force=True)
    obs.set_gauge("bench.engine_init_s", bin_time[1], force=True)
    obs.set_gauge("bench.ttfi_s", bin_time[2], force=True)
    obs.set_gauge("bench.predict_rps", predict_rps, force=True)
    obs.set_gauge("bench.shap_rows_per_sec", shap_rps, force=True)

    # continuity figure: the rounds-1..3 headline config (higgs-1M,
    # plain full-row f32) timed in the same process on the main run's
    # holdout rows
    if args.plain1m and args.rows >= 1_000_000 and (
            args.rows != 1_000_000 or args.goss or args.quant):
        n1 = 1_000_000
        p1 = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "max_bin": MAX_BIN, "learning_rate": 0.1,
              "verbosity": -1, "use_quantized_grad": False}
        # 40-iteration chunks: shorter ones fall below tpu_fuse_iters
        # and pay per-iteration dispatch (measured 2x slower)
        ips1, auc1, _, _, _ = run_config(
            X[:n1], y[:n1], X_ho[:100_000], y_ho[:100_000], p1,
            40, 50, windows=3, measure_predict=False)
        obs.set_gauge("bench.plain1m_iters_per_sec", ips1, force=True)
        obs.set_gauge("bench.plain1m_auc", auc1, force=True)

    # categorical/NaN/interaction guard (see module docstring)
    if args.guard2:
        Xg, yg = synth_guard(250_000)
        gp = {"objective": "binary", "num_leaves": 63, "max_bin": 255,
              "learning_rate": 0.1, "verbosity": -1}
        g_ips, g_auc, _, _, _ = run_config(Xg[:200_000], yg[:200_000],
                                        Xg[200_000:], yg[200_000:], gp,
                                        10, 40, windows=1,
                                        cat_features=[10, 11],
                                        measure_predict=False)
        obs.set_gauge("bench.guard2_auc", g_auc, force=True)

    # streamed-training trajectory (docs/perf.md "Streamed x sharded"):
    # a small forced-streaming train — sharded over the local devices
    # when the platform has more than one — so BENCH_*.json carries
    # stream_rows_per_sec / allreduce_bytes alongside the resident
    # headline instead of an empty streamed history
    if args.stream_rows > 0:
        import jax
        import lightgbm_tpu as lgb
        ns = min(args.rows, args.stream_rows)
        sp = {"objective": "binary", "num_leaves": NUM_LEAVES,
              "max_bin": MAX_BIN, "learning_rate": 0.1,
              "verbosity": -1, "tpu_streaming": "true",
              "tpu_stream_block_rows": 1 << 16,
              "tpu_stream_overlap":
                  "auto" if args.stream_overlap else "false"}
        shards = max(1, jax.local_device_count())
        if shards > 1:
            sp["tree_learner"] = "data"
            sp["tpu_mesh_shape"] = shards
        s_trees = 4
        sds = lgb.Dataset(X[:ns], label=y[:ns], params=dict(sp))
        t0 = time.time()
        sbst = lgb.train(sp, sds, num_boost_round=s_trees)
        s_secs = max(time.time() - t0, 1e-9)
        cs = sbst.engine.comm_stats
        obs.set_gauge("bench.stream_shards", sbst.engine.R, force=True)
        obs.set_gauge("bench.stream_rows_per_sec",
                      ns * s_trees / s_secs, force=True)
        obs.set_gauge("bench.stream_allreduce_bytes",
                      cs["allreduce_bytes"], force=True)
        obs.set_gauge("bench.stream_overlap",
                      1.0 if args.stream_overlap else 0.0, force=True)
        del sbst, sds

    peak = peak_hbm_gib()
    if peak is not None:
        obs.set_gauge("bench.peak_hbm_gib", peak, force=True)

    # ONE snapshot is the source for the metric line, the optional
    # JSONL dump, and (with tpu_metrics on) the full phase-timer /
    # cache-hit / compile-gauge picture of the run
    snap = obs.snapshot()
    if args.metrics_json:
        obs.dump_jsonl(args.metrics_json, snap)

    ips = _snap_gauge(snap, "bench.iters_per_sec")
    extras = "; goss" if args.goss else "; full-rows"
    if args.quant:
        extras += "+quantized"
    extras += f"; median-of-{args.windows}"
    extras += (f"; predict_rps="
               f"{_snap_gauge(snap, 'bench.predict_rps'):.0f}")
    v = _snap_gauge(snap, "bench.shap_rows_per_sec")
    if v is not None:
        # device-SHAP explain throughput on the same holdout rows
        extras += f"; shap_rps={v:.0f}"
    v = _snap_gauge(snap, "bench.hist_partition")
    extras += f"; partition={'on' if v else 'off'}"
    if not args.donate:
        # the --no-donate A/B arm tags itself so a pasted metric line
        # can never pass an undonated number off as the flagship
        extras += "; donate=off"
    v = _snap_gauge(snap, "train.copy_share")
    if v is not None:
        # --profile-dir attribution (scripts/trace_attr.py): fraction
        # of device busy in loop-state %copy ops — the signal the
        # donation pass squeezes
        extras += f"; copy_share={v:.4f}"
    v = _snap_gauge(snap, "train.comm_share")
    if v is not None:
        # collective busy share from the same attribution — read with
        # the gap: overlap keeps comm busy, shrinks the gap
        extras += f"; comm_share={v:.4f}"
    v = _snap_gauge(snap, "train.wall_busy_gap_ms")
    if v is not None:
        # per-iter wall-vs-busy gap: the stall residue the overlap
        # pipeline (and the donation pass before it) squeezes — carried
        # whenever attribution ran, not only when copy_share did
        extras += f"; wall_busy_gap_ms={v:.2f}"
    v = _snap_total(snap, "hist.cols_scanned")
    if v:
        # the structural win the partition exists for: total columns
        # the histogram calls were handed (masked = n_pad x rounds)
        extras += f"; hist_rows_scanned={v:.3g}"
    v = _snap_gauge(snap, "bench.stream_rows_per_sec")
    if v is not None:
        # the streamed-training trajectory: rows x trees per second on
        # the out-of-core path, the shard count it ran at, and the
        # per-level collective payload it moved
        extras += (
            f"; stream_shards="
            f"{int(_snap_gauge(snap, 'bench.stream_shards'))}"
            f"; overlap="
            f"{'on' if _snap_gauge(snap, 'bench.stream_overlap') else 'off'}"
            f"; stream_rows_per_sec={v:.0f}"
            f"; allreduce_bytes="
            f"{int(_snap_gauge(snap, 'bench.stream_allreduce_bytes'))}")
    v = _snap_gauge(snap, "bench.plain1m_iters_per_sec")
    if v is not None:
        extras += (f"; plain1m={v:.2f}@auc"
                   f"{_snap_gauge(snap, 'bench.plain1m_auc'):.4f}"
                   f"(median-of-3)")
    v = _snap_gauge(snap, "bench.guard2_auc")
    if v is not None:
        extras += f"; guard2_auc={v:.4f}"
        if v < 0.85:
            extras += " GUARD2_BELOW_FLOOR(0.85)"
    v = _snap_gauge(snap, "bench.peak_hbm_gib")
    if v is not None:
        extras += f"; peak_hbm_gib={v}"
    shape_tag = ("higgs1m-synth" if args.rows == 1_000_000
                 else f"higgs{args.rows // 1_000_000}m-synth"
                 if args.rows % 1_000_000 == 0
                 else f"higgs{args.rows}-synth")
    base = CPU_LIGHTGBM_BASELINE.get(
        (args.goss, args.rows),
        (2.0 if args.goss else 1.0) * 1e6 / max(args.rows, 1))
    result = {
        "metric": (("" if dev.platform == "tpu"
                    else f"{dev.platform}_rehearsal_")
                   + "boosting_iters_per_sec "
                   f"({shape_tag} nl={NUM_LEAVES} mb={MAX_BIN}; "
                   f"holdout_auc="
                   f"{_snap_gauge(snap, 'bench.holdout_auc'):.4f}"
                   f"@{args.warmup + args.iters}rounds; construct_s="
                   f"{_snap_gauge(snap, 'bench.construct_s'):.1f}; "
                   f"engine_init_s="
                   f"{_snap_gauge(snap, 'bench.engine_init_s'):.1f}; "
                   f"ttfi_s={_snap_gauge(snap, 'bench.ttfi_s'):.1f}"
                   f"{extras})"),
        "value": round(ips, 4),
        "unit": "iters/sec",
        "vs_baseline": round(ips / base, 4),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
