"""Observability subsystem (lightgbm_tpu/obs/; docs/observability.md).

What these tests pin, per pillar:

* **Metrics registry** — thread-safety under concurrent increments
  (serving is threaded), label fan-out, kind-collision errors, and the
  JSONL / Prometheus exporters' formats.
* **Tracing** — span nesting (per-thread stack, parent/depth args) and
  Chrome-trace export schema validity: the file must be loadable by
  Perfetto, i.e. ``traceEvents`` of ``ph:"X"`` complete events with
  microsecond ``ts``/``dur`` and child spans contained in their parent.
* **Persistence** — metrics survive checkpoint/restore: a
  ``resume_from=`` cycle CONTINUES the interrupted run's counters
  (train.iterations reaches the total round count, the resume counter
  increments) instead of restarting them at zero.
* **Device telemetry** — the CompileWatch signal as a continuous
  metric: warm serving increments ``compile.requests`` by ZERO, and
  the stack-cache hit counter proves the warm path was taken.
* **Off-by-default** — a run without ``tpu_metrics`` records nothing
  (the registry stays empty; spans are the shared no-op context).
"""
import json
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import tracing as obs_tracing


@pytest.fixture(autouse=True)
def _isolated_obs(monkeypatch):
    """Every test gets a clean, DISABLED obs world and cannot leak an
    enabled registry (or a pinned process-global trace dir) into the
    rest of tier-1 — the off-by-default guarantee the suite relies on
    for its timing."""
    obs.disable()
    obs.reset()
    monkeypatch.setattr(obs_tracing, "_dir", None)
    yield
    obs.disable()
    obs.reset()
    monkeypatch.setattr(obs_tracing, "_dir", None)


def _data(n=1200, f=8, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 20}


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_thread_safety_under_concurrent_increments():
    reg = obs_metrics.MetricsRegistry()
    threads, per_thread, n_threads = [], 5000, 8

    def hammer(i):
        # same counter from every thread + get-or-create races on a
        # per-thread labeled one + histogram observes
        c = reg.counter("stress.total")
        mine = reg.counter("stress.labeled", thread=i % 2)
        h = reg.histogram("stress.lat")
        for _ in range(per_thread):
            c.inc()
            mine.inc()
            h.observe(0.001)

    for i in range(n_threads):
        t = threading.Thread(target=hammer, args=(i,))
        threads.append(t)
        t.start()
    for t in threads:
        t.join()

    total = n_threads * per_thread
    assert reg.get("stress.total").value == total
    assert (reg.get("stress.labeled", thread=0).value
            + reg.get("stress.labeled", thread=1).value) == total
    h = reg.get("stress.lat")
    assert h.count == total
    assert sum(h.bucket_counts) == total


def test_registry_labels_kinds_and_exporters():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("req", model="a").inc(3)
    reg.counter("req", model="b").inc()
    reg.gauge("hbm.bytes_limit").set(1e9)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)

    # same name, different labels -> distinct metrics; kind collision
    # on the same (name, labels) key is an error, not silent reuse
    assert reg.get("req", model="a").value == 3
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("req", model="a")

    snap = reg.snapshot()
    assert snap["schema"] == "lightgbm-tpu-metrics-v1"
    by_name = {}
    for m in snap["metrics"]:
        by_name.setdefault(m["name"], []).append(m)
    assert len(by_name["req"]) == 2
    lat = by_name["lat"][0]
    assert lat["count"] == 3 and lat["min"] == 0.05 and lat["max"] == 99.0
    # +inf auto-appended, cumulative export is per-bucket counts here
    assert [b for b, _c in lat["buckets"]] == [0.1, 1.0, "+Inf"]
    assert [c for _b, c in lat["buckets"]] == [1, 1, 1]
    # the whole snapshot must be JSON-able (the JSONL dump contract)
    json.dumps(snap)

    prom = reg.prometheus_text()
    assert '# TYPE req counter' in prom
    assert 'req{model="a"} 3' in prom
    # Prometheus histogram semantics: cumulative buckets + sum/count
    assert 'lat_bucket{le="0.1"} 1' in prom
    assert 'lat_bucket{le="1"} 2' in prom
    assert 'lat_bucket{le="+Inf"} 3' in prom
    assert 'lat_count 3' in prom


def test_dump_jsonl_appends_parseable_lines(tmp_path):
    path = str(tmp_path / "m" / "metrics.jsonl")
    obs.enable(metrics=True)
    obs.inc("x")
    obs.dump_jsonl(path)
    obs.inc("x")
    obs.dump_jsonl(path)
    lines = open(path).read().splitlines()
    assert len(lines) == 2
    snaps = [json.loads(ln) for ln in lines]
    vals = [[m["value"] for m in s["metrics"] if m["name"] == "x"][0]
            for s in snaps]
    assert vals == [1, 2]


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
def test_span_nesting_and_chrome_trace_schema(tmp_path):
    obs.enable(metrics=True, trace_dir=str(tmp_path))
    with obs.span("outer", phase="test"):
        assert obs.span_stack() == ["outer"]
        with obs.span("inner"):
            assert obs.span_stack() == ["outer", "inner"]
    assert obs.span_stack() == []

    out = obs.export_chrome_trace()
    assert out is not None and out.endswith(".json")
    doc = json.load(open(out))
    # Perfetto/chrome://tracing JSON object form: a traceEvents list of
    # complete events with microsecond ts/dur, plus the process/thread
    # naming metadata rows the export prepends
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    # the export envelope carries the wall/monotonic pair the
    # cross-rank merger rebases on (scripts/trace_merge.py)
    assert {"ts", "monotonic"} <= set(doc["otherData"])
    events = {e["name"]: e for e in doc["traceEvents"]
              if e["ph"] == "X"}
    assert set(events) == {"outer", "inner"}
    for e in events.values():
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    inner, outer = events["inner"], events["outer"]
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["depth"] == 1
    assert outer["args"]["phase"] == "test"
    # containment: the child renders inside the parent on the timeline
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1.0
    # spans double as duration histograms in the registry
    assert obs.registry().get("outer").count == 1


def test_trace_buffer_bounded_and_dropped_counted(monkeypatch):
    monkeypatch.setattr(obs_tracing, "MAX_EVENTS", 4)
    obs.enable(trace=True, metrics=False)
    for i in range(9):
        with obs.span(f"s{i}"):
            pass
    # oldest-dropped: a long-lived process keeps its most RECENT
    # window (the one a p99 postmortem needs), counting the evictions
    assert [e["name"] for e in obs_tracing.events()] == \
        ["s5", "s6", "s7", "s8"]
    assert obs_tracing.dropped_events() == 5


def test_span_is_only_a_profiler_annotation_when_disabled():
    # off-by-default hot-path cost: one bool check and a profiler
    # annotation (a no-op outside a profiler session); no clock, no
    # buffer, nothing in the registry
    import jax
    sp = obs.span("a", rows=3)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    with sp as entered:
        entered.set(hit=True)       # the one interface, on or off
    assert obs.registry().get("a") is None
    assert obs_tracing.events() == []
    # force=True measures regardless (the utils/timer shim contract)
    with obs.span("forced", force=True):
        pass
    assert obs.registry().get("forced").count == 1


def test_timer_shim_records_into_registry():
    from lightgbm_tpu.utils.timer import (log_timers, reset_timers,
                                          timed, timer_totals)
    with timed("phase_a"):
        pass
    with timed("phase_a"):
        pass
    totals = timer_totals()
    assert "phase_a" in totals and totals["phase_a"] >= 0.0
    assert obs.registry().get("phase_a").count == 2
    log_timers()                      # smoke: reads the same registry
    # reset_timers clears TIMERS (histograms) only — cumulative
    # counters/gauges (compile, restart telemetry) are not timers
    obs.counter("compile.requests").inc(5)
    obs.gauge("hbm.bytes_limit").set(1.0)
    reset_timers()
    assert obs.registry().get("phase_a") is None
    assert obs.counter("compile.requests").value == 5
    assert obs.gauge("hbm.bytes_limit").value == 1.0


# ---------------------------------------------------------------------------
# end-to-end: train + warm predict with tpu_metrics=true
# ---------------------------------------------------------------------------
def test_train_and_warm_predict_populate_metrics(tmp_path):
    dump = str(tmp_path / "metrics.jsonl")
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    # fuse disabled so the PER-ROUND loop (train/round, train/update,
    # train/step spans) is the path under test; fused-chunk training
    # records train/fused instead
    params = dict(PARAMS, tpu_metrics=True, tpu_metrics_dump=dump,
                  tpu_trace_dir=str(tmp_path / "tr"), tpu_fuse_iters=1)
    bst = lgb.train(params, ds, num_boost_round=5)
    p1 = bst.predict(X[:256])
    p2 = bst.predict(X[:256])         # warm: same shape bucket
    np.testing.assert_allclose(p1, p2)

    snap = bst.metrics()
    names = {m["name"] for m in snap["metrics"]}
    # per-round phase timings, predict latency histogram, cache-hit
    # counters, compile-count and cache-size gauges (ISSUE acceptance)
    assert {"train/round", "train/update", "train/step",
            "dataset/construct", "predict/call",
            "predict.requests", "predict.rows",
            "train.iterations", "compile.requests",
            "compile.predict_programs"} <= names
    get = {m["name"]: m for m in snap["metrics"]}
    assert get["train.iterations"]["value"] == 5
    assert get["train/round"]["count"] == 5
    assert get["predict.requests"]["value"] == 2
    assert get["predict.rows"]["value"] == 512
    assert get["predict/call"]["count"] == 2
    assert get["compile.predict_programs"]["value"] >= 1
    # second predict hit the stacked-forest cache
    assert get["predict.stack_cache_hits"]["value"] >= 1

    # the run's end wrote the JSONL dump + the Chrome trace
    lines = [ln for ln in open(dump).read().splitlines() if ln.strip()]
    assert lines and json.loads(lines[-1])["schema"] \
        == "lightgbm-tpu-metrics-v1"
    trace = obs.export_chrome_trace()
    assert trace is not None
    tnames = {e["name"] for e in json.load(open(trace))["traceEvents"]}
    assert {"train/round", "train/update", "predict/call"} <= tnames


def test_warm_serving_compiles_zero_as_metric():
    """The CompileWatch signal as a gauge: after the cold call, repeat
    predicts at the same bucketed shape add ZERO compile requests."""
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(PARAMS, tpu_metrics=True), ds,
                    num_boost_round=4)
    bst.predict(X[:200])              # cold: traces + compiles
    cold = obs.counter("compile.requests").value
    hits = obs.counter("predict.stack_cache_hits").value
    for _ in range(3):
        bst.predict(X[:200])
    assert obs.counter("compile.requests").value == cold
    assert obs.counter("predict.stack_cache_hits").value == hits + 3


def test_booster_metrics_on_streaming_and_file_boosters(tmp_path):
    """Booster.metrics() works on every booster flavor: the streaming
    engine (no GBDT.metrics_snapshot) and a model-file booster (no
    engine at all) fall back to the process-wide snapshot."""
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(PARAMS, tpu_metrics=True, tpu_streaming=True),
                    ds, num_boost_round=3)
    assert bst.metrics()["schema"] == "lightgbm-tpu-metrics-v1"
    path = str(tmp_path / "m.txt")
    bst.save_model(path)
    loaded = lgb.Booster(model_file=path)
    assert loaded.metrics()["schema"] == "lightgbm-tpu-metrics-v1"


def test_metrics_off_by_default_records_nothing():
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(PARAMS), ds, num_boost_round=3)
    bst.predict(X[:100])
    # no span histogram, no gated counter or gauge: only the work
    # counters the grower and ingest always keep (once a step or chunk)
    names = {m.name for m in obs.registry().metrics()}
    assert names and all(n.startswith(("hist.", "goss.", "ingest.",
                                       "split.", "tree.", "bundle.",
                                       "partition.", "compact.",
                                       "valid.", "eval."))
                         for n in names), names
    assert not obs.enabled()


def test_record_metrics_callback_sink():
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    sink = []
    lgb.train(dict(PARAMS), ds, num_boost_round=4,
              callbacks=[lgb.record_metrics(sink, period=2)])
    assert [s["iteration"] for s in sink] == [1, 3]
    names = {m["name"] for m in sink[-1]["metrics"]}
    assert "train/update" in names
    it = [m for m in sink[-1]["metrics"]
          if m["name"] == "train.iterations"][0]
    assert it["value"] == 4
    with pytest.raises(TypeError, match="list or a callable"):
        lgb.record_metrics(sink=42)


def test_crashed_run_still_writes_exports(tmp_path):
    """The observability artifacts matter MOST on runs that die: a
    training run that raises mid-loop must still write the configured
    metrics dump and Chrome trace."""
    dump = str(tmp_path / "crash.jsonl")
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    params = dict(PARAMS, tpu_metrics=True, tpu_metrics_dump=dump,
                  tpu_trace_dir=str(tmp_path / "tr"),
                  checkpoint_dir=str(tmp_path / "ck"),
                  checkpoint_interval=2,
                  tpu_fault_inject="exn:iter=3")
    with pytest.raises(lgb.LightGBMError, match="injected failure"):
        lgb.train(params, ds, num_boost_round=10)
    snap = json.loads(open(dump).read().splitlines()[-1])
    names = {m["name"] for m in snap["metrics"]}
    assert "train/round" in names
    import glob
    traces = glob.glob(str(tmp_path / "tr" / "trace_*.json"))
    assert traces and json.load(open(traces[0]))["traceEvents"]


# ---------------------------------------------------------------------------
# persistence: metrics survive checkpoint/restore
# ---------------------------------------------------------------------------
def test_metrics_survive_checkpoint_restore_cycle(tmp_path):
    """Interrupt at iteration 17 (checkpoint at 10), wipe the registry
    (a restarted process starts empty), resume: the restored counters
    CONTINUE — train.iterations ends at the full round total and the
    resume counter increments across the cycle."""
    ckdir = str(tmp_path / "ck")
    X, y = _data(n=2000)
    params = dict(PARAMS, tpu_metrics=True, checkpoint_dir=ckdir,
                  checkpoint_interval=10,
                  tpu_fault_inject="exn:iter=17")

    ds = lgb.Dataset(X, label=y)
    with pytest.raises(lgb.LightGBMError, match="injected failure"):
        lgb.train(params, ds, num_boost_round=30)
    assert obs.counter("train.iterations").value == 17
    assert obs.counter("checkpoint.saves").value >= 1

    # simulate the restarted process: empty registry, metrics off until
    # the resuming run's Config re-enables them
    obs.disable()
    obs.reset()
    assert obs.registry().metrics() == []

    ds = lgb.Dataset(X, label=y)
    resumed = lgb.train(params, ds, num_boost_round=30,
                        resume_from=ckdir)
    assert resumed.num_trees() == 30
    # 10 iterations adopted from the checkpoint's obs state + 20 run
    # here — a fresh-start registry would read 20
    assert obs.counter("train.iterations").value == 30
    assert obs.counter("train.resumes").value == 1
    # the restore that powered THIS resume survives the state import —
    # EXACTLY once (the interrupted run never restored, so its saved
    # state lacks the metric; folding live values back on top of an
    # absent saved metric must not double-count)
    assert obs.counter("checkpoint.restores").value == 1
    assert obs.registry().get("checkpoint/restore").count == 1

    # a resume with metrics OFF must not repopulate the registry from
    # the checkpoint (off-by-default means empty, forced counters aside)
    obs.disable()
    obs.reset()
    ds = lgb.Dataset(X, label=y)
    off = {k: v for k, v in params.items() if k != "tpu_metrics"}
    lgb.train(off, ds, num_boost_round=30, resume_from=ckdir)
    assert obs.registry().get("train.iterations") is None
    assert obs.counter("train.resumes").value == 1      # forced


def test_registry_state_roundtrip_overwrites_not_merges():
    obs.enable(metrics=True)
    obs.inc("a", 7)
    obs.observe("h", 0.3)
    state = obs.export_state()
    obs.reset()
    obs.inc("a", 100)                 # pre-restore noise
    assert obs.import_state(state) == 2
    assert obs.counter("a").value == 7          # overwritten, not 107
    h = obs.registry().get("h")
    assert h.count == 1 and h.sum == pytest.approx(0.3)
    assert obs.import_state(None) == 0


# ---------------------------------------------------------------------------
# CLI: task=dump_metrics
# ---------------------------------------------------------------------------
def test_cli_dump_metrics_reads_jsonl(tmp_path, capsys):
    from lightgbm_tpu.app import run
    path = str(tmp_path / "m.jsonl")
    obs.enable(metrics=True)
    obs.inc("train.iterations", 12)
    obs.dump_jsonl(path)
    assert run([f"task=dump_metrics", f"data={path}",
                "verbosity=-1"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE train_iterations counter" in out
    assert "train_iterations 12" in out
    assert run([f"task=dump_metrics", f"data={path}", "format=json",
                "verbosity=-1"]) == 0
    snap = json.loads(capsys.readouterr().out)
    assert snap["schema"] == "lightgbm-tpu-metrics-v1"

    bad = tmp_path / "bad.jsonl"
    bad.write_text("{not json\n")
    with pytest.raises(lgb.LightGBMError, match="not valid JSON"):
        run([f"task=dump_metrics", f"data={bad}", "verbosity=-1"])
