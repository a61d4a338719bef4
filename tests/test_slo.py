"""Windowed SLIs + SLO evaluation (lightgbm_tpu/obs/slo.py).

What these tests pin:

* **Quantile accuracy** — SlidingHistogram.quantile vs
  ``numpy.percentile`` on known distributions, within one value-bucket
  width (the documented estimator resolution).
* **Windowing** — observations age out of the ring: a spike older than
  the window stops moving the quantile; slot recycling keeps memory
  bounded.
* **Derived gauges + thresholds** — evaluate() publishes
  slo.predict_p99_ms / slo.error_ratio / predict.cache_hit_ratio /
  slo.queue_depth into the registry; a threshold crossing flips the
  ``slo.breached{slo=...}`` gauge and counts the TRANSITION (not every
  evaluation) in ``slo.breaches``.
* **Wiring** — the tracker feeds off the existing obs funnels
  (span/inc/observe) only when SLO is enabled, and
  ``obs.export_state`` excludes the ephemeral slo.*/heartbeat.* names
  so checkpoints never carry process-local monotonic state.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import slo as obs_slo

PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 20}


def _data(n=1200, f=8, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


def _bucket_width_at(bounds, v):
    """Width of the value bucket containing v (the estimator's
    documented resolution)."""
    lo = 0.0
    for hi in bounds:
        if v <= hi:
            return (hi - lo) if hi != float("inf") else float("inf")
        lo = hi
    return float("inf")


# ---------------------------------------------------------------------------
# SlidingHistogram
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dist", ["uniform", "lognormal", "bimodal"])
def test_sliding_quantiles_match_numpy_within_bucket_width(dist):
    rng = np.random.default_rng(11)
    if dist == "uniform":
        vals = rng.uniform(0.0008, 0.3, size=8000)
    elif dist == "lognormal":
        vals = np.minimum(rng.lognormal(-5.0, 1.2, size=8000), 50.0)
    else:
        vals = np.concatenate([rng.uniform(0.001, 0.004, 6000),
                               rng.uniform(0.5, 2.0, 2000)])
    h = obs_slo.SlidingHistogram(window_s=300, slots=30)
    for v in vals:
        h.observe(float(v), now=1000.0)
    for q in (0.5, 0.9, 0.95, 0.99):
        est = h.quantile(q, now=1000.0)
        ref = float(np.percentile(vals, q * 100))
        tol = max(_bucket_width_at(h.bounds, ref),
                  _bucket_width_at(h.bounds, est))
        assert est == pytest.approx(ref, abs=tol), (dist, q)


def test_sliding_window_ages_out_old_observations():
    h = obs_slo.SlidingHistogram(window_s=60, slots=6)   # 10 s slots
    for _ in range(100):
        h.observe(10.0, now=5.0)          # slow spike at t=5
    # at t=30 the spike still dominates the window
    assert h.quantile(0.99, now=30.0) > 5.0
    for _ in range(100):
        h.observe(0.001, now=100.0)       # fast traffic at t=100
    # a window ending at t=100 starts after t=40: the spike is gone
    assert h.quantile(0.99, now=100.0) < 0.01
    assert h.count(now=100.0) == 100


def test_sliding_ring_memory_is_bounded_under_clock_advance():
    h = obs_slo.SlidingHistogram(window_s=10, slots=5)
    for t in range(0, 10_000, 7):
        h.observe(0.01, now=float(t))
    assert len(h._counts) == 5            # the ring never grows
    assert h.count(now=9997.0) <= 5 * 2   # only in-window slots counted


def test_empty_window_returns_none():
    h = obs_slo.SlidingHistogram(window_s=10, slots=5)
    assert h.quantile(0.99, now=0.0) is None
    h.observe(1.0, now=0.0)
    assert h.quantile(0.99, now=1000.0) is None   # aged out


def test_sliding_counter_window_total():
    c = obs_slo.SlidingCounter(window_s=60, slots=6)
    c.inc(5, now=5.0)
    c.inc(2, now=55.0)
    assert c.total(now=55.0) == 7.0
    assert c.total(now=100.0) == 2.0      # the t=5 slot aged out
    assert c.total(now=500.0) == 0.0


# ---------------------------------------------------------------------------
# SloTracker: derived gauges + threshold evaluation
# ---------------------------------------------------------------------------
def test_tracker_derives_gauges_and_ratios():
    t = obs_slo.SloTracker(window_s=300)
    for v in (0.002, 0.004, 0.010):
        t.feed_hist("predict/call", v, now=10.0)
    t.feed_hist("train/round", 1.5, now=10.0)
    for _ in range(10):
        t.feed_count("predict.requests", now=10.0)
    t.feed_count("predict.errors", now=10.0)
    t.feed_count("predict.stack_cache_hits", 3, now=10.0)
    t.feed_count("predict.stack_cache_misses", 1, now=10.0)
    slis = t.evaluate(now=10.0)
    assert slis["slo.error_ratio"] == pytest.approx(0.1)
    assert slis["predict.cache_hit_ratio"] == pytest.approx(0.75)
    assert 2.0 <= slis["slo.predict_p99_ms"] <= 25.0
    assert 1.0 <= slis["slo.round_p99_s"] <= 2.5
    assert slis["slo.queue_depth"] == 0.0
    # published into the registry
    reg = obs.registry()
    assert reg.get("slo.error_ratio").value == pytest.approx(0.1)
    assert reg.get("predict.cache_hit_ratio").value \
        == pytest.approx(0.75)


def test_threshold_breach_flips_gauge_and_counts_transitions():
    t = obs_slo.SloTracker(window_s=300,
                           thresholds={"predict_p99_ms": 5.0,
                                       "error_ratio": 0.5})
    reg = obs.registry()
    # healthy: 1 ms predictions
    for _ in range(50):
        t.feed_hist("predict/call", 0.001, now=10.0)
        t.feed_count("predict.requests", now=10.0)
    t.evaluate(now=10.0)
    assert reg.get("slo.breached", slo="predict_p99_ms").value == 0.0
    assert reg.get("slo.breaches", slo="predict_p99_ms") is None
    # regress: 50 ms predictions dominate the window
    for _ in range(200):
        t.feed_hist("predict/call", 0.050, now=20.0)
    t.evaluate(now=20.0)
    assert reg.get("slo.breached", slo="predict_p99_ms").value == 1.0
    assert reg.get("slo.breaches", slo="predict_p99_ms").value == 1.0
    # still breached: the gauge stays 1, the counter does NOT re-count
    t.evaluate(now=21.0)
    assert reg.get("slo.breached", slo="predict_p99_ms").value == 1.0
    assert reg.get("slo.breaches", slo="predict_p99_ms").value == 1.0
    # recover: the slow window ages out entirely
    for _ in range(50):
        t.feed_hist("predict/call", 0.001, now=400.0)
    t.evaluate(now=400.0)
    assert reg.get("slo.breached", slo="predict_p99_ms").value == 0.0
    # re-breach counts a SECOND transition
    for _ in range(200):
        t.feed_hist("predict/call", 0.050, now=410.0)
    t.evaluate(now=410.0)
    assert reg.get("slo.breaches", slo="predict_p99_ms").value == 2.0
    # error-ratio threshold never configured data -> no false breach
    assert reg.get("slo.breached", slo="error_ratio").value == 0.0


def test_unset_thresholds_are_gauge_only():
    t = obs_slo.SloTracker(window_s=300, thresholds={})
    t.feed_hist("predict/call", 99.0, now=1.0)
    t.evaluate(now=1.0)
    assert obs.registry().get("slo.breached",
                              slo="predict_p99_ms") is None


def test_unknown_threshold_keys_are_rejected_not_misrouted():
    # a typo'd key must not silently evaluate against the wrong SLI
    t = obs_slo.SloTracker(window_s=300,
                           thresholds={"round_p99_s": 5.0,
                                       "predict_p99_ms": 10.0})
    assert t.thresholds == {"predict_p99_ms": 10.0}
    t.evaluate(now=1.0)
    assert obs.registry().get("slo.breached", slo="round_p99_s") is None


def test_drained_window_drops_gauges_instead_of_freezing():
    t = obs_slo.SloTracker(window_s=60)
    for _ in range(20):
        t.feed_hist("predict/call", 0.8, now=10.0)
    t.evaluate(now=10.0)
    reg = obs.registry()
    assert reg.get("slo.predict_p99_ms").value > 100.0
    # traffic stops; the window drains — a frozen 800 ms gauge would
    # lie to every later scrape, so it must disappear
    t.evaluate(now=500.0)
    assert reg.get("slo.predict_p99_ms") is None
    assert reg.get("slo.error_ratio") is None
    assert reg.get("slo.queue_depth") is not None   # placeholder stays


# ---------------------------------------------------------------------------
# obs wiring
# ---------------------------------------------------------------------------
def test_obs_funnels_feed_tracker_only_when_slo_enabled():
    obs.enable(metrics=True)
    with obs.span("predict/call"):
        pass
    obs.inc("predict.requests")
    assert not obs.slo_enabled()          # metrics alone: no tracker
    obs.enable(slo=True)
    assert obs.slo_enabled()
    with obs.span("predict/call"):
        pass
    obs.observe("predict/call", 0.003)
    obs.inc("predict.requests", 2)
    t = obs_slo.tracker()
    assert t.hists["predict/call"].count() == 2
    assert t.counters["predict.requests"].total() == 2.0
    # snapshot runs an evaluation period: SLO gauges appear
    names = {m["name"] for m in obs.snapshot()["metrics"]}
    assert {"slo.predict_p99_ms", "slo.queue_depth"} <= names


def test_enable_slo_implies_metrics_and_merges_thresholds():
    obs.enable(slo=True, slo_thresholds={"predict_p99_ms": 10.0})
    assert obs.enabled()
    # a later enable ADDS a threshold without dropping window state
    obs_slo.feed_hist("predict/call", 0.001)
    obs.enable(slo=True, slo_thresholds={"error_ratio": 0.2})
    t = obs_slo.tracker()
    assert t.thresholds == {"predict_p99_ms": 10.0,
                            "error_ratio": 0.2}
    assert t.hists["predict/call"].count() == 1


def test_export_state_excludes_ephemeral_slo_and_heartbeat_state():
    obs.enable(metrics=True, slo=True)
    obs.heartbeat("train")
    obs.inc("train.iterations", 3)
    obs.inc("predict.stack_cache_hits")   # windowed ratio gets data
    obs.snapshot()                        # publishes slo.* gauges
    reg_names = {m.name for m in obs.registry().metrics()}
    assert "heartbeat.train" in reg_names
    assert "predict.cache_hit_ratio" in reg_names
    assert any(n.startswith("slo.") for n in reg_names)
    saved = {m["name"] for m in obs.export_state()["metrics"]}
    assert "train.iterations" in saved
    assert not any(n.startswith(("heartbeat.", "slo.")) for n in saved)
    # the windowed cache-hit ratio is SLO-derived state too: a resumed
    # process with the tracker off must not expose a dead process's
    # frozen ratio
    assert "predict.cache_hit_ratio" not in saved


def test_heartbeat_noop_when_metrics_off():
    assert not obs.enabled()
    obs.heartbeat("train")
    assert obs.registry().get("heartbeat.train") is None


def test_clean_training_retires_train_heartbeat(tmp_path):
    """Absent heartbeat = finished; stale heartbeat = wedged/crashed.
    A clean train() must retire its stamp so an idle post-training
    process reads healthy forever; a crashed one must leave the stale
    stamp behind as the 503 signal."""
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    lgb.train(dict(PARAMS, tpu_metrics=True), ds, num_boost_round=3)
    assert obs.registry().get("heartbeat.train") is None
    ds = lgb.Dataset(X, label=y)
    with pytest.raises(lgb.LightGBMError, match="injected failure"):
        lgb.train(dict(PARAMS, tpu_metrics=True,
                       tpu_fault_inject="exn:iter=2",
                       tpu_fault_marker=str(tmp_path)),
                  ds, num_boost_round=5)
    assert obs.registry().get("heartbeat.train") is not None


def test_erroring_predicts_still_stamp_serve_liveness():
    """Liveness means "the serving loop is running", not "requests
    succeed": a process drowning in malformed requests must stay
    /healthz-green (slo.error_ratio is the alert for that), so the
    serve heartbeat stamps on ATTEMPT."""
    X, y = _data()
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train(dict(PARAMS, tpu_metrics=True), ds,
                    num_boost_round=3)
    err0 = obs.counter("predict.errors").value
    req0 = obs.counter("predict.requests").value
    with pytest.raises(lgb.LightGBMError, match="number of features"):
        bst.predict(X[:10, :3])          # wrong feature count: raises
    assert obs.registry().get("heartbeat.serve") is not None
    assert obs.counter("predict.errors").value == err0 + 1
    assert obs.counter("predict.requests").value == req0 + 1


def test_slo_window_knob_alone_starts_tracker():
    from lightgbm_tpu.config import Config
    assert not obs.slo_enabled()
    Config({"tpu_metrics": True, "tpu_slo_window_s": 60.0,
            "verbosity": -1})
    assert obs.slo_enabled()
    assert obs_slo.tracker().window_s == 60.0


def test_slo_error_ratio_param_arms_the_breach_alert():
    """``tpu_slo_error_ratio`` through the train params starts the
    tracker with that threshold, and a window in which more than that
    share of predicts raise flips ``slo.breached{slo=error_ratio}``."""
    X, y = _data()
    assert not obs.slo_enabled()
    bst = lgb.train(dict(PARAMS, tpu_slo_error_ratio=0.25),
                    lgb.Dataset(X, label=y), num_boost_round=3)
    assert obs.slo_enabled()
    assert obs_slo.tracker().thresholds == {"error_ratio": 0.25}
    bst.predict(X[:10])
    obs_slo.tracker().evaluate()
    reg = obs.registry()
    assert reg.get("slo.breached", slo="error_ratio").value == 0.0
    for _ in range(3):
        with pytest.raises(lgb.LightGBMError):
            bst.predict(X[:10, :3])      # wrong feature count: raises
    obs_slo.tracker().evaluate()
    assert reg.get("slo.breached", slo="error_ratio").value == 1.0
