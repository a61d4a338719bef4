"""Live metrics endpoint (lightgbm_tpu/obs/server.py).

What these tests pin:

* **Route smoke** — /metrics serves parseable Prometheus text of the
  live registry, /metrics.json the v1 snapshot schema, unknown paths
  404; all bound to 127.0.0.1 only.
* **Health semantics** — /readyz is 503 until a heartbeat is stamped,
  200 while one is fresh, 503 again when every stamp is stale (the
  wedged-loop signal); /healthz tolerates "no heartbeat yet" but fails
  on staleness.
* **Robustness** — a port already in use logs-and-disables instead of
  crashing the run; the serve thread is a daemon (cannot hang process
  exit); start_server is idempotent and process-global.
* **Acceptance** — a warm serving loop scraped mid-run reports a
  rolling slo.predict_p99_ms within one histogram-bucket width of the
  offline percentile of the same run's recorded latencies, and a
  forced breach (threshold below the observed p99) flips slo.breached
  within one evaluation period (== one scrape).
"""
import json
import socket
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import server as obs_server
from lightgbm_tpu.obs import slo as obs_slo


def _get(url):
    """(status, body_text) without raising on 4xx/5xx."""
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture()
def live_server():
    obs.enable(metrics=True)
    srv = obs_server.start_server(0)     # ephemeral localhost port
    assert srv is not None
    yield srv
    obs_server.stop_server()


def test_metrics_routes_smoke(live_server):
    obs.inc("train.iterations", 7)
    obs.observe("predict/call", 0.004)

    code, text = _get(live_server.url + "/metrics")
    assert code == 200
    assert "# TYPE train_iterations counter" in text
    assert "train_iterations 7" in text
    assert "predict_call_count 1" in text

    code, body = _get(live_server.url + "/metrics.json")
    assert code == 200
    snap = json.loads(body)
    assert snap["schema"] == "lightgbm-tpu-metrics-v1"
    assert any(m["name"] == "train.iterations"
               for m in snap["metrics"])

    code, _ = _get(live_server.url + "/nope")
    assert code == 404


def test_health_and_ready_follow_heartbeats(live_server):
    # no heartbeat yet: live (the reply proves it) but NOT ready
    code, body = _get(live_server.url + "/healthz")
    assert code == 200 and json.loads(body)["status"] == "ok"
    code, body = _get(live_server.url + "/readyz")
    assert code == 503
    assert json.loads(body)["status"] == "no_heartbeat"

    obs.heartbeat("train")
    assert _get(live_server.url + "/healthz")[0] == 200
    code, body = _get(live_server.url + "/readyz")
    assert code == 200
    assert "train" in json.loads(body)["heartbeats"]

    # stale: back-date the stamp past the staleness timeout
    obs.registry().gauge("heartbeat.train").set(
        time.monotonic() - 10 * obs_server.DEFAULT_HEARTBEAT_TIMEOUT_S)
    code, body = _get(live_server.url + "/healthz")
    assert code == 503 and json.loads(body)["status"] == "stale"
    assert _get(live_server.url + "/readyz")[0] == 503
    # a fresh stamp on ANY heartbeat recovers both probes
    obs.heartbeat("serve")
    assert _get(live_server.url + "/healthz")[0] == 200
    assert _get(live_server.url + "/readyz")[0] == 200


def test_port_in_use_disables_instead_of_crashing():
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        assert obs_server.start_server(port) is None
        assert obs_server.server() is None
    finally:
        blocker.close()


def test_port_in_use_raises_when_required():
    """The fleet path (serve/fleet.py) asks for required=True: a
    replica whose /metrics+/readyz cannot bind is invisible to its
    router — it must fail its launch loudly, not serve blind."""
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        with pytest.raises(RuntimeError, match="REQUIRED"):
            obs_server.start_server(port, required=True)
        assert obs_server.server() is None
    finally:
        blocker.close()


def test_ephemeral_port_exposes_actually_bound_port():
    """port=0 binds an ephemeral port and the returned server's .port
    is the real one — fleet replicas bind 0 and publish what they
    got."""
    srv = obs_server.start_server(0, required=True)
    try:
        assert srv is not None and srv.port > 0
        code, _body = _get(srv.url + "/healthz")
        assert code in (200, 503)     # answering proves the port
    finally:
        obs_server.stop_server()


def test_start_server_is_idempotent_and_daemonized():
    srv = obs_server.start_server(0)
    assert srv._thread.daemon            # cannot hang process exit
    again = obs_server.start_server(srv.port + 1)   # warns, keeps first
    assert again is srv
    assert obs_server.start_server(0) is srv
    obs_server.stop_server()
    assert obs_server.server() is None
    obs_server.stop_server()             # idempotent


def test_metrics_port_param_starts_the_endpoint():
    """``tpu_metrics_port`` through the train params: 0 (the default)
    starts nothing; a free port serves the run's counters on /metrics
    with the windowed SLOs on, and ``tpu_heartbeat_timeout`` is the
    endpoint's staleness limit."""
    from lightgbm_tpu.parallel.launch import _free_port
    rng = np.random.default_rng(9)
    X = rng.normal(size=(800, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    lgb.train(dict(params, tpu_metrics_port=0), lgb.Dataset(X, label=y),
              num_boost_round=2)
    assert obs_server.server() is None and not obs.slo_enabled()
    port = _free_port()
    try:
        lgb.train(dict(params, tpu_metrics_port=port,
                       tpu_heartbeat_timeout=7.0),
                  lgb.Dataset(X, label=y), num_boost_round=3)
        srv = obs_server.server()
        assert srv is not None and srv.port == port
        assert srv._httpd.heartbeat_timeout_s == 7.0
        assert obs.slo_enabled()
        code, text = _get(srv.url + "/metrics")
        assert code == 200 and "train_iterations 3" in text
    finally:
        obs_server.stop_server()


def _bucket_width_at(bounds, v):
    lo = 0.0
    for hi in bounds:
        if v <= hi:
            return (hi - lo) if hi != float("inf") else float("inf")
        lo = hi
    return float("inf")


def _prom_value(text, name):
    for line in text.splitlines():
        if line.startswith(name) and " " in line:
            head, val = line.rsplit(" ", 1)
            if head == name or head.startswith(name + "{"):
                return float(val)
    return None


def test_model_file_booster_serving_turns_ready(tmp_path):
    """The documented load-model-and-serve deployment: a Booster built
    from a model FILE routes predicts through the host model, which
    must carry the same serve instrumentation as the engine path —
    otherwise /readyz never turns 200 for exactly the pod /readyz was
    built for."""
    rng = np.random.default_rng(9)
    X = rng.normal(size=(800, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=3)
    path = str(tmp_path / "m.txt")
    bst.save_model(path)

    obs.enable(metrics=True, slo=True)
    loaded = lgb.Booster(model_file=path)
    srv = obs_server.start_server(0)
    try:
        assert _get(srv.url + "/readyz")[0] == 503
        loaded.predict(X[:64])           # the documented warmup call
        assert _get(srv.url + "/readyz")[0] == 200
        assert obs.counter("predict.requests").value >= 1
        assert obs.registry().get("predict/call").count >= 1
        # pred_contrib detours through the host model on a TRAINED
        # booster too — same instrumentation
        before = obs.counter("predict.requests").value
        bst.predict(X[:16], pred_contrib=True)
        assert obs.counter("predict.requests").value == before + 1
    finally:
        obs_server.stop_server()


def test_warm_serving_scrape_reports_rolling_p99_and_breach(tmp_path):
    """ISSUE acceptance: mid-run /metrics scrape vs offline percentile
    of the same run's recorded latencies, plus a forced breach."""
    rng = np.random.default_rng(5)
    X = rng.normal(size=(1500, 8))
    y = (X[:, 0] > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    # threshold far below any real predict latency -> guaranteed breach
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "tpu_metrics": True, "tpu_slo_predict_p99_ms": 1e-6}
    bst = lgb.train(params, ds, num_boost_round=5)
    assert obs.slo_enabled()             # the threshold knob engaged it
    bst.predict(X[:256])                 # cold call: compiles
    # restart the rolling window at steady state so the one-off compile
    # latency is not in the window the offline percentile can't see
    obs_slo.reset()
    obs.enable(slo=True, slo_thresholds={"predict_p99_ms": 1e-6})
    srv = obs_server.start_server(0)
    assert srv is not None
    try:
        latencies = []
        for _ in range(40):              # warm serving loop
            t0 = time.monotonic()
            bst.predict(X[:256])
            latencies.append(time.monotonic() - t0)

        code, text = _get(srv.url + "/metrics")
        assert code == 200
        p99_ms = _prom_value(text, "slo_predict_p99_ms")
        assert p99_ms is not None
        offline_ms = float(np.percentile(latencies, 99)) * 1000.0
        bounds_ms = [b * 1000.0
                     for b in obs_slo.tracker()
                     .hists["predict/call"].bounds]
        tol = max(_bucket_width_at(bounds_ms, offline_ms),
                  _bucket_width_at(bounds_ms, p99_ms))
        assert p99_ms == pytest.approx(offline_ms, abs=tol)
        # the scrape WAS an evaluation period: the forced breach is up
        assert _prom_value(
            text, 'slo_breached{slo="predict_p99_ms"}') == 1.0
        assert _prom_value(
            text, 'slo_breaches{slo="predict_p99_ms"}') >= 1.0
        # heartbeat.serve was stamped by the predict path
        assert _get(srv.url + "/readyz")[0] == 200
    finally:
        obs_server.stop_server()
