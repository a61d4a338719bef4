"""Request-lifecycle tracing through the serving stack
(docs/observability.md "Request tracing"; serve/service.py +
serve/queue.py + obs/tracing.py + obs/slo.py).

What these tests pin:

* **Span tree** — one dispatched batch records ONE ``serve/batch``
  parent whose children decompose it (queue_wait / coalesce /
  registry_checkout / dispatch / postprocess), riders attach as flow
  events (submit point -> carrying batch), and the checkout span says
  hit vs re-admission.
* **Flush causes** — the queue classifies WHY each batch left
  (fill / freeze / deadline) onto the popped requests, and the
  dispatch counts ``serve.flush_cause{cause=...}``.
* **Live decomposition** — the same stage durations feed the SLO
  windows: ``slo.queue_wait_p50|p99_ms``, ``slo.dispatch_p99_ms``
  and ``slo.device_share`` derive on evaluate().
* **Bounded buffer under load** — sustained traced serving overflows
  the (shrunken) buffer: the dropped-event gauge increments,
  oldest-dropped semantics hold (the newest requests' events remain),
  and a drained buffer's next export is well-formed.
"""
import json

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import slo as _slo
from lightgbm_tpu.obs import tracing as obs_tracing

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.disable()
    obs.reset()
    obs.set_trace_rank(None)
    yield
    obs.disable()
    obs.reset()
    obs.set_trace_rank(None)


def _data(n=2000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 8, "verbosity": -1}


@pytest.fixture(scope="module")
def trained():
    X, y = _data()
    bst = lgb.train(PARAMS, lgb.Dataset(X, label=y), num_boost_round=4)
    return bst, X


def _service(start=True, **over):
    from lightgbm_tpu.serve import PredictService
    p = {"tpu_serve_batch_budget_ms": 200.0,
         "tpu_serve_max_batch_rows": 1024,
         "tpu_serve_shard_trees": "false"}
    p.update(over)
    return PredictService(p, start=start)


# ---------------------------------------------------------------------------
# the per-batch span tree + rider flows
# ---------------------------------------------------------------------------
def test_batch_span_tree_and_rider_flows(trained, tmp_path):
    bst, X = trained
    obs.enable(metrics=True, trace_dir=str(tmp_path))
    svc = _service()
    try:
        svc.add_model("m", bst)
        futs = [svc.submit("m", X[:96]) for _ in range(3)]
        for f in futs:
            f.result(timeout=20)
    finally:
        svc.close()
    evs = obs_tracing.events()
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)

    # one coalesced dispatch: one batch span, the stage children under
    # it, one queue-wait event per rider
    batches = by_name["serve/batch"]
    assert len(batches) == 1
    b = batches[0]
    assert b["args"]["riders"] == 3 and b["args"]["rows"] == 288
    assert b["args"]["cause"] in ("fill", "freeze", "deadline")
    for stage in ("serve/coalesce", "serve/registry_checkout",
                  "serve/dispatch", "serve/postprocess"):
        (ev,) = by_name[stage]
        assert ev["args"]["parent"] == "serve/batch"
        # containment: children render inside the batch slice
        assert ev["ts"] >= b["ts"] - 1.0
        assert ev["ts"] + ev["dur"] <= b["ts"] + b["dur"] + 1.0
    assert by_name["serve/coalesce"][0]["args"]["cause"] == \
        b["args"]["cause"]
    assert "fill" in by_name["serve/coalesce"][0]["args"]
    # first touch of the model: a re-admission re-stack, not a hit
    assert by_name["serve/registry_checkout"][0]["args"]["hit"] is False

    waits = by_name["serve/queue_wait"]
    assert len(waits) == 3
    qtid = obs_tracing.track_tid("serve queue")
    for wv in waits:
        assert wv["args"]["parent"] == "serve/batch"
        assert wv["tid"] == qtid          # the virtual queue row
        # retroactive: the wait STARTS at enqueue, before the batch
        assert wv["ts"] <= b["ts"] + 1.0

    # flow events: one start per submit (caller thread), one finish
    # per rider inside the batch, matched on the request id
    starts = {e["id"] for e in by_name["serve/req"]
              if e["ph"] == "s"}
    finishes = {e["id"] for e in by_name["serve/req"]
                if e["ph"] == "f"}
    assert len(starts) == 3 and starts == finishes
    assert {w["args"]["req"] for w in waits} == starts


def test_checkout_hit_attr_tracks_residency(trained, tmp_path):
    """hit=False on first admission and after an eviction, hit=True on
    the warm path — the trace attr that separates an LRU-thrash p99
    breach from a device-time one."""
    bst, X = trained
    obs.enable(metrics=True, trace_dir=str(tmp_path))
    svc = _service(tpu_serve_batch_budget_ms=1.0)
    try:
        svc.add_model("m", bst)
        svc.predict("m", X[:16], timeout=20)
        svc.predict("m", X[:16], timeout=20)
        svc.registry.evict("m")
        svc.predict("m", X[:16], timeout=20)
    finally:
        svc.close()
    hits = [e["args"]["hit"] for e in obs_tracing.events()
            if e["name"] == "serve/registry_checkout"]
    assert hits == [False, True, False]


# ---------------------------------------------------------------------------
# flush-cause taxonomy
# ---------------------------------------------------------------------------
def test_queue_stamps_flush_causes():
    """Pure queue (no engine): each pop carries WHY it flushed."""
    from lightgbm_tpu.serve.queue import MicroBatchQueue
    q = MicroBatchQueue(budget_s=30.0, max_batch_rows=256)
    q.submit("m", np.zeros((128, 2)))
    q.submit("m", np.zeros((128, 2)))     # prefix reaches the cap
    _, b = q.next_batch()
    assert [r.flush_cause for r in b] == ["fill", "fill"]

    q.submit("m", np.zeros((100, 2)))
    q.submit("m", np.zeros((2000, 2)))    # freezes the prefix at 100
    _, b = q.next_batch()
    assert [r.flush_cause for r in b] == ["freeze"]
    _, b = q.next_batch()                 # the oversize: its own full
    assert [r.flush_cause for r in b] == ["fill"]

    q2 = MicroBatchQueue(budget_s=0.01, max_batch_rows=256)
    q2.submit("m", np.zeros((8, 2)))      # lone request: budget flush
    _, b = q2.next_batch()
    assert [r.flush_cause for r in b] == ["deadline"]


def test_shattered_batch_records_queue_wait_once(trained):
    """A malformed rider shatters its batch into per-rider
    re-dispatches — admission must NOT re-record: one queue-wait
    observation per rider, or the slo.queue_wait_* windows double-feed
    exactly when batches go bad."""
    bst, X = trained
    obs.enable(metrics=True)
    svc = _service(tpu_serve_batch_budget_ms=200.0)
    try:
        svc.add_model("m", bst)
        good = svc.submit("m", X[:16])
        bad = svc.submit("m", X[:8, :4])      # wrong column count
        good.result(timeout=20)
        with pytest.raises(Exception):
            bad.result(timeout=20)
    finally:
        svc.close()
    assert obs.registry().get("serve/queue_wait").count == 2


def test_flush_cause_counters_recorded(trained):
    bst, X = trained
    obs.enable(metrics=True)
    svc = _service(tpu_serve_batch_budget_ms=5.0)
    try:
        svc.add_model("m", bst)
        for _ in range(3):
            svc.predict("m", X[:16], timeout=20)
    finally:
        svc.close()
    reg = obs.registry()
    total = sum((reg.get("serve.flush_cause", cause=c).value
                 if reg.get("serve.flush_cause", cause=c) else 0.0)
                for c in ("fill", "freeze", "deadline", "close"))
    assert total >= 3.0


# ---------------------------------------------------------------------------
# the live decomposition gauges
# ---------------------------------------------------------------------------
def test_slo_decomposition_gauges_derive_from_serve_traffic(trained):
    bst, X = trained
    obs.enable(metrics=True, slo=True)
    svc = _service(tpu_serve_batch_budget_ms=2.0)
    try:
        svc.add_model("m", bst)
        for _ in range(5):
            svc.predict("m", X[:32], timeout=20)
    finally:
        svc.close()
    slis = _slo.tracker().evaluate()
    reg = obs.registry()
    for name in ("slo.queue_wait_p50_ms", "slo.queue_wait_p99_ms",
                 "slo.dispatch_p99_ms", "slo.device_share"):
        assert slis[name] is not None, name
        assert reg.get(name) is not None, name
    assert slis["slo.queue_wait_p99_ms"] >= \
        slis["slo.queue_wait_p50_ms"]
    assert 0.0 < slis["slo.device_share"] <= 1.0


def test_sliding_histogram_windowed_total():
    """The exact windowed sum the device-share ratio is built on."""
    from lightgbm_tpu.obs.slo import SlidingHistogram
    h = SlidingHistogram(window_s=100.0, slots=10)
    h.observe(1.5, now=1000.0)
    h.observe(2.5, now=1050.0)
    assert h.total(now=1060.0) == pytest.approx(4.0)
    # the early slot ages out of a narrower window
    assert h.total(window_s=20.0, now=1060.0) == pytest.approx(2.5)
    # ... and of the full window once the clock advances past it
    assert h.total(now=1101.0) == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# bounded buffer under sustained serving load (ISSUE satellite)
# ---------------------------------------------------------------------------
def test_bounded_buffer_under_serving_load(trained, tmp_path,
                                           monkeypatch):
    bst, X = trained
    monkeypatch.setattr(obs_tracing, "MAX_EVENTS", 60)
    obs.enable(metrics=True, trace_dir=str(tmp_path))
    svc = _service(tpu_serve_batch_budget_ms=0.5)
    try:
        svc.add_model("m", bst)
        for _ in range(40):               # ~9 events per request
            svc.predict("m", X[:16], timeout=20)

        assert obs_tracing.dropped_events() > 0
        # the dropped count is a LIVE gauge on the snapshot/scrape path
        snap = obs.snapshot()
        (g,) = [m for m in snap["metrics"]
                if m["name"] == "trace.dropped_events"]
        assert g["value"] == obs_tracing.dropped_events() > 0

        # oldest-dropped: the surviving queue-wait events belong to
        # the NEWEST requests (early request ids were evicted)
        req_ids = [e["args"]["req"] for e in obs_tracing.events()
                   if e["name"] == "serve/queue_wait"]
        assert req_ids == sorted(req_ids)
        assert min(req_ids) > 1
        assert len(obs_tracing.events()) <= 60

        # a drained buffer's next export is well-formed
        obs_tracing.reset_events()
        assert obs_tracing.dropped_events() == 0
        svc.predict("m", X[:16], timeout=20)
    finally:
        svc.close()
    out = obs.export_chrome_trace()
    doc = json.load(open(out))
    assert doc["otherData"]["dropped_events"] == 0
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "serve/batch" in names and "serve/dispatch" in names


STAGES = ("serve/queue_wait", "serve/coalesce", "serve/registry_checkout",
          "serve/dispatch", "serve/postprocess")


def test_tracing_flipped_on_warm_compiles_nothing_and_stages_add_up(
        trained, tmp_path):
    """Request tracing flipped ON over a warm service adds no XLA
    program, and no stage of a request's life is unaccounted for: over
    a window of one-rider batches the stages' p50s sum to within 10% of
    the p50 from enqueue (the queue wait's start) to resolve (the batch
    span's end)."""
    from lightgbm_tpu.utils.debug import CompileWatch
    bst, X = trained
    obs.enable(metrics=True)
    svc = _service(tpu_serve_batch_budget_ms=2.0)
    try:
        svc.add_model("m", bst)
        svc.warmup("m", X[:1])
        Xq = X[:64]
        svc.predict("m", Xq, timeout=20)
        with CompileWatch("trace-on-warm") as w:
            svc.predict("m", Xq, timeout=20)
            obs.enable(metrics=False, trace_dir=str(tmp_path))
            for _ in range(60):
                svc.predict("m", Xq, timeout=20)
        w.assert_compiles(0)
    finally:
        svc.close()
    # a group closes at its serve/batch event (the batch span exits
    # last) and counts when it holds every stage once, for the batch's
    # own request
    groups, cur = [], {}
    for e in obs_tracing.events():
        if e["name"] in STAGES:
            cur[e["name"]] = e
        elif e["name"] == "serve/batch":
            qw = cur.get("serve/queue_wait")
            if len(cur) == len(STAGES) and \
                    qw["args"].get("req") == e["args"].get("req"):
                groups.append((cur, e))
            cur = {}
    assert len(groups) >= 50
    e2e = np.median([b["ts"] + b["dur"] - g["serve/queue_wait"]["ts"]
                     for g, b in groups])
    staged = np.median([sum(g[s]["dur"] for s in STAGES)
                        for g, _b in groups])
    assert abs(staged - e2e) <= 0.10 * e2e, (staged, e2e)


def test_tracing_off_leaves_no_serve_events(trained):
    """Off-by-default: metrics-only serving records histograms but no
    trace events and no flow points (the zero-cost-off bar)."""
    bst, X = trained
    obs.enable(metrics=True)
    svc = _service(tpu_serve_batch_budget_ms=1.0)
    try:
        svc.add_model("m", bst)
        svc.predict("m", X[:16], timeout=20)
    finally:
        svc.close()
    assert obs_tracing.events() == []
    assert obs.registry().get("serve/batch") is not None
