"""PredictService: the async serving front of the engine.

One process, many tenants, one dispatch loop:

- callers ``submit(model_id, X)`` from any thread and get a Future;
- the micro-batch queue (serve/queue.py) coalesces concurrent
  requests per model under the latency budget;
- the dispatch thread checks the model out of the LRU registry
  (serve/registry.py), takes the model's hot-swap lock
  (serving.ModelWatcher.swap_lock) and runs ONE bucketed
  ``Booster.predict`` for the whole batch — steady-state traffic
  compiles zero programs (PR 7's pow2 row buckets), and a mid-batch
  hot-swap or LRU eviction can reorder work but never drop a request:
  every Future resolves with rows or an exception.

Observability contract (docs/serving.md): the queue feeds the REAL
``slo.queue_depth`` gauge through obs/slo.py's registered provider,
the dispatch loop stamps ``heartbeat.serve`` (so ``/readyz`` turns
green after :meth:`warmup` — the PR 13 readiness-by-warmup contract),
and every dispatch records ``serve.dispatches`` /
``serve.coalesced_requests`` / ``serve.batch_fill_ratio``.

Request-lifecycle tracing (docs/observability.md "Request tracing"):
each dispatched batch runs under ONE ``serve/batch`` span whose
children decompose it — per-rider ``serve/queue_wait`` (recorded
retroactively from the request's enqueue stamp), ``serve/coalesce``
(riders / rows / fill / flush cause), ``serve/registry_checkout``
(hit vs re-admission re-stack), ``serve/dispatch`` (the bucketed
predict), and ``serve/postprocess`` (slice + resolve). Riders attach
to their carrying batch as flow events, and the same stage durations
feed the PR 11 sliding windows so ``SloTracker.evaluate()`` derives
``slo.queue_wait_p50|p99_ms`` / ``slo.dispatch_p99_ms`` /
``slo.device_share`` and the ``serve.flush_cause{cause=...}``
counters — the p99 decomposition is live on ``/metrics``, not only
in trace files. All of it is off by default behind the existing obs
gates (one bool check per site when off).
"""
from __future__ import annotations

import threading
import time
import weakref
from concurrent.futures import Future
from typing import List, Optional

import numpy as np

from .. import obs
from ..config import Config
from ..obs import slo as _slo
from ..obs import tracing as _tracing
from ..utils import log
from .queue import MicroBatchQueue, PredictRequest
from .registry import ModelRegistry

__all__ = ["PredictService"]

# slo.queue_depth sources: every LIVE service's queue contributes to
# ONE module-level provider, so the gauge survives any construct/close
# interleaving (blue/green in either order) and reads the process's
# total backlog — the quantity a load balancer actually cares about.
# Weak references: a service abandoned without close() must not pin
# its queue (and every undispatched request payload) for the process
# lifetime, nor keep feeding a dead backlog into the gauge
_live_lock = threading.Lock()
_live_queues: "weakref.WeakSet" = weakref.WeakSet()


def _total_queue_depth() -> float:
    with _live_lock:     # vs a blue/green construct/close mid-scrape
        queues = list(_live_queues)
    return float(sum(q.depth() for q in queues))


def _track_queue(q: MicroBatchQueue) -> None:
    with _live_lock:
        _live_queues.add(q)
        _slo.set_queue_depth_provider(_total_queue_depth)


def _untrack_queue(q: MicroBatchQueue) -> None:
    with _live_lock:
        _live_queues.discard(q)
        if not _live_queues:
            _slo.clear_queue_depth_provider(_total_queue_depth)


def _resolve(req: PredictRequest, value=None, exc=None) -> None:
    """Settle one request's future, tolerating a client-side cancel: a
    caller that cancelled (e.g. after a result() timeout) made its own
    choice — settling its batchmates must not blow up on its
    InvalidStateError and poison THEIR correctly computed results."""
    fut = req.future
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)
    except Exception:
        if not fut.cancelled() and not fut.done():
            raise


class PredictService:
    """Async micro-batching predict service over a model registry."""

    def __init__(self, params=None,
                 registry: Optional[ModelRegistry] = None,
                 start: bool = True):
        cfg = params if isinstance(params, Config) \
            else Config(dict(params or {}))
        self.config = cfg
        # the service is a serving PROCESS entry point: honor the obs
        # knobs (tpu_metrics_port and friends) the same way train() does
        obs.configure_from_config(cfg)
        self.registry = registry if registry is not None \
            else ModelRegistry(cfg)
        self.queue = MicroBatchQueue(
            budget_s=float(cfg.tpu_serve_batch_budget_ms) / 1000.0,
            max_batch_rows=int(cfg.tpu_serve_max_batch_rows))
        _track_queue(self.queue)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # riders of the batch CURRENTLY mid-dispatch (0 when the loop
        # is between batches). The queue's depth() drops at pop, so
        # depth alone cannot tell "idle" from "wedged inside predict"
        # — the fleet replica's liveness loop (serve/fleet.py) stamps
        # heartbeat.serve only while depth()==0 AND inflight==0, so a
        # wedged dispatch goes /readyz-stale and gets replaced
        self._inflight = 0
        if start:
            self.start()

    # ------------------------------------------------------------------
    def start(self) -> "PredictService":
        if self.queue.closed:
            # close() is terminal for the queue: a restarted thread
            # would spin while every submit raises — refuse loudly
            # instead of returning a zombie service
            raise RuntimeError("serve: service is closed; build a new "
                               "PredictService")
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="lightgbm-tpu-serve-dispatch")
            self._thread.start()
        return self

    def close(self, timeout: float = 10.0) -> None:
        """Stop dispatching; queued-but-undispatched futures fail with
        RuntimeError (explicitly — never a silent drop)."""
        self._stop.set()
        leftover = self.queue.close()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        for req in leftover:
            if not req.future.done():
                _resolve(req, exc=RuntimeError(
                    "serve: service closed before dispatch"))
        _untrack_queue(self.queue)

    def __enter__(self) -> "PredictService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------------
    def add_model(self, model_id: str, booster,
                  watch_dir: Optional[str] = None,
                  watch_interval: float = 2.0) -> "PredictService":
        self.registry.register(model_id, booster, watch_dir=watch_dir,
                               watch_interval=watch_interval)
        return self

    @property
    def inflight(self) -> int:
        """Riders of the batch currently mid-dispatch (0 between
        batches) — with ``queue.depth()``, the replica idle/wedged
        discriminator."""
        return self._inflight

    def submit(self, model_id: str, X,
               kind: str = "predict") -> Future:
        """Enqueue one request; the Future resolves to exactly the rows
        submitted, or raises what the predict raised.

        ``kind="predict"`` resolves to converted model output;
        ``kind="contrib"`` resolves to per-feature SHAP contributions
        (``pred_contrib`` layout: ``[rows, n_feat + 1]`` per class).
        Explain riders ride the same micro-batch queue and flush rules
        but coalesce only with other explain requests for the same
        model — never into a predict batch."""
        if kind not in ("predict", "contrib"):
            raise ValueError(f"serve: unknown predict kind {kind!r} "
                             f"(expected 'predict' or 'contrib')")
        return self.queue.submit(model_id, X, kind=kind)

    def predict(self, model_id: str, X,
                timeout: Optional[float] = None) -> np.ndarray:
        """Synchronous convenience wrapper over :meth:`submit`."""
        return self.submit(model_id, X).result(timeout=timeout)

    def warmup(self, model_id: str, X,
               kinds=("predict",)) -> None:
        """Compile the steady state for one model: predict one batch at
        every pow2 row bucket up to the batch cap (tiling ``X``'s first
        row), through the registry like real traffic. After this
        returns, ``heartbeat.serve`` is stamped — the /readyz contract
        — and warm dispatches of any COALESCED size compile nothing.
        A single request LARGER than ``tpu_serve_max_batch_rows``
        dispatches alone and pads to a bigger pow2 bucket the warmup
        never visited — it pays a one-time compile per new bucket
        (bounded: log2(chunk/cap) programs); size the batch cap to
        your largest expected request to avoid that.

        ``kinds``: which predict kinds to warm — serve mixed
        predict+explain traffic with ``kinds=("predict", "contrib")``
        so warm SHAP dispatches also compile nothing."""
        X = np.asarray(X, dtype=np.float64)
        row = X[:1]
        if (self._thread is None or not self._thread.is_alive()
                or self.queue.closed):
            # no inline fallback: a predict on the caller's thread
            # would race the dispatch loop on the engine AND stamp
            # heartbeat.serve (the engine's predict instrumentation),
            # turning /readyz green for a service that drains nothing
            raise RuntimeError("serve: warmup needs a running service "
                               "— call start() first")
        # walk every pow2 bucket from the ENGINE's floor up to the
        # batch cap: steady-state dispatches of any coalesced size then
        # reuse a compiled program (tests/test_serve_queue.py holds
        # zero warm compiles across swap + eviction)
        from ..boosting.gbdt import PREDICT_ROW_BUCKET_FLOOR
        cap = self.queue.max_batch_rows
        for kind in kinds:
            bucket = PREDICT_ROW_BUCKET_FLOOR
            while True:
                # through the real dispatch path, one awaited bucket at
                # a time (awaiting keeps warmup batches from coalescing
                # WITH EACH OTHER into a skipped bucket): registry
                # checkout and the engine's stack/SHAP-cache mutations
                # stay on the dispatch thread, so a warmup — or a
                # tenant added mid-traffic — never races a live
                # dispatch on the same engine
                self.submit(model_id, np.repeat(row, bucket, axis=0),
                            kind=kind).result()
                if bucket >= cap:
                    break
                bucket = min(bucket * 2, cap)
        obs.heartbeat("serve")

    # ------------------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.is_set():
            item = self.queue.next_batch(poll_s=0.05)
            if item is None:
                continue
            model_id, batch = item
            self._inflight = len(batch)
            try:
                self._dispatch(model_id, batch)
            except Exception as e:   # belt-and-braces: the loop lives on
                for req in batch:
                    if not req.future.done():
                        _resolve(req, exc=e)
                log.warning(f"serve: dispatch for model "
                            f"{model_id!r} failed ({e})")
            finally:
                self._inflight = 0

    def _dispatch(self, model_id: str,
                  batch: List[PredictRequest],
                  admitted: bool = False) -> None:
        rows = sum(r.rows for r in batch)
        # the queue stamped WHY it flushed onto the popped requests;
        # warmup-era direct calls (tests) may carry none. The batch is
        # kind-homogeneous by the queue's (model, kind) lanes.
        cause = batch[0].flush_cause or "fill"
        kind = getattr(batch[0], "kind", "predict")
        with obs.span("serve/batch", model=model_id, riders=len(batch),
                      rows=rows, cause=cause, kind=kind,
                      req=batch[0].id) as bsp:
            if not admitted and obs.any_enabled():
                self._admission_records(batch)
            X = self._coalesce(batch, rows, cause)
            if X is None and bsp is not None:
                bsp.set(shattered=True)
            if X is not None:
                self._dispatch_batch(model_id, batch, X, rows, cause)
        if X is None:
            # one malformed rider (wrong column count, ragged
            # payload) must not poison its batchmates: dispatch
            # each request alone so only the offender's future
            # fails, with the engine's own error. admitted=True:
            # queue waits / flow ends were already recorded for the
            # shattered batch — re-recording would double-feed the
            # SLO windows and duplicate flow finishes
            for req in batch:
                self._dispatch(model_id, [req], admitted=True)

    def _admission_records(self, batch: List[PredictRequest]) -> None:
        """Per-rider admission instrumentation, under the open
        ``serve/batch`` span: the queue-wait stage (feeds the metrics
        histogram + the SLO sliding window) and, when tracing, a
        RETROACTIVE ``serve/queue_wait`` event spanning enqueue→now on
        the virtual "serve queue" track (its own Perfetto row — waits
        overlap the previous batch's spans on the dispatch thread)
        plus the flow end tying each rider's submit to this batch."""
        now = time.monotonic()
        tracing = _tracing.tracing_enabled()
        qtid = _tracing.track_tid("serve queue") if tracing else 0
        for req in batch:
            wait = max(now - req.t_enqueue, 0.0)
            obs.observe("serve/queue_wait", wait)
            if tracing:
                _tracing.record_event(
                    "serve/queue_wait", req.t_enqueue, wait,
                    {"parent": "serve/batch", "req": req.id,
                     "model": req.model_id, "rows": req.rows},
                    tid=qtid)
                _tracing.record_flow("serve/req", req.id, "f")

    def _coalesce(self, batch: List[PredictRequest], rows: int,
                  cause: str):
        """Concatenate the riders into one payload (None = a malformed
        rider; the caller shatters the batch). ``fill`` is estimated
        against the SERVICE config's bucket ladder — the dispatched
        booster (whose knobs decide the real padding) is not checked
        out yet; ``serve.batch_fill_ratio`` stays the exact number."""
        with obs.span("serve/coalesce", riders=len(batch), rows=rows,
                      cause=cause,
                      fill=round(rows / float(self._bucket_rows(rows)),
                                 4)):
            if len(batch) == 1:
                return batch[0].X
            try:
                return np.concatenate([np.asarray(r.X) for r in batch],
                                      axis=0)
            except Exception:
                return None

    def _dispatch_batch(self, model_id: str,
                        batch: List[PredictRequest], X, rows: int,
                        cause: str) -> None:
        try:
            # admission and predict under ONE continuous hold of the
            # model's registry lock (begin_dispatch) — register() /
            # evict() engine mutations from user threads serialize
            # against this in-flight predict, and an evict cannot
            # slip between admission and the predict that would
            # repopulate the stack it released. Booster.predict
            # itself additionally holds the watcher's swap_lock for
            # the whole model read (basic.py), so a concurrent
            # hot-swap lands before or after the WHOLE batch: every
            # rider sees one model.
            with obs.span("serve/registry_checkout",
                          model=model_id) as ck:
                booster, lock, hit = \
                    self.registry.begin_dispatch(model_id)
                if ck is not None:
                    ck.set(hit=hit)
        except KeyError as e:
            for req in batch:
                _resolve(req, exc=e)
            return
        kind = getattr(batch[0], "kind", "predict")
        try:
            with obs.span("serve/dispatch", rows=rows,
                          riders=len(batch), kind=kind):
                out = (booster.predict(X, pred_contrib=True)
                       if kind == "contrib" else booster.predict(X))
        except Exception as e:
            for req in batch:
                _resolve(req, exc=e)
            self._record(batch, rows, booster, cause)
            return
        finally:
            lock.release()
        with obs.span("serve/postprocess", riders=len(batch)):
            off = 0
            for req in batch:
                part = out[off:off + req.rows]
                # coalesced riders get COPIES: independent callers must
                # not hold aliasing views of one shared batch buffer (an
                # in-place tweak by one would corrupt its batchmates, and
                # a retained slice would pin the whole batch)
                _resolve(req, value=(part.copy() if len(batch) > 1
                                     else part))
                off += req.rows
        self._record(batch, rows, booster, cause)

    def _record(self, batch: List[PredictRequest], rows: int,
                booster=None, cause: str = "fill") -> None:
        obs.inc("serve.dispatches")
        explain = getattr(batch[0], "kind", "predict") == "contrib"
        if explain:
            obs.inc("serve.explain_requests", len(batch))
        if len(batch) > 1:
            obs.inc("serve.coalesced_requests", len(batch))
        obs.set_gauge("serve.batch_fill_ratio",
                      rows / float(self._bucket_rows(rows, booster)))
        if obs.enabled():
            # flush-cause taxonomy + per-rider end-to-end latency: the
            # decomposition the slo.* gauges derive from (one bool
            # gate for the per-request loop). Explain riders feed their
            # own window too, so slo.explain_p99_ms decomposes the
            # mixed workload without muddying the predict e2e signal.
            obs.inc("serve.flush_cause", cause=cause)
            now = time.monotonic()
            for req in batch:
                e2e = max(now - req.t_enqueue, 0.0)
                obs.observe("serve/e2e", e2e)
                if explain:
                    obs.observe("serve/explain", e2e)
        # liveness from the LOOP, not just the predict instrumentation:
        # /readyz must track "the dispatcher is draining work" even
        # with a model whose predicts error
        obs.heartbeat("serve")

    def _bucket_rows(self, rows: int, booster=None) -> int:
        """The pow2 bucket this dispatch padded to (PR 7's serving
        bucketing) — the fill-ratio denominator, from the engine's own
        shared pad policy. The DISPATCHED booster's config decides the
        real padding (a tenant may carry its own chunk/bucket knobs);
        the service config is only the host-model / unregistered
        fallback."""
        from ..boosting.gbdt import predict_pad_rows
        eng = getattr(booster, "_engine", None) if booster is not None \
            else None
        cfg = eng.config if eng is not None else self.config
        return predict_pad_rows(rows, cfg.tpu_predict_chunk_rows,
                                cfg.tpu_predict_buckets)
