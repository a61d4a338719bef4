"""Serving fleet: N ``PredictService`` replica processes under one
supervisor (docs/serving.md "Fleet deployment").

One serving process (serve/service.py) survives hot-swaps and slow
tenants but not its own death — "millions of users" (ROADMAP item 4)
needs replication. The fleet layer composes machinery that already
exists instead of inventing new protocols:

- **Replica** = one spawned process running the full single-process
  stack: micro-batch queue + LRU registry + (optionally tree-sharded)
  predict, a REQUIRED metrics endpoint on an ephemeral port
  (``obs.server.start_server(0, required=True)`` — a replica whose
  /metrics cannot bind is invisible to the router and refuses to
  start), a tiny HTTP predict endpoint the router calls, and a
  per-rank heartbeat stamp file (the gang launcher's watchdog file
  protocol, ``heartbeat.serve.rank<r>``).
- **Readiness is warmup** (the PR 15 contract): a joining replica
  warms every pow2 bucket through its real dispatch queue before
  ``heartbeat.serve`` is stamped, so its ``/readyz`` stays 503 — and
  the router admits zero traffic — until the steady state is
  compiled.
- **Liveness has two watchers**: the supervisor kills-and-relaunches
  a replica whose heartbeat FILE goes stale (wedged dispatch: the
  replica's idle loop stamps only while ``queue.depth()==0 and
  service.inflight==0``, so a predict stuck on-device stops the
  stamps) or whose process exits; the router independently stops
  routing at a replica whose ``/readyz`` goes 503 and re-dispatches
  its un-acked in-flight work to siblings (predict is pure — a
  re-sent request is idempotent).
- **Elastic membership** reuses degrade-and-continue (PR 18): a
  ``.host_gone.rank<r>`` marker (chaos harness or operator
  touch-file) or an exhausted per-replica restart budget retires the
  slot permanently — the fleet degrades to N−1 and keeps serving —
  while ordinary deaths relaunch into the SAME rank with a fresh
  generation.
- **Model convergence needs no coordination**: every replica watches
  the one checkpoint dir through its own ``ModelWatcher`` (atomic
  forward-only publishes + per-watcher poll jitter), so publishes
  reach all replicas without a control plane.

Fleet metrics (forced — rare events must be visible with metrics
off; docs/observability.md): ``fleet.replicas_live``,
``fleet.degrades``, ``fleet.relaunches`` in this module;
``fleet.router_retries``, ``fleet.redispatches`` in serve/router.py.

The wire protocol is deliberately minimal (stdlib http + npy bodies,
localhost only — same safety posture as obs/server.py): the router
POSTs ``/predict?model=<id>`` with an ``np.save`` body and gets an
``np.save`` body back. 404 = unknown model (a REQUEST error: the
router fails the future, no retry); 503 = closed/overloaded and any
connection error = a REPLICA error (the router retries a sibling).
"""
from __future__ import annotations

import io
import json
import multiprocessing as mp
import os
import signal
import tempfile
import threading
import time
import urllib.parse
import urllib.request
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

import numpy as np

from .. import obs
from ..recovery.faults import (clear_host_gone_markers, host_gone_ranks,
                               write_host_gone_marker)
from ..utils import log

__all__ = ["FleetSupervisor", "ReplicaModel", "ReplicaHandle"]

_HB_PREFIX = "heartbeat.serve.rank"
_ENDPOINT_TMPL = "replica_{rank}.json"


@dataclass
class ReplicaModel:
    """One tenant every replica serves: the model text (pickles across
    the spawn boundary), a sample row for bucketed warmup, and an
    optional checkpoint dir the replica's watcher hot-swaps from."""

    model_id: str
    model_str: str
    warmup_row: Optional[np.ndarray] = None
    watch_dir: Optional[str] = None
    watch_interval: float = 2.0


@dataclass
class ReplicaHandle:
    """Supervisor-side view of one replica slot."""

    rank: int
    proc: Optional[mp.process.BaseProcess] = None
    generation: int = 0
    restarts: int = 0
    predict_url: Optional[str] = None
    metrics_url: Optional[str] = None
    ready: bool = False
    retired: bool = False          # degraded away — never relaunched
    started_at: float = 0.0
    inflight: int = 0              # router-side in-flight counter
    depth: float = 0.0             # last scraped slo.queue_depth

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.is_alive()


# ----------------------------------------------------------------------
# replica process side
# ----------------------------------------------------------------------

def _scrub_replica_obs_params(params: Dict) -> Dict:
    """The driver's obs knobs must not replay in a replica: a fixed
    tpu_metrics_port would collide across N processes (the replica
    binds its own REQUIRED ephemeral endpoint), and file-writing knobs
    (dump/rank-dir/trace) would have N processes clobber one path."""
    p = dict(params or {})
    for k in ("tpu_metrics_port", "tpu_metrics_dump",
              "tpu_metrics_rank_dir", "tpu_trace_dir",
              "tpu_model_watch"):
        p.pop(k, None)
    return p


class _PredictHandler(BaseHTTPRequestHandler):
    server_version = "lightgbm-tpu-replica"
    protocol_version = "HTTP/1.1"

    def log_message(self, *args) -> None:       # router calls spam logs
        pass

    def _send(self, code: int, body: bytes,
              ctype: str = "application/octet-stream") -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_err(self, code: int, msg: str) -> None:
        self._send(code, json.dumps({"error": msg}).encode(),
                   "application/json")

    def do_POST(self) -> None:          # noqa: N802 (stdlib API name)
        path, _, query = self.path.partition("?")
        if path != "/predict":
            self._send_err(404, "not found")
            return
        model_id = None
        for part in query.split("&"):
            if part.startswith("model="):
                model_id = urllib.parse.unquote(part[len("model="):])
        try:
            n = int(self.headers.get("Content-Length", "0"))
            X = np.load(io.BytesIO(self.rfile.read(n)),
                        allow_pickle=False)
        except Exception as e:
            self._send_err(400, f"bad payload: {e}")
            return
        svc = self.server.service
        try:
            out = svc.predict(model_id or "", X,
                              timeout=self.server.predict_timeout_s)
        except KeyError as e:
            self._send_err(404, f"unknown model: {e}")
            return
        except RuntimeError as e:
            # closed queue / shutdown — retriable at a sibling
            self._send_err(503, str(e))
            return
        except Exception as e:
            self._send_err(500, f"{type(e).__name__}: {e}")
            return
        buf = io.BytesIO()
        np.save(buf, np.asarray(out), allow_pickle=False)
        try:
            self._send(200, buf.getvalue())
        except BrokenPipeError:
            pass        # router gave up / died mid-reply; work is pure


class _PredictServer(ThreadingHTTPServer):
    daemon_threads = True
    service = None
    predict_timeout_s = 30.0


def _replica_main(rank: int, fleet_dir: str, params: Dict,
                  models: List[ReplicaModel], heartbeat_timeout: float,
                  platform: Optional[str], warmup_delay_s: float,
                  predict_timeout_s: float) -> None:
    """Entry point of one spawned replica process: build the full
    single-process serving stack, prove readiness by warmup, publish
    the endpoint file, then idle-stamp liveness until killed."""
    from ..parallel.launch import strip_fake_device_flags
    strip_fake_device_flags()
    if platform:
        # through jax.config, so the choice holds whatever
        # JAX_PLATFORMS the child inherited — N replicas must not
        # fight over one chip (a chip belongs to one process)
        import jax
        jax.config.update("jax_platforms", platform)
    import lightgbm_tpu as lgb
    from ..obs.server import start_server
    from .service import PredictService

    obs.enable(metrics=True, slo=True)
    # REQUIRED endpoint on an ephemeral port: a replica the router
    # cannot scrape must fail its launch, not serve blind
    srv = start_server(0, heartbeat_timeout_s=heartbeat_timeout,
                       required=True)
    # heartbeat FILE before the first stamp: warmup's heartbeat("serve")
    # doubles as the supervisor watchdog's first proof of life
    obs.set_heartbeat_file(
        "serve", os.path.join(fleet_dir, f"{_HB_PREFIX}{rank}"))

    svc = PredictService(_scrub_replica_obs_params(params))
    for spec in models:
        bst = lgb.Booster(model_str=spec.model_str)
        svc.add_model(spec.model_id, bst, watch_dir=spec.watch_dir,
                      watch_interval=spec.watch_interval)

    httpd = _PredictServer(("127.0.0.1", 0), _PredictHandler)
    httpd.service = svc
    httpd.predict_timeout_s = float(predict_timeout_s)
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="lightgbm-tpu-replica-predict").start()

    # publish WHERE to find this replica before it is ready — the
    # supervisor/router poll /readyz (503 until warmup stamps the
    # heartbeat) to decide WHEN to admit traffic. Atomic rename: a
    # half-written endpoint file must never parse
    ep = {"rank": rank, "pid": os.getpid(),
          "predict_url": f"http://127.0.0.1:"
                         f"{httpd.server_address[1]}",
          "metrics_url": srv.url}
    path = os.path.join(fleet_dir, _ENDPOINT_TMPL.format(rank=rank))
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(ep, f)
    os.replace(tmp, path)

    if warmup_delay_s > 0:      # chaos/test hook: a slow joiner
        time.sleep(warmup_delay_s)
    for spec in models:
        row = spec.warmup_row
        if row is None:
            continue
        svc.warmup(spec.model_id, np.asarray(row, np.float64)
                   .reshape(1, -1))

    # liveness loop: stamp while TRULY idle (empty queue AND nothing
    # mid-dispatch). Under load _record() stamps per dispatched batch;
    # a wedged predict leaves inflight>0 with no _record stamps — the
    # file goes stale and the supervisor replaces this process
    try:
        while True:
            t = svc._thread
            if t is None or not t.is_alive():
                break               # dispatcher died: stop stamping
            if svc.queue.depth() == 0 and svc.inflight == 0:
                obs.heartbeat("serve")
            time.sleep(0.25)
    except KeyboardInterrupt:
        pass
    svc.close()


# ----------------------------------------------------------------------
# supervisor side
# ----------------------------------------------------------------------

class FleetSupervisor:
    """Spawns, watches, relaunches, and degrades N serving replicas.

    The monitor thread owns membership: process exits and stale
    heartbeat files turn into relaunches (same rank, next generation)
    until the slot's ``max_restarts`` budget runs out or a host-gone
    marker names it — then the slot retires and the fleet serves at
    N−1 (degrade-and-continue, PR 18 semantics). ``/readyz`` scraped
    per replica gates ``ReplicaHandle.ready``; the router
    (serve/router.py) only dispatches at ready handles and gets
    queue-depth hints from the same scrape loop.
    """

    def __init__(self, params: Optional[Dict],
                 models: List[ReplicaModel], n_replicas: int, *,
                 fleet_dir: Optional[str] = None,
                 max_restarts: int = 2,
                 heartbeat_timeout: float = 10.0,
                 platform: Optional[str] = "cpu",
                 warmup_delay_s: float = 0.0,
                 slow_warmup_ranks: tuple = (),
                 predict_timeout_s: float = 30.0,
                 poll_s: float = 0.1):
        if n_replicas < 1:
            raise ValueError("fleet: n_replicas must be >= 1")
        self.params = dict(params or {})
        self.models = list(models)
        self.n_replicas = int(n_replicas)
        self.fleet_dir = fleet_dir or tempfile.mkdtemp(
            prefix="lgbm_tpu_fleet_")
        os.makedirs(self.fleet_dir, exist_ok=True)
        self.max_restarts = int(max_restarts)
        self.heartbeat_timeout = max(float(heartbeat_timeout), 1.0)
        self.platform = platform
        self.warmup_delay_s = float(warmup_delay_s)
        self.slow_warmup_ranks = tuple(slow_warmup_ranks)
        self.predict_timeout_s = float(predict_timeout_s)
        self.poll_s = float(poll_s)
        self.handles: List[ReplicaHandle] = [
            ReplicaHandle(rank=r) for r in range(self.n_replicas)]
        self.degrades = 0
        self.relaunches = 0
        self._ctx = mp.get_context("spawn")
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def start(self) -> "FleetSupervisor":
        # fresh-run hygiene, exactly like the gang launcher: stale
        # heartbeat files read as instantly-hung replicas, stale
        # host-gone markers re-apply yesterday's loss
        self._clear_files()
        clear_host_gone_markers(self.fleet_dir)
        for h in self.handles:
            self._launch(h)
        self._stop.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True,
            name="lightgbm-tpu-fleet-monitor")
        self._monitor.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5.0)
        for h in self.handles:
            self._terminate(h)

    def __enter__(self) -> "FleetSupervisor":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------
    def ready_handles(self) -> List[ReplicaHandle]:
        """Snapshot of handles the router may dispatch at."""
        with self._lock:
            return [h for h in self.handles
                    if h.ready and not h.retired and h.alive]

    def live_count(self) -> int:
        return len(self.ready_handles())

    def wait_ready(self, n: Optional[int] = None,
                   timeout: float = 120.0) -> int:
        """Block until ``n`` replicas (default: every non-retired
        slot) pass /readyz; returns the ready count."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                want = n if n is not None else sum(
                    1 for h in self.handles if not h.retired)
            got = self.live_count()
            if got >= want:
                return got
            time.sleep(0.05)
        return self.live_count()

    # ------------------------------------------------------------------
    def kill_replica(self, rank: int, host_gone: bool = False) -> None:
        """Chaos/test helper: SIGKILL one replica mid-traffic. With
        ``host_gone`` the marker is written FIRST, so the monitor
        degrades instead of relaunching — the 'machine vanished'
        shape, not the 'process crashed' shape."""
        h = self.handles[rank]
        if host_gone:
            write_host_gone_marker(self.fleet_dir, rank,
                                   note="fleet kill_replica")
        if h.proc is not None and h.proc.pid and h.alive:
            try:
                os.kill(h.proc.pid, signal.SIGKILL)
            except OSError:
                pass

    # ------------------------------------------------------------------
    def _clear_files(self) -> None:
        try:
            names = os.listdir(self.fleet_dir)
        except OSError:
            return
        for name in names:
            if name.startswith(_HB_PREFIX) \
                    or name.startswith("replica_"):
                try:
                    os.unlink(os.path.join(self.fleet_dir, name))
                except OSError:
                    pass

    def _launch(self, h: ReplicaHandle) -> None:
        """(Re)spawn one slot; the handle's endpoint/readiness reset
        until the new process republishes and re-warms."""
        h.ready = False
        h.predict_url = None
        h.metrics_url = None
        h.depth = 0.0
        # a relaunch must not read the DEAD generation's last stamp as
        # fresh, nor its endpoint file as live
        for name in (f"{_HB_PREFIX}{h.rank}",
                     _ENDPOINT_TMPL.format(rank=h.rank)):
            try:
                os.unlink(os.path.join(self.fleet_dir, name))
            except OSError:
                pass
        delay = self.warmup_delay_s \
            if (not self.slow_warmup_ranks
                or h.rank in self.slow_warmup_ranks) else 0.0
        h.proc = self._ctx.Process(
            target=_replica_main,
            args=(h.rank, self.fleet_dir, self.params, self.models,
                  self.heartbeat_timeout, self.platform, delay,
                  self.predict_timeout_s),
            daemon=True, name=f"lgbm-tpu-replica-{h.rank}")
        h.proc.start()
        h.generation += 1
        h.started_at = time.monotonic()

    def _terminate(self, h: ReplicaHandle) -> None:
        if h.proc is None:
            return
        try:
            if h.alive:
                h.proc.terminate()
                h.proc.join(timeout=3.0)
            if h.alive:
                h.proc.kill()
                h.proc.join(timeout=3.0)
        except Exception:
            pass

    # ------------------------------------------------------------------
    def _monitor_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self._tick()
            except Exception as e:      # the fleet outlives its nurse
                log.warning(f"fleet: monitor tick failed ({e})")
            self._stop.wait(self.poll_s)

    def _tick(self) -> None:
        gone = set(host_gone_ranks(self.fleet_dir))
        for h in self.handles:
            if h.retired:
                continue
            if h.rank in gone:
                self._retire(h, f"host-gone marker for rank {h.rank}")
                clear_host_gone_markers(self.fleet_dir,
                                        ranks=[h.rank])
                continue
            if not h.alive:
                self._replace(h, f"exit code {h.proc.exitcode}"
                              if h.proc is not None else "never spawned")
                continue
            age = self._heartbeat_age(h)
            if age is not None and age > self.heartbeat_timeout:
                log.warning(f"fleet: replica {h.rank} heartbeat stale "
                            f"({age:.1f}s > {self.heartbeat_timeout}s)"
                            f"; killing for relaunch")
                self.kill_replica(h.rank)
                self._replace(h, f"stale heartbeat ({age:.1f}s)")
                continue
            self._scrape(h)
        obs.set_gauge("fleet.replicas_live", float(self.live_count()),
                      force=True)

    def _heartbeat_age(self, h: ReplicaHandle) -> Optional[float]:
        """Age of the slot's stamp file; None before the first stamp
        (starting up / warming — that is readiness's job, not a
        hang)."""
        try:
            st = os.stat(os.path.join(self.fleet_dir,
                                      f"{_HB_PREFIX}{h.rank}"))
        except OSError:
            return None
        return time.time() - st.st_mtime

    def _replace(self, h: ReplicaHandle, why: str) -> None:
        with self._lock:
            h.ready = False
        self._terminate(h)
        if h.restarts >= self.max_restarts:
            self._retire(h, f"restart budget exhausted "
                         f"({self.max_restarts}) after: {why}")
            return
        h.restarts += 1
        self.relaunches += 1
        obs.inc("fleet.relaunches", force=True)
        log.warning(f"fleet: replica {h.rank} down ({why}); "
                    f"relaunching (restart {h.restarts}/"
                    f"{self.max_restarts}, generation "
                    f"{h.generation + 1})")
        self._launch(h)

    def _retire(self, h: ReplicaHandle, why: str) -> None:
        with self._lock:
            h.ready = False
            h.retired = True
        self._terminate(h)
        self.degrades += 1
        obs.inc("fleet.degrades", force=True)
        width = sum(1 for x in self.handles if not x.retired)
        log.warning(f"fleet: replica {h.rank} RETIRED ({why}); "
                    f"degrading to {width} replica(s) — queued work "
                    f"drains to siblings")

    # ------------------------------------------------------------------
    def _scrape(self, h: ReplicaHandle) -> None:
        """One monitor-loop scrape: endpoint discovery, /readyz
        admission, and the router's queue-depth hint."""
        if h.predict_url is None:
            path = os.path.join(self.fleet_dir,
                                _ENDPOINT_TMPL.format(rank=h.rank))
            try:
                with open(path) as f:
                    ep = json.load(f)
            except (OSError, ValueError):
                return      # not published yet
            # a stale file from the PREVIOUS generation is unlinked in
            # _launch, so whatever parses here is this generation's
            h.predict_url = ep["predict_url"]
            h.metrics_url = ep["metrics_url"]
        ready = False
        depth = h.depth
        try:
            with urllib.request.urlopen(
                    h.metrics_url + "/readyz", timeout=2.0) as r:
                ready = (r.status == 200)
            with urllib.request.urlopen(
                    h.metrics_url + "/metrics.json", timeout=2.0) as r:
                snap = json.load(r)
            for m in snap.get("metrics", []):
                if m.get("name") == "slo.queue_depth":
                    depth = float(m.get("value", 0.0))
        except Exception:
            # scrape failures degrade to "not ready" — the process
            # watchdogs (exit / stale heartbeat) decide its fate
            ready = False
        if ready and not h.ready:
            log.info(f"fleet: replica {h.rank} (generation "
                     f"{h.generation}) is ready — router admitted")
        with self._lock:
            h.ready = ready
            h.depth = depth
