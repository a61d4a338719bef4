"""Observability subsystem: metrics registry + phase-scoped tracing +
device/compile telemetry + active SLO/serving plane
(docs/observability.md).

Pillars, one import:

- **Metrics** (obs/metrics.py): process-wide counters / gauges /
  histograms with labels, exported as JSONL snapshots
  (``dump_jsonl``), Prometheus-style text (``prometheus_text``), or
  the ``Booster.metrics()`` / ``GBDT.metrics_snapshot()`` APIs.
- **Tracing** (obs/tracing.py): ``with obs.span("train/round",
  round=i):`` — nested spans that record wall time into a
  Chrome-trace JSON viewable in Perfetto; the serving dispatch loop
  adds per-batch span trees with rider flow events, and rank-tagged
  exports merge into one gang-wide timeline via
  ``scripts/trace_merge.py`` (obs/aggregate.py). Every span is ALSO a
  ``jax.profiler.TraceAnnotation("lgbm/<name>")``, and device code is
  wrapped in ``obs.scope(<name of LAYERS>)``: any profiler dump
  (``tpu_profile_dir``) then holds the program's host spans and its
  layers on the device's clock, and obs/trace_attr.py reduces it by
  layer. The profiler session is the switch for everything timed on
  the device.
- **Device telemetry** (obs/telemetry.py): compile-request counting,
  program-cache-size and HBM gauges refreshed into the registry.
- **Active plane** (obs/slo.py + obs/server.py + obs/aggregate.py):
  windowed SLIs (rolling p50/p99 under the same span names) with
  threshold evaluation, a live localhost ``/metrics`` + ``/healthz`` /
  ``/readyz`` endpoint driven by :func:`heartbeat` stamps, and
  per-rank snapshot aggregation for ``train_distributed`` gangs.

OFF BY DEFAULT and engineered for ~zero cost when off: every
instrumented hot path funnels through :func:`span` / :func:`inc` /
:func:`observe`, whose disabled path is one bool check (and, for a
span, a profiler annotation that is a no-op of under 1 us while no
profiler session is open) — no locks, no clocks. There is no span
site per row, per leaf or per tree. Enabled
via ``Config`` knobs (``tpu_metrics=true``, ``tpu_trace_dir=DIR``,
``tpu_metrics_dump=PATH``, ``tpu_metrics_port=N``, ``tpu_slo_*``) or
programmatically with :func:`enable`.

Cold paths that must record regardless (restart/retry accounting, the
benches, the utils/timer back-compat shim, and the work counters of
the grower and of ingest, once a chunk) pass ``force=True``.
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Optional

from . import metrics as _metrics
from . import slo as _slo
from . import tracing as _tracing
from .metrics import prometheus_from_snapshot, registry
from .tracing import (export_chrome_trace, set_trace_rank, span_stack,
                      trace_dir, trace_rank, tracing_enabled)

__all__ = [
    "enable", "disable", "enabled", "any_enabled", "tracing_enabled",
    "slo_enabled", "span", "scope", "LAYERS", "inc", "set_gauge", "observe", "counter",
    "gauge", "histogram", "heartbeat", "retire_heartbeat",
    "set_heartbeat_file",
    "predict_instrumented", "registry", "snapshot", "dump_jsonl",
    "prometheus_text", "prometheus_from_snapshot",
    "export_chrome_trace", "export_state", "import_state", "reset",
    "configure_from_config", "flush_from_config", "span_stack",
    "trace_dir", "set_trace_rank", "trace_rank",
]


class _State:
    __slots__ = ("metrics", "slo")

    def __init__(self) -> None:
        self.metrics = False
        self.slo = False


_state = _State()

# metric-name prefixes that never ride checkpoints: monotonic-clock
# heartbeat stamps and windowed SLO gauges (slo.* plus the windowed
# cache-hit ratio) describe THIS process's recent behavior — importing
# them into a resumed process would be stale at best and wrong-clock
# at worst (heartbeats must resume from live stamping, not from saved
# state; a resumed process whose tracker is off would otherwise expose
# the dead process's frozen ratios forever)
_EPHEMERAL_PREFIXES = ("heartbeat.", "slo.", "predict.cache_hit_ratio")

# Device scopes: the ONE table of names that may appear under "lgbm/"
# inside a device program, each with its layer of PERF.md section 3.
# obs/trace_attr.py reduces a profiler dump by these names.
LAYERS: Dict[str, str] = {
    "engine/gradients": "engine",
    "engine/goss_sample": "engine",
    "engine/goss_compact": "engine",
    "engine/score_update": "engine",
    "engine/valid_update": "engine",
    "grower/histogram": "grower",
    "grower/split_search": "grower",
    # the categorical candidates (sorted many-vs-many, one-hot) inside
    # split_search: an op takes the innermost scope
    "grower/cat_search": "grower",
    "grower/partition": "grower",
    "grower/leaf_values": "grower",
    "ingest/assign": "ingest",
    # the category-table lookup inside assign
    "ingest/cat_lookup": "ingest",
}

SCOPE_PREFIX = "lgbm/"


def scope(name: str):
    """``jax.named_scope("lgbm/" + name)`` for a name of :data:`LAYERS`
    (anything else raises): metadata on the ops traced inside it, no
    arithmetic and no schedule change."""
    if name not in LAYERS:
        raise KeyError(f"obs.scope: {name!r} is not in obs.LAYERS")
    import jax
    return jax.named_scope(SCOPE_PREFIX + name)


_ANNOTATION = None


def _annotation_cls():
    """``jax.profiler.TraceAnnotation`` with the ``set`` of a span, made
    at the first span so that importing obs never imports jax."""
    global _ANNOTATION
    from jax.profiler import TraceAnnotation

    class _Annotation(TraceAnnotation):
        __slots__ = ()

        def set(self, **attrs) -> None:
            self.set_metadata(**attrs)

    _ANNOTATION = _Annotation
    return _Annotation


def enable(metrics: bool = True, trace_dir: Optional[str] = None,
           trace: Optional[bool] = None,
           slo: Optional[bool] = None,
           slo_window_s: Optional[float] = None,
           slo_thresholds: Optional[Dict[str, float]] = None) -> None:
    """Turn observability on (idempotent; never turns anything off —
    a later Config that leaves ``tpu_metrics`` at its default must not
    silently disable what an earlier one enabled).

    ``slo=True`` (or any ``slo_window_s`` / ``slo_thresholds``) starts
    the windowed-SLI tracker (obs/slo.py); SLIs derive from the metric
    feeds, so enabling SLOs implies the metrics pillar.
    """
    if metrics:
        _state.metrics = True
        from .telemetry import ensure_compile_listener
        ensure_compile_listener()
    if trace or trace_dir:
        _tracing.enable_tracing(trace_dir)
    if slo or slo_window_s or slo_thresholds:
        _slo.enable(window_s=slo_window_s, thresholds=slo_thresholds)
        _state.slo = True
        enable(metrics=True)


def disable() -> None:
    """Turn instrumentation off (collected metrics/events persist until
    :func:`reset`). Primarily for tests."""
    _state.metrics = False
    _state.slo = False
    _tracing.disable_tracing()
    from .telemetry import pause_compile_listener
    pause_compile_listener()


def enabled() -> bool:
    """Is the METRICS pillar live (the gate hot paths check)?"""
    return _state.metrics


def slo_enabled() -> bool:
    """Is the windowed-SLI tracker live?"""
    return _state.slo and _slo.enabled()


def any_enabled() -> bool:
    return _state.metrics or _tracing.tracing_enabled()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class _Span:
    """Reentrant-per-instance span context manager (one per call)."""

    __slots__ = ("_t", "_force", "_ann")

    def __init__(self, name: str, args: Dict[str, Any], force: bool,
                 ann) -> None:
        self._t = _tracing._SpanTimer(name, args)
        self._force = force
        self._ann = ann

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self._t.start()
        return self

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. whether a
        registry checkout was a cache hit) — they land in the trace
        event recorded at exit (a disabled span takes them as the
        profiler annotation's metadata)."""
        self._t.args.update(attrs)

    def __exit__(self, *exc) -> None:
        self._t.stop(_tracing.tracing_enabled(),
                     _observe_span if (_state.metrics or self._force)
                     else None)
        self._ann.__exit__(*exc)


def _observe_span(name: str, dur: float) -> None:
    _metrics.registry().histogram(name).observe(dur)
    if _state.slo:
        _slo.feed_hist(name, dur)


def span(name: str, force: bool = False, **attrs):
    """Scoped phase timer: records a duration histogram under ``name``
    (when metrics are on) and a Chrome-trace event (when tracing is on).

    On EVERY call, on or off, the span is also a
    ``jax.profiler.TraceAnnotation("lgbm/" + name, **attrs)``: a no-op
    while no profiler session is open, and inside one the span lands on
    the host plane of the dump, on the clock the device ops are on.

    ``force=True`` records even when observability is globally off
    (explicit-measurement callers: utils/timer shim, benches).
    """
    ann = (_ANNOTATION or _annotation_cls())(SCOPE_PREFIX + name, **attrs)
    if not (force or _state.metrics or _tracing.tracing_enabled()):
        return ann
    return _Span(name, attrs, force, ann)


# ---------------------------------------------------------------------------
# metric helpers (hot-path funnels; force bypasses the global gate)
# ---------------------------------------------------------------------------
def inc(name: str, n: float = 1.0, force: bool = False,
        **labels) -> None:
    if _state.metrics or force:
        _metrics.registry().counter(name, **labels).inc(n)
        if _state.slo and not labels:
            _slo.feed_count(name, n)


def set_gauge(name: str, value: float, force: bool = False,
              **labels) -> None:
    if _state.metrics or force:
        _metrics.registry().gauge(name, **labels).set(value)


def observe(name: str, value: float, force: bool = False,
            **labels) -> None:
    if _state.metrics or force:
        _metrics.registry().histogram(name, **labels).observe(value)
        if _state.slo and not labels:
            _slo.feed_hist(name, value)


# cross-process heartbeat FILE sinks: kind -> [path, min_interval_s,
# last_stamp_monotonic]. The obs gauges above are process-local; the
# distributed launcher's watchdog lives in ANOTHER process, so workers
# stamp a file (mtime = the heartbeat) it can stat. Registered by
# engine.train from ``tpu_heartbeat_dir``; stamping is throttled to
# min_interval so a sub-millisecond round loop costs one clock read,
# not one syscall, per round. The file is created lazily on the FIRST
# stamp — a worker still compiling has no file, which the watchdog
# reads as "starting up" (covered by the gang timeout), never "stale".
_HB_FILES: Dict[str, list] = {}


def set_heartbeat_file(kind: str, path: Optional[str],
                       min_interval: float = 1.0) -> None:
    """Register (or, with ``path=None``, drop) a heartbeat file for
    ``kind``: every :func:`heartbeat` call refreshes the file's mtime
    (rate-limited to ``min_interval`` seconds). Works with the metrics
    pillar OFF — watchdog liveness must not depend on the user opting
    into metrics."""
    if path is None:
        _HB_FILES.pop(kind, None)
        return
    _HB_FILES[kind] = [str(path), float(min_interval), 0.0]


def heartbeat(kind: str) -> None:
    """Stamp the ``heartbeat.<kind>`` gauge with the current monotonic
    time. The round loop stamps ``train``, the predict path ``serve``;
    /healthz and /readyz (obs/server.py) compare these stamps against
    the staleness timeout, and the launcher watchdog compares the
    registered heartbeat FILE's mtime (:func:`set_heartbeat_file`).
    One gauge set when metrics are on, a single bool check when off —
    heartbeat call sites ride the hot loops."""
    if _state.metrics:
        _metrics.registry().gauge(f"heartbeat.{kind}").set(
            time.monotonic())
    if _HB_FILES:
        ent = _HB_FILES.get(kind)
        if ent is not None:
            now = time.monotonic()
            if now - ent[2] >= ent[1]:
                ent[2] = now
                try:
                    with open(ent[0], "a"):
                        pass
                    os.utime(ent[0])
                except OSError:
                    pass


def predict_instrumented(call: Callable[[], Any], data) -> Any:
    """The ONE serve-instrumentation sequence every predict entry point
    shares (engine path in boosting/gbdt.py, host-model path in
    basic.py — two copies WOULD drift and split the SLO feeds):
    ``predict.requests`` counts the ATTEMPT, the ``predict/call`` span
    times it (feeding the rolling SLO window), ``predict.errors``
    counts a raise, the serve heartbeat stamps on attempt (liveness is
    "the loop runs", not "requests succeed"), and ``predict.rows``
    lands on success. Callers gate on :func:`any_enabled` first — the
    off path must stay one bool check."""
    try:
        n_rows = int(data.shape[0])
    except Exception:
        n_rows = len(data) if hasattr(data, "__len__") else 0
    inc("predict.requests")
    try:
        with span("predict/call", rows=n_rows):
            out = call()
    except BaseException:
        inc("predict.errors")
        raise
    finally:
        heartbeat("serve")
    inc("predict.rows", n_rows)
    return out


def retire_heartbeat(kind: str) -> None:
    """Remove a heartbeat stamp at the CLEAN end of the loop it
    tracked. A retired heartbeat is *absent* — /healthz stays green
    for a process that finished its work and went idle — while a
    crashed or wedged loop leaves its last stamp behind to go stale
    (the 503 signal). The same contract applies to the heartbeat FILE:
    a clean finish unlinks it (absent = finished), a wedge leaves it
    to go stale under the launcher watchdog. Serving heartbeats are
    never retired: a serving process with no traffic for the staleness
    timeout IS the signal a load balancer probes for."""
    reg = _metrics.registry()
    if reg.get(f"heartbeat.{kind}") is not None:
        reg.reset(prefix=f"heartbeat.{kind}", kind="gauge")
    ent = _HB_FILES.pop(kind, None)
    if ent is not None:
        try:
            os.unlink(ent[0])
        except OSError:
            pass


def counter(name: str, **labels) -> _metrics.Counter:
    return _metrics.registry().counter(name, **labels)


def gauge(name: str, **labels) -> _metrics.Gauge:
    return _metrics.registry().gauge(name, **labels)


def histogram(name: str, **labels) -> _metrics.Histogram:
    return _metrics.registry().histogram(name, **labels)


# ---------------------------------------------------------------------------
# exporters / state
# ---------------------------------------------------------------------------
def snapshot(refresh_device: bool = True) -> Dict[str, Any]:
    """Full registry snapshot; refreshes the device/compile gauges
    first so HBM and program-cache numbers are current, and re-derives
    the SLO gauges from the sliding windows (one snapshot/scrape ==
    one SLO evaluation period)."""
    if refresh_device and any_enabled():
        from .telemetry import refresh_device_gauges
        refresh_device_gauges()
    if _state.slo:
        _slo.evaluate()
    return _metrics.registry().snapshot()


def dump_jsonl(path: str, snap: Optional[Dict[str, Any]] = None) -> str:
    """Append one snapshot line to ``path``. Pass ``snap`` to dump an
    already-taken snapshot (the benches print their metric line and
    dump from the SAME dict so the two can never disagree); otherwise
    a fresh device-gauge-refreshed snapshot is taken."""
    return _metrics.registry().dump_jsonl(
        path, snap if snap is not None else snapshot())


def prometheus_text() -> str:
    return prometheus_from_snapshot(snapshot())


def export_state() -> Dict[str, Any]:
    """Serializable metrics state for checkpoints (metrics pillar only;
    trace events are a per-process artifact, not training state, and
    heartbeat stamps / windowed SLO gauges are process-local monotonic
    state that must NOT resume from a checkpoint — the live round loop
    re-stamps them)."""
    state = _metrics.registry().export_state()
    state["metrics"] = [
        m for m in state["metrics"]
        if not str(m.get("name", "")).startswith(_EPHEMERAL_PREFIXES)]
    return state


def import_state(state: Optional[Dict[str, Any]]) -> int:
    return _metrics.registry().import_state(state)


def reset(prefix: Optional[str] = None) -> None:
    """Clear collected metrics (all, or a name prefix) and — when
    clearing everything — the trace buffer and the windowed-SLI
    tracker. Enable flags persist (except SLO, whose state IS the
    tracker)."""
    _metrics.registry().reset(prefix)
    if prefix is None:
        _tracing.reset_events()
        _slo.reset()
        _state.slo = False


# ---------------------------------------------------------------------------
# Config wiring (called from Config._post_process; see config.py knobs)
# ---------------------------------------------------------------------------
def configure_from_config(cfg) -> None:
    """Engage pillars the config asks for. Enable-only: a Config built
    with default knobs mid-run (train() builds several) never disables
    what an earlier explicit config enabled."""
    want_metrics = bool(getattr(cfg, "tpu_metrics", False))
    tdir = str(getattr(cfg, "tpu_trace_dir", "") or "").strip()
    dump = str(getattr(cfg, "tpu_metrics_dump", "") or "").strip()
    rank_dir = str(getattr(cfg, "tpu_metrics_rank_dir", "") or "").strip()
    port = int(getattr(cfg, "tpu_metrics_port", 0) or 0)
    thresholds = {
        "predict_p99_ms": float(
            getattr(cfg, "tpu_slo_predict_p99_ms", 0.0) or 0.0),
        "error_ratio": float(
            getattr(cfg, "tpu_slo_error_ratio", 0.0) or 0.0),
    }
    thresholds = {k: v for k, v in thresholds.items() if v > 0}
    if want_metrics or dump or rank_dir:
        enable(metrics=True)
    if tdir:
        enable(metrics=False, trace_dir=tdir)
    # any SLO knob — a threshold, an explicit window, or the live
    # endpoint (whose whole point is rolling SLO gauges) — starts the
    # windowed-SLI tracker; tpu_slo_window_s alone must not be inert
    win = float(getattr(cfg, "tpu_slo_window_s", 0.0) or 0.0)
    if thresholds or port > 0 or win > 0:
        enable(slo=True, slo_window_s=win or None,
               slo_thresholds=thresholds or None)
    if port > 0:
        from .server import start_server
        hb = float(getattr(cfg, "tpu_heartbeat_timeout", 0.0) or 0.0)
        # None = knob unset: keep the live server's timeout (or the
        # default on first start) — enable-only like every other knob
        start_server(port, heartbeat_timeout_s=(hb if hb > 0 else None))


def flush_from_config(cfg) -> None:
    """End-of-run exports the config asked for: the JSONL metrics
    snapshot (``tpu_metrics_dump``) and the Chrome trace file
    (``tpu_trace_dir``). Idempotent and exception-safe — a failed
    export warns, it never fails the training run that produced it."""
    from ..utils import log
    dump = str(getattr(cfg, "tpu_metrics_dump", "") or "").strip()
    if dump:
        try:
            dump_jsonl(dump)
        except Exception as e:
            log.warning(f"tpu_metrics_dump: cannot write {dump!r}: {e}")
    if _tracing.tracing_enabled() and _tracing.trace_dir():
        try:
            export_chrome_trace()
        except Exception as e:
            log.warning(f"tpu_trace_dir: cannot export trace: {e}")
