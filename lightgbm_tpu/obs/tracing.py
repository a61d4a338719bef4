"""Phase-scoped tracing: nested spans exported as Chrome-trace JSON.

The deep kernel story belongs to ``jax.profiler`` (xprof/TensorBoard,
wired via ``tpu_profile_dir``); these spans cover the HOST orchestration
the device profiler does not attribute — round loops, chunked predict,
ingest streaming, checkpoint writes — and export to the Chrome trace
event format, loadable directly in Perfetto (ui.perfetto.dev) or
chrome://tracing.

Span bookkeeping is thread-local (a per-thread stack gives nesting
depth and parent names); the event buffer is process-global, bounded,
and lock-protected. Every event is a ``ph: "X"`` complete event with
microsecond ``ts``/``dur`` on a monotonic base, so nesting renders as
containment per thread row. The serving dispatch loop additionally
records flow events (``ph: "s"``/``"f"``) so a coalesced rider's
submit visually connects to the batch that carried it.

Multi-rank runs: :func:`set_trace_rank` tags the export with the
process's rank — events get ``pid = rank`` plus a ``process_name``
metadata row ("rank N"), the default filename becomes
``rank_<r>.trace.json``, and the export envelope carries a wall/
monotonic clock pair taken at the same instant so
``scripts/trace_merge.py`` (obs/aggregate.py) can rebase every rank
onto one wall-clock timeline.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

__all__ = ["tracing_enabled", "enable_tracing", "disable_tracing",
           "record_event", "record_flow", "events", "dropped_events",
           "reset_events", "export_chrome_trace", "span_stack",
           "trace_dir", "set_trace_rank", "trace_rank", "track_tid"]

# bound the buffer: a runaway span site must degrade to dropped-event
# accounting, never to unbounded host memory. Overflow drops the
# OLDEST events (a long-lived serving process keeps its most recent
# window — the one the p99 postmortem needs), counted in _dropped.
MAX_EVENTS = 200_000

_lock = threading.Lock()
_enabled = False
_dir: Optional[str] = None
_events: Deque[tuple] = deque()
_dropped = 0
_rank: Optional[int] = None
_tls = threading.local()


def tracing_enabled() -> bool:
    return _enabled


def trace_dir() -> Optional[str]:
    return _dir


def enable_tracing(directory: Optional[str] = None) -> None:
    """Start collecting span events; ``directory`` (optional) is where
    ``export_chrome_trace`` writes by default. A second different
    directory keeps the first (one trace stream per process)."""
    global _enabled, _dir
    with _lock:
        _enabled = True
        if directory:
            if _dir and _dir != str(directory):
                from ..utils import log
                log.warning(
                    f"tpu_trace_dir={directory!r} ignored: tracing is "
                    f"already exporting to {_dir!r} (process-global)")
            else:
                _dir = str(directory)


def disable_tracing() -> None:
    global _enabled
    with _lock:
        _enabled = False


def set_trace_rank(rank: Optional[int]) -> None:
    """Tag this process's trace stream with a gang rank (None clears).
    Called by the distributed worker body once ``jax.process_index()``
    is known; single-process runs stay untagged (pid-keyed export)."""
    global _rank
    _rank = None if rank is None else int(rank)


def trace_rank() -> Optional[int]:
    return _rank


def span_stack() -> List[str]:
    """This thread's open span names, outermost first."""
    return list(getattr(_tls, "stack", ()))


# named virtual tracks: stable synthetic tids OUTSIDE the 31-bit
# range real thread idents are masked into (& 0x7FFFFFFF), so a
# retroactive/asynchronous event's row can never collide with a real
# thread's and corrupt its nesting
_tracks: Dict[str, int] = {}
_TRACK_BASE = 0x80000000


def track_tid(name: str) -> int:
    """Stable synthetic tid for a named virtual track (registered so
    the export names the row, e.g. "serve queue")."""
    with _lock:
        t = _tracks.get(name)
        if t is None:
            t = _TRACK_BASE + len(_tracks)
            _tracks[name] = t
        return t


def _push(name: str) -> int:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    stack.append(name)
    return len(stack) - 1


def _pop() -> None:
    stack = getattr(_tls, "stack", None)
    if stack:
        stack.pop()


def record_event(name: str, start_monotonic: float, dur_s: float,
                 args: Optional[Dict[str, Any]] = None,
                 tid: Optional[int] = None) -> None:
    """Append one complete event (called by ``obs.span`` on exit).
    ``tid`` overrides the recording thread's ident — retroactive
    events (e.g. the serving queue-wait, recorded at dispatch time
    but SPANNING the enqueue window) go on a :func:`track_tid`
    virtual row so they do not overlap real spans on this thread.

    The buffer holds RAW TUPLES, not Chrome-trace dicts: recording
    rides the serving dispatch loop (~10 events per coalesced batch),
    and a tuple append under the GIL costs a fraction of a dict build
    + lock round-trip — dict materialization happens once, on the
    cold export/read path (:func:`events`). Shapes:
    ``("X", name, ts_s, dur_s, tid, args|None, parent|None, depth)``
    and ``("s"|"f", name, flow_id, ts_s, tid,
    args|None)``."""
    stack = getattr(_tls, "stack", ())
    depth = len(stack) - 1
    _append(("X", name, start_monotonic, dur_s,
             (int(tid) if tid is not None
              else threading.get_ident() & 0x7FFFFFFF),
             args, stack[-2] if depth > 0 else None, depth))


def _append(rec: tuple) -> None:
    """Buffer one raw record, dropping the OLDEST past MAX_EVENTS.
    The append itself is a single GIL-atomic deque op; only the
    (amortized) overflow trim takes the lock."""
    global _dropped
    _events.append(rec)
    if len(_events) > MAX_EVENTS:
        with _lock:
            while len(_events) > MAX_EVENTS:
                _events.popleft()
                _dropped += 1


def record_flow(name: str, flow_id: int, phase: str,
                args: Optional[Dict[str, Any]] = None) -> None:
    """Append one flow event (``phase`` = "s" start / "f" finish):
    Perfetto draws an arrow from the "s" point to the "f" point with
    the same ``id``/``name`` — the serving path uses it to connect a
    coalesced rider's submit to the batch that carried it."""
    _append(("f" if phase == "f" else "s", str(name), int(flow_id),
             time.monotonic(),
             threading.get_ident() & 0x7FFFFFFF, args))


def _materialize(rec: tuple, pid: int) -> Dict[str, Any]:
    """One raw buffer tuple -> Chrome-trace event dict (cold path)."""
    kind = rec[0]
    if kind == "X":
        _k, name, ts, dur, tid, args, parent, depth = rec
        ev: Dict[str, Any] = {
            "name": str(name), "ph": "X", "ts": ts * 1e6,
            "dur": max(dur, 0.0) * 1e6, "pid": pid, "tid": tid,
        }
        a = dict(args) if args else {}
        if parent is not None:
            a["parent"] = parent
            a["depth"] = depth
        if a:
            ev["args"] = a
        return ev
    _k, name, flow_id, ts, tid, args = rec
    ev = {"name": name, "cat": name, "ph": kind, "id": flow_id,
          "ts": ts * 1e6, "pid": pid, "tid": tid}
    if kind == "f":
        # bind to the ENCLOSING slice's end, so the arrow lands on the
        # batch span rather than a zero-width point
        ev["bp"] = "e"
    if args:
        ev["args"] = dict(args)
    return ev


def events() -> List[Dict[str, Any]]:
    """The buffered events as Chrome-trace dicts (cold path: tests,
    benches, the export)."""
    pid = os.getpid()
    with _lock:
        raw = list(_events)
    return [_materialize(r, pid) for r in raw]


def dropped_events() -> int:
    return _dropped


def reset_events() -> None:
    global _dropped
    with _lock:
        _events.clear()
        _dropped = 0


def export_chrome_trace(path: Optional[str] = None) -> Optional[str]:
    """Write the collected events as Chrome-trace JSON and return the
    path (None when there is nowhere to write). Default filename is
    ``rank_<r>.trace.json`` when a rank is set (multi-rank gangs must
    not collide on pid-keyed names across hosts), else
    ``trace_<pid>.json``, under the configured trace dir; repeat
    exports overwrite (the buffer only grows within a process).

    The export rank-tags the stream: every event's ``pid`` becomes the
    rank (all buffered events belong to THIS process — the buffer is
    process-global), a ``process_name`` metadata row names the
    Perfetto process track, and the envelope records a wall/monotonic
    clock pair taken at the same instant so the cross-rank merger can
    rebase per-boot monotonic timestamps onto one shared timeline —
    the same envelope contract obs/aggregate.py's gauge merge uses."""
    rank = _rank
    if path is None:
        if not _dir:
            return None
        name = (f"rank_{rank}.trace.json" if rank is not None
                else f"trace_{os.getpid()}.json")
        path = os.path.join(_dir, name)
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    pid = os.getpid()
    out_pid = rank if rank is not None else pid
    proc_label = (f"rank {rank} (pid {pid})" if rank is not None
                  else f"lightgbm-tpu (pid {pid})")
    # wall/monotonic envelope pair, read back-to-back: the rebase error
    # is bounded by the gap between these two clock reads
    wall, mono = time.time(), time.monotonic()
    with _lock:
        raw = list(_events)
        dropped = _dropped
        # snapshot under the same lock track_tid mutates under — an
        # unlocked dict-comprehension could catch a concurrent first
        # registration mid-iteration
        track_names = {t: n for n, t in _tracks.items()}
    events = [_materialize(r, out_pid) for r in raw]
    tids = sorted({e["tid"] for e in events if "tid" in e})
    meta: List[Dict[str, Any]] = [{
        "name": "process_name", "ph": "M", "pid": out_pid,
        "args": {"name": proc_label},
    }]
    if rank is not None:
        # rank order == row order in the merged Perfetto view
        meta.append({"name": "process_sort_index", "ph": "M",
                     "pid": out_pid, "args": {"sort_index": rank}})
    meta.extend({"name": "thread_name", "ph": "M", "pid": out_pid,
                 "tid": t,
                 "args": {"name": track_names.get(t, f"thread {t}")}}
                for t in tids)
    doc = {
        "displayTimeUnit": "ms",
        "traceEvents": meta + events,
        "otherData": {
            "producer": "lightgbm-tpu obs",
            "dropped_events": dropped,
            "pid": pid,
            "rank": rank,
            # envelope clock pair for cross-rank monotonic rebase
            "ts": wall,
            "monotonic": mono,
        },
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


class _SpanTimer:
    """Internal helper used by ``obs.span``: measures wall duration
    and feeds trace + metrics."""

    __slots__ = ("name", "args", "t0", "depth")

    def __init__(self, name: str, args: Dict[str, Any]) -> None:
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.depth = 0

    def start(self) -> None:
        self.depth = _push(self.name)
        self.t0 = time.monotonic()

    def stop(self, record_trace: bool, observe) -> None:
        dur = time.monotonic() - self.t0
        if record_trace:
            record_event(self.name, self.t0, dur, self.args)
        _pop()
        if observe is not None:
            observe(self.name, dur)
