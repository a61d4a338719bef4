"""Trace-level attribution: a profiler dump (``*.xplane.pb``) by layer.

``jax.profiler`` writes an XSpace dump; the TensorBoard converter is
protobuf-incompatible in this environment, so this module reads the
wire format itself (stdlib only — the obs package's import-light
constraint; no ``protobuf``, no jax) and reduces the dump to what the
layers of this program cost on the device's clock:

- the device plane's "XLA Ops" line, each op with its SELF time (its
  duration less what its children cover: a scan's ``while`` encloses
  the ops of its body) and the device's busy time as the UNION of the
  LEAF op intervals inside the window, so busy never passes the window;
- ``layers``: every leaf op joined to the ``metadata.op_name`` XLA
  kept for its instruction, cut to the innermost
  ``lgbm/<layer>/<phase>`` that ``obs.scope`` put there. A TPU's dump
  carries that op_name on the event's own metadata (the ``tf_op``
  stat); where it does not (the CPU's), the op is joined by its name
  and the program it ran in to the HLO the dump embeds
  (``/host:metadata``). An op with no such scope, or whose name means
  different scopes in different programs where the event names none,
  goes to ``unscoped``. The scopes and ``unscoped`` add up to busy
  exactly;
- the window: a named host annotation (default: the outermost
  ``lgbm/train/*`` spans, which ``obs.span`` writes into every dump),
  else first op to last op;
- ``idle_gaps``: the complement of busy inside the window, each gap
  named by the innermost ``lgbm/`` host annotation open at its middle;
  ``spans``: the program's host spans, summed by name;
- the ``%copy`` share (the loop-state-copy signal the donation pass
  exists to squeeze) and the collective share.

These are the rules of ``benchmark/lib/xplane.py``; a test holds the
two readers to the same numbers on the benchmark's recorded dump.
:func:`profile_gauges` feeds the result into the metrics registry as
``train.copy_share`` / ``train.comm_share`` /
``train.wall_busy_gap_ms`` / ``train.layer_ms{scope=...}``.

Consumed by ``engine.train`` (after a ``tpu_profile_dir`` trace stops)
and the ``scripts/trace_attr.py`` CLI. A
dump with no device plane (the CPU backend's) is read through the
host threads that ran XLA's ops, so the join can be checked without a
chip; a dump with neither reports "no device plane found" instead of
failing the run that produced it.
"""
from __future__ import annotations

import bisect
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import SCOPE_PREFIX

__all__ = ["parse_xspace", "aggregate_ops", "attribute",
           "newest_xplane", "profile_gauges", "hlo_scopes"]

# ops counted as loop-state / buffer copies in the share metric: HLO
# names like "copy.1234", "%copy", "copy-start.5"/"copy-done.5" (async
# copy pairs) — matched on the base name before the ".N" suffix
_COPY_BASES = ("copy", "copy-start", "copy-done")

# ops counted as cross-device communication in the comm share metric:
# the collectives the sharded trainer/predictor can emit (sync forms
# plus the async -start/-done pairs XLA splits them into). comm_share
# is the number the tpu_stream_overlap pipeline moves: overlapped
# collectives show the same comm busy but a smaller wall-vs-busy gap.
_COMM_BASES = ("all-reduce", "all-reduce-start", "all-reduce-done",
               "reduce-scatter", "all-gather", "all-gather-start",
               "all-gather-done", "collective-permute",
               "collective-permute-start", "collective-permute-done",
               "all-to-all")

UNSCOPED = "unscoped"
_SCOPE_RE = re.compile(r"lgbm/[\w.\-]+/[\w.\-]+")


# ---------------------------------------------------------------------------
# protobuf wire format
# ---------------------------------------------------------------------------
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if not b & 0x80:
            return x, i
        s += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, Any]]:
    """(field_number, wire_type, value) triples of one message."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:                       # varint
            v, i = _varint(buf, i)
        elif wt == 2:                     # length-delimited
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:                     # 32-bit
            v = buf[i:i + 4]
            i += 4
        elif wt == 1:                     # 64-bit
            v = buf[i:i + 8]
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield fnum, wt, v


def _text(v: bytes) -> str:
    return v.decode("utf-8", "replace")


def _parse_event(buf: bytes) -> Tuple[int, int, int]:
    """XEvent -> (metadata_id, offset_ps, duration_ps)."""
    mid = off = dur = 0
    for fnum, _wt, v in _fields(buf):
        if fnum == 1:
            mid = v
        elif fnum == 2:
            off = v
        elif fnum == 3:
            dur = v
    return mid, off, dur


def _parse_line(buf: bytes) -> Dict[str, Any]:
    """XLine -> {name, timestamp_ns, events}."""
    out: Dict[str, Any] = {"name": "", "timestamp_ns": 0, "events": []}
    for fnum, _wt, v in _fields(buf):
        if fnum == 2:
            out["name"] = _text(v)
        elif fnum == 11 and not out["name"]:
            out["name"] = _text(v)
        elif fnum == 3:
            out["timestamp_ns"] = v
        elif fnum == 4:
            out["events"].append(_parse_event(v))
    return out


def _parse_stat(buf: bytes) -> Tuple[int, Any]:
    """XStat -> (stat metadata id, value); bytes stay bytes."""
    sid, val = 0, None
    for fnum, wt, v in _fields(buf):
        if fnum == 1:
            sid = v
        elif fnum in (3, 4, 7) and wt == 0:
            val = v
        elif fnum == 5:
            val = _text(v)
        elif fnum == 6:
            val = v
    return sid, val


def _parse_plane(buf: bytes) -> Dict[str, Any]:
    """XPlane -> {name, lines, event_names (metadata_id -> op name),
    event_stats (metadata_id -> raw XStat list), stat_names}."""
    out: Dict[str, Any] = {"name": "", "lines": [], "event_names": {},
                           "event_stats": {}, "stat_names": {}}
    for fnum, _wt, v in _fields(buf):
        if fnum == 2:
            out["name"] = _text(v)
        elif fnum == 3:
            out["lines"].append(_parse_line(v))
        elif fnum == 4:
            # map<int64, XEventMetadata> entry: key=1, value=2
            key, name, disp, stats = 0, "", "", []
            for f2, _w2, v2 in _fields(v):
                if f2 == 1:
                    key = v2
                elif f2 == 2:
                    for f3, _w3, v3 in _fields(v2):
                        if f3 == 1:
                            key = key or v3
                        elif f3 == 2:
                            name = _text(v3)
                        elif f3 == 4:
                            disp = _text(v3)
                        elif f3 == 5:
                            stats.append(v3)
            out["event_names"][key] = name or disp
            if stats:
                out["event_stats"][key] = stats
        elif fnum == 5:
            # map<int64, XStatMetadata> entry
            key, name = 0, ""
            for f2, _w2, v2 in _fields(v):
                if f2 == 1:
                    key = v2
                elif f2 == 2:
                    for f3, _w3, v3 in _fields(v2):
                        if f3 == 1:
                            key = key or v3
                        elif f3 == 2:
                            name = _text(v3)
            out["stat_names"][key] = name
    return out


def parse_xspace(data: bytes) -> List[Dict[str, Any]]:
    """XSpace bytes -> list of plane dicts (schema subset above)."""
    return [_parse_plane(v) for fnum, _wt, v in _fields(data)
            if fnum == 1]


def _event_stats(plane: Dict[str, Any], mid: int) -> Dict[str, Any]:
    """One event metadata's stats by stat name."""
    out = {}
    for raw in plane["event_stats"].get(mid, ()):
        sid, val = _parse_stat(raw)
        out[plane["stat_names"].get(sid, str(sid))] = val
    return out


# ---------------------------------------------------------------------------
# the HLO the dump embeds: instruction name -> scope, by program
# ---------------------------------------------------------------------------
def _scope_of(op_name: str) -> Optional[str]:
    """The innermost ``lgbm/<layer>/<phase>`` of an HLO op_name such as
    ``jit(chunk)/while/body/lgbm/engine/goss_sample/jit(sort)/sort``."""
    hits = _SCOPE_RE.findall(op_name or "")
    return hits[-1] if hits else None


def _hlo_instr_scopes(hlo_proto: bytes) -> Tuple[str, Dict[str, str]]:
    """HloProto bytes -> (module name, {instruction name -> scope}) over
    every computation (fused ones too; an instruction's scope is the one
    XLA kept in its own metadata)."""
    module = b""
    for fnum, _wt, v in _fields(hlo_proto):
        if fnum == 1:
            module = v
    name, scopes = "", {}
    for fnum, _wt, comp in _fields(module):
        if fnum == 1:
            name = _text(comp)
        elif fnum == 3:
            for f2, _w2, ins in _fields(comp):
                if f2 != 2:
                    continue
                iname, op_name = "", ""
                for f3, _w3, v3 in _fields(ins):
                    if f3 == 1:
                        iname = _text(v3)
                    elif f3 == 7:
                        for f4, _w4, v4 in _fields(v3):
                            if f4 == 2:
                                op_name = _text(v4)
                sc = _scope_of(op_name)
                if sc:
                    scopes[iname] = sc
    return name, scopes


def hlo_scopes(planes: List[Dict[str, Any]]) -> Dict[str, Dict[str, str]]:
    """{program -> {instruction name -> ``lgbm/...`` scope}} from the
    dump's ``/host:metadata`` plane, whose event metadata hold one
    program each ("jit_chunk_impl(3)") with its HLO as a bytes stat."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in planes:
        if plane["name"] != "/host:metadata":
            continue
        for mid, program in plane["event_names"].items():
            for val in _event_stats(plane, mid).values():
                if isinstance(val, bytes) and val:
                    try:
                        _mod, scopes = _hlo_instr_scopes(val)
                    except (ValueError, IndexError):
                        continue
                    out.setdefault(program, {}).update(scopes)
    return out


# ---------------------------------------------------------------------------
# arithmetic (the rules of benchmark/lib/xplane.py)
# ---------------------------------------------------------------------------
def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Disjoint sorted cover of a list of [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: List[Tuple[Any, int, int]]
                ) -> Tuple[Dict[Any, List[float]],
                           List[Tuple[Any, int, int]]]:
    """(key -> [self picoseconds, calls], the LEAF events) over one
    device's (key, start, end) events, which nest (a ``while``
    encloses its body) and do not cross. A leaf encloses no other."""
    out: Dict[Any, List[float]] = {}
    leaves: List[Tuple[Any, int, int]] = []
    stack: List[list] = []        # [name, start, end, self_ps, is_leaf]

    def close(upto: int) -> None:
        while stack and stack[-1][2] <= upto:
            name, s, e, self_ps, is_leaf = stack.pop()
            ent = out.setdefault(name, [0.0, 0])
            ent[0] += max(self_ps, 0)
            ent[1] += 1
            if is_leaf:
                leaves.append((name, s, e))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            # a child takes its span out of the parent's self time
            stack[-1][3] -= min(e, stack[-1][2]) - s
            stack[-1][4] = False
        stack.append([name, s, e, e - s, True])
    close(1 << 62)
    return out, leaves


def _short_name(name: str) -> str:
    """The op's own name out of the profiler's event name, which on a
    TPU is the whole HLO line: "%fusion.3 = f32[...] fusion(...)" ->
    "fusion.3"."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _base_op(name: str) -> str:
    """HLO op base name: "%copy.123" -> "copy", "fusion.7" -> "fusion"."""
    return _short_name(name).split(".", 1)[0]


def _line_events(plane: Dict[str, Any], ln: Dict[str, Any]
                 ) -> List[Tuple[str, int, int]]:
    base = ln["timestamp_ns"] * 1000
    names = plane["event_names"]
    return [(names.get(mid, f"op#{mid}"), base + off, base + off + dur)
            for mid, off, dur in ln["events"]]


def _own_scopes(plane: Dict[str, Any]) -> Dict[str, str]:
    """{event name -> scope} for the events of a device plane whose own
    metadata carry their op_name (a TPU's ``tf_op`` stat); an event
    that has the stat and no ``lgbm/`` scope in it is ``unscoped``."""
    out = {}
    for mid, name in plane["event_names"].items():
        if mid in plane["event_stats"]:
            tf_op = _event_stats(plane, mid).get("tf_op")
            if isinstance(tf_op, str):
                out[name] = _scope_of(tf_op) or UNSCOPED
    return out


def _devices(planes: List[Dict[str, Any]], known: set
             ) -> List[Dict[str, Any]]:
    """One entry a device: {plane, ops [(event name, start, end)], own
    ({event name -> scope} where the events carry their op_name),
    modules [(program, start, end)]}. A device plane's "XLA Ops" line
    (every line where there is none); for a dump with no device plane,
    ONE stand-in device made of the host threads' events whose names
    are instructions of the embedded HLO (the CPU backend runs its ops
    on host threads)."""
    found = []
    for plane in planes:
        if "/device:" not in plane["name"]:
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"]
        if not lines:
            lines = [ln for ln in plane["lines"] if ln["events"]
                     and ln["name"] not in ("XLA Modules", "Steps")]
        ops = [ev for ln in lines for ev in _line_events(plane, ln)]
        if not ops:
            continue
        modules = [ev for ln in plane["lines"]
                   if ln["name"] == "XLA Modules"
                   for ev in _line_events(plane, ln)]
        found.append({"plane": plane["name"], "ops": ops,
                      "own": _own_scopes(plane),
                      "modules": sorted(modules, key=lambda m: m[1])})
    if found or not known:
        return found
    ops = []
    for plane in planes:
        if plane["name"].startswith("/host:") \
                and plane["name"] != "/host:metadata":
            for ln in plane["lines"]:
                ops += [ev for ev in _line_events(plane, ln)
                        if ev[0] in known]
    if ops:
        # host threads run side by side, so their events cross and no
        # nesting can be read from them: "flat" makes every event a
        # leaf whose time is its length (busy stays a union)
        found.append({"plane": "host threads (no device plane)",
                      "ops": ops, "own": {}, "modules": [], "flat": True})
    return found


def _host_annotations(planes: List[Dict[str, Any]], prefix: str
                      ) -> List[Tuple[str, int, int]]:
    out = []
    for plane in planes:
        if "/device:" in plane["name"]:
            continue
        for ln in plane["lines"]:
            out += [ev for ev in _line_events(plane, ln)
                    if ev[0].startswith(prefix)]
    return out


def _window(notes: List[Tuple[str, int, int]], name: Optional[str]
            ) -> Optional[Tuple[str, int, int]]:
    """(name, start, end) of the window among the host annotations: the
    hull of the spans called ``name``, or, with no name, of the
    OUTERMOST ``lgbm/train/*`` spans (those no other encloses)."""
    if name:
        hit = [(s, e) for n, s, e in notes if n == name]
        label = name
    else:
        train = [(n, s, e) for n, s, e in notes
                 if n.startswith(SCOPE_PREFIX + "train/")]
        outer = [(n, s, e) for n, s, e in train
                 if not any((s2 <= s and e <= e2) and (s2, e2) != (s, e)
                            for _n2, s2, e2 in train)]
        hit = [(s, e) for _n, s, e in outer]
        label = "+".join(sorted({n for n, _s, _e in outer}))
    if not hit:
        return None
    return label, min(s for s, _e in hit), max(e for _s, e in hit)


def aggregate_ops(planes: List[Dict[str, Any]],
                  window: Optional[str] = None,
                  prefix: str = SCOPE_PREFIX) -> Optional[Dict[str, Any]]:
    """Everything :func:`attribute` reports, in picoseconds and meaned
    over the device planes that hold ops: per-op self times keyed by
    (name, scope), busy (union of leaf ops inside the window), the
    scopes' parts of it, the copy and collective parts of the self
    times, idle gaps, host spans. None when nothing ran on a device
    (and no host thread ran an op of the embedded HLO), or the named
    window is not in the dump."""
    scopes = hlo_scopes(planes)
    known = {n for prog in scopes.values() for n in prog}
    # a name that means ONE scope in every program that has it joins
    # without a program; one that means several does not
    by_name: Dict[str, Optional[str]] = {}
    for prog in scopes.values():
        for n, sc in prog.items():
            by_name[n] = sc if by_name.get(n, sc) == sc else None
    devs = _devices(planes, known)
    if not devs:
        return None
    notes = _host_annotations(planes, prefix)
    win = _window(notes, window)
    if window and win is None:
        return None
    all_s = min(s for d in devs for _n, s, _e in d["ops"])
    all_e = max(e for d in devs for _n, _s, e in d["ops"])
    label, t0, t1 = win or ("first op to last op", all_s, all_e)

    k = len(devs)
    busy = 0.0
    ops: Dict[Tuple[str, str], List[float]] = {}
    layers: Dict[str, float] = {}
    layer_ops: Dict[str, Dict[str, float]] = {}
    gaps: List[Tuple[str, float]] = []
    for idx, dev in enumerate(devs):
        mod_starts = [m[1] for m in dev["modules"]]

        def scope_for(raw: str, n: str, s: int) -> str:
            own = dev["own"].get(raw)
            if own is not None:
                return own
            if dev["modules"]:
                i = bisect.bisect_right(mod_starts, s) - 1
                if i >= 0 and s < dev["modules"][i][2]:
                    prog = scopes.get(dev["modules"][i][0])
                    if prog is not None:
                        return prog.get(n) or UNSCOPED
            return by_name.get(n) or UNSCOPED

        # an op is its name AND its scope: one name can be two
        # instructions in two programs
        events = []
        for raw, s, e in dev["ops"]:
            n = _short_name(raw)
            events.append(((n, scope_for(raw, n, s)), s, e))
        if dev.get("flat"):
            times: Dict[Tuple[str, str], List[float]] = {}
            for key, s, e in events:
                ent = times.setdefault(key, [0.0, 0])
                ent[0] += e - s
                ent[1] += 1
            leaves = events
        else:
            times, leaves = _self_times(events)
        for key, (ps, calls) in times.items():
            ent = ops.setdefault(key, [0.0, 0])
            ent[0] += ps
            ent[1] += calls
        # the union, walked in time order, so that every covered
        # instant goes to exactly one leaf: scopes add up to busy
        cursor = t0
        cover = []
        for (n, sc), s, e in sorted(leaves, key=lambda ev: ev[1]):
            a, b = max(s, cursor), min(e, t1)
            if b > a:
                layers[sc] = layers.get(sc, 0.0) + (b - a)
                lo = layer_ops.setdefault(sc, {})
                lo[n] = lo.get(n, 0.0) + (b - a)
                cover.append((a, b))
                cursor = b
        cover = _union(cover)
        busy += sum(e - s for s, e in cover)
        if idx == 0:
            edges = [(t0, t0)] + cover + [(t1, t1)]
            for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
                if s1 <= e0:
                    continue
                mid = (e0 + s1) // 2
                # the innermost (latest-started) annotation names it
                inner = max(((s, n) for n, s, e in notes if s <= mid < e),
                            default=None)
                gaps.append((inner[1] if inner else "(none)", s1 - e0))
    spans: Dict[str, List[float]] = {}
    for n, s, e in notes:
        ent = spans.setdefault(n, [0.0, 0])
        ent[0] += e - s
        ent[1] += 1
    ops = {key: [v[0] / k, int(v[1])] for key, v in ops.items()}
    return {
        "device_plane": devs[0]["plane"], "n_devices": k,
        "window": label, "window_ps": t1 - t0, "busy_ps": busy / k,
        "ops": ops,
        "copy_ps": sum(v[0] for (n, _sc), v in ops.items()
                       if _base_op(n) in _COPY_BASES),
        "comm_ps": sum(v[0] for (n, _sc), v in ops.items()
                       if _base_op(n) in _COMM_BASES),
        "layers": {sc: ps / k for sc, ps in layers.items()},
        "layer_ops": layer_ops,
        "idle_gaps": sorted(gaps, key=lambda g: -g[1]),
        "spans": spans,
    }


def newest_xplane(path: str) -> Optional[str]:
    """``path`` itself if it is a file, else the newest ``*.xplane.pb``
    under it (jax.profiler writes <dir>/plugins/profile/<ts>/...)."""
    if os.path.isfile(path):
        return path
    newest, newest_m = None, -1.0
    for dirpath, _dirs, files in os.walk(path):
        for fn in files:
            if fn.endswith(".xplane.pb"):
                full = os.path.join(dirpath, fn)
                m = os.path.getmtime(full)
                if m > newest_m:
                    newest, newest_m = full, m
    return newest


def attribute(path: str, iters: Optional[int] = None,
              wall_ms: Optional[float] = None,
              window: Optional[str] = None,
              prefix: str = SCOPE_PREFIX) -> Dict[str, Any]:
    """Full attribution of one profile dump.

    Args:
      path: an ``.xplane.pb`` file or a ``tpu_profile_dir`` tree (the
        newest dump inside is used).
      iters: boosting iterations the window covered — enables the
        per-iteration figures.
      wall_ms: host-measured wall time of the traced window; overrides
        the window's own length in ``wall_ms`` and the gap.
      window: name of the host annotation that is the window (the hull
        of its spans); default the outermost ``lgbm/train/*`` spans,
        and first op to last op where the dump has none.
      prefix: host annotations that may name a window or a gap.

    Returns a dict with ``found`` False (and ``reason``) when there is
    nothing to attribute; else ``ops`` (descending by self time, each
    ``{name, ms, calls, share, scope}``), ``layers`` (descending, each
    ``{scope, ms, ms_per_iter, share, ops}``, adding up to ``busy_ms``),
    ``idle_gaps`` (``{name, ms}``, longest first), ``spans`` (the
    ``prefix`` host annotations summed by name: ``{name, ms, count}``),
    ``busy_ms``, ``wall_ms``, ``window``, ``copy_*``, ``comm_*`` and —
    with ``iters`` — ``wall_busy_gap_ms`` per iteration.
    """
    f = newest_xplane(path)
    if f is None:
        return {"found": False, "reason": f"no .xplane.pb under {path}"}
    try:
        with open(f, "rb") as fh:
            planes = parse_xspace(fh.read())
        agg = aggregate_ops(planes, window, prefix)
    except (OSError, ValueError, IndexError) as e:
        return {"found": False,
                "reason": f"cannot parse {f}: {type(e).__name__}: {e}"}
    if agg is None:
        why = (f"no host annotation {window!r}" if window and any(
            "/device:" in p["name"] for p in planes)
            else "no device plane with op events (CPU/host trace?)")
        return {"found": False, "source": f, "reason": why}
    busy_ms = agg["busy_ps"] / 1e9
    wall = wall_ms if wall_ms is not None else agg["window_ps"] / 1e9
    n_it = int(iters) if iters else None
    self_ps = sum(v[0] for v in agg["ops"].values())

    def share(ps: float, of: float) -> float:
        return ps / of if of else 0.0

    out: Dict[str, Any] = {
        "found": True,
        "source": f,
        "device_plane": agg["device_plane"],
        "n_devices": agg["n_devices"],
        "window": agg["window"],
        "busy_ms": busy_ms,
        "wall_ms": wall,
        "copy_ms": agg["copy_ps"] / 1e9,
        "copy_share": share(agg["copy_ps"], self_ps),
        "comm_ms": agg["comm_ps"] / 1e9,
        "comm_share": share(agg["comm_ps"], self_ps),
        "ops": [
            {"name": name, "ms": ps / 1e9, "calls": calls,
             "share": share(ps, self_ps), "scope": scope}
            for (name, scope), (ps, calls) in sorted(
                agg["ops"].items(), key=lambda kv: (-kv[1][0], kv[0]))],
        "layers": [
            {"scope": sc, "ms": ps / 1e9,
             "ms_per_iter": (ps / 1e9 / n_it if n_it else None),
             "share": share(ps, agg["busy_ps"]),
             "ops": [n for n, _ps in sorted(
                 agg["layer_ops"][sc].items(), key=lambda kv: -kv[1])[:5]]}
            for sc, ps in sorted(agg["layers"].items(),
                                 key=lambda kv: -kv[1])],
        "idle_gaps": [{"name": n, "ms": ps / 1e9}
                      for n, ps in agg["idle_gaps"]],
        "spans": [{"name": n, "ms": v[0] / 1e9, "count": int(v[1])}
                  for n, v in sorted(agg["spans"].items(),
                                     key=lambda kv: -kv[1][0])],
    }
    if n_it:
        out["iters"] = n_it
        out["wall_busy_gap_ms"] = max(wall - busy_ms, 0.0) / n_it
    return out


def profile_gauges(profile_dir: str, iters: Optional[int] = None,
                   wall_ms: Optional[float] = None) -> Dict[str, Any]:
    """Attribute a finished ``tpu_profile_dir`` dump into the metrics
    registry: ``train.copy_share`` (fraction of the ops' self time
    spent in copy ops), ``train.comm_share`` (fraction spent in
    cross-device collectives), ``train.layer_ms{scope=...}`` (device
    time of each ``lgbm/`` scope and of ``unscoped``, an iteration
    where ``iters`` is known) and — when ``iters`` is known —
    ``train.wall_busy_gap_ms`` (per-iteration wall-vs-busy gap).
    Forced gauges: asking for a
    profiler trace IS opting into its attribution, tpu_metrics or not.
    Never raises — a malformed dump warns and returns the reason; the
    training/bench run that produced it must not fail on telemetry."""
    from ..utils import log
    try:
        res = attribute(profile_dir, iters=iters, wall_ms=wall_ms)
    except Exception as e:   # defense in depth: attribution is telemetry
        res = {"found": False,
               "reason": f"{type(e).__name__}: {e}"}
    if not res.get("found"):
        log.debug(f"trace_attr: nothing to attribute under "
                  f"{profile_dir!r}: {res.get('reason')}")
        return res
    from . import set_gauge
    set_gauge("train.copy_share", float(res["copy_share"]), force=True)
    set_gauge("train.comm_share", float(res["comm_share"]), force=True)
    for layer in res["layers"]:
        ms = layer["ms_per_iter"] if iters else layer["ms"]
        set_gauge("train.layer_ms", float(ms), force=True,
                  scope=layer["scope"])
    if "wall_busy_gap_ms" in res:
        set_gauge("train.wall_busy_gap_ms",
                  float(res["wall_busy_gap_ms"]), force=True)
    return res
