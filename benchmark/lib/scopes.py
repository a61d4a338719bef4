"""The traced window by the program's own names: a second reading of the
same profiler dump that `lib/xplane.py::reduce_trace` reads.

The program wraps its device code in `jax.named_scope("lgbm/<layer>/<phase>")`
and every host span of it is a profiler annotation `lgbm/<span>`. On a TPU
each device op's event metadata carry the op's name path in the `tf_op`
stat. Here
  - each LEAF device op inside the window takes the innermost
    `lgbm/<layer>/<phase>` of its `tf_op`, or `unscoped` where it has none;
    the covered instants are walked in time order, so every instant goes to
    exactly one leaf, and the scopes and `unscoped` add up to `busy_s`;
  - the idle gaps are `reduce_trace`'s, of the same durations, each named by
    the innermost annotation open at its middle among the benchmark's own
    (`bench/`) and the program's (`lgbm/`);
  - `host_idle_s` sums the gaps whose innermost annotation is the program's.

The arithmetic (window, leaves, union) is `reduce_trace`'s; the wire format
is read with its helpers. This reader does not import the program's own
(`lightgbm_tpu/obs/trace_attr.py`): the yardstick is not the program's.
"""
from __future__ import annotations

import re

from lib import xplane

SCOPE_PREFIX = "lgbm/"
UNSCOPED = "unscoped"
_SCOPE = re.compile(r"lgbm/[\w.\-]+/[\w.\-]+")


def scope_of(op_name: str | None) -> str:
    """The innermost `lgbm/<layer>/<phase>` of an op's name path, such as
    `jit(chunk)/while/body/lgbm/engine/goss_sample/jit(sort)/sort`."""
    hits = _SCOPE.findall(op_name or "")
    return hits[-1] if hits else UNSCOPED


def _str_stat(buf: bytes) -> tuple[int, str | None]:
    """XStat -> (stat metadata id, its `str_value` or None)."""
    sid, val = 0, None
    for fnum, _wt, v in xplane._fields(buf):
        if fnum == 1:
            sid = v
        elif fnum == 5:
            val = v.decode("utf-8", "replace")
    return sid, val


def _parse_plane(buf: bytes) -> dict:
    """`xplane._parse_plane`'s plane, and the `tf_op` of each event
    metadata that carries one."""
    out = {"name": "", "lines": [], "event_names": {}, "tf_op": {}}
    stat_names: dict[int, str] = {}
    raw: dict[int, list[bytes]] = {}
    for fnum, _wt, v in xplane._fields(buf):
        if fnum == 2:
            out["name"] = v.decode("utf-8", "replace")
        elif fnum == 3:
            out["lines"].append(xplane._parse_line(v))
        elif fnum == 4:
            key, name, disp, stats = 0, "", "", []
            for f2, _w2, v2 in xplane._fields(v):
                if f2 == 1:
                    key = v2
                elif f2 == 2:
                    for f3, _w3, v3 in xplane._fields(v2):
                        if f3 == 1:
                            key = key or v3
                        elif f3 == 2:
                            name = v3.decode("utf-8", "replace")
                        elif f3 == 4:
                            disp = v3.decode("utf-8", "replace")
                        elif f3 == 5:
                            stats.append(v3)
            out["event_names"][key] = name or disp
            if stats:
                raw[key] = stats
        elif fnum == 5:
            key, name = 0, ""
            for f2, _w2, v2 in xplane._fields(v):
                if f2 == 1:
                    key = v2
                elif f2 == 2:
                    for f3, _w3, v3 in xplane._fields(v2):
                        if f3 == 1:
                            key = key or v3
                        elif f3 == 2:
                            name = v3.decode("utf-8", "replace")
            stat_names[key] = name
    for mid, stats in raw.items():
        for buf2 in stats:
            sid, val = _str_stat(buf2)
            if val is not None and stat_names.get(sid) == "tf_op":
                out["tf_op"][mid] = val
    return out


def _leaves(events: list[tuple[int, int, int]]) -> list[tuple[int, int, int]]:
    """The leaf events of one device's (metadata id, start, end) events,
    which nest and do not cross: `xplane.self_times`'s rule, keeping each
    leaf's id."""
    leaves: list[tuple[int, int, int]] = []
    stack: list[list] = []          # [key, start, end, is_leaf]
    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            k, s0, e0, leaf = stack.pop()
            if leaf:
                leaves.append((k, s0, e0))
        if stack:
            stack[-1][3] = False
        stack.append([key, s, e, True])
    leaves += [(k, s0, e0) for k, s0, e0, leaf in stack if leaf]
    return leaves


def reduce_scopes(path: str, window_annotation: str,
                  prefixes: tuple[str, ...] = ("bench/", SCOPE_PREFIX)
                  ) -> dict | None:
    """The newest dump under `path` by scope, or None where
    `reduce_trace` gives None (no device plane with ops, or no window).

    Keys: scopes ({scope or "unscoped": seconds}, meaned over the device
    planes as `busy_s` is; they add up to it), scope_ops ({scope: [[op,
    seconds], ...]}, its five largest leaves by covered time), busy_s,
    window_s, idle_gaps ([annotation or "(none)", seconds], longest first,
    first plane), host_idle_s.
    """
    f = xplane.newest_xplane(path)
    if f is None:
        return None
    with open(f, "rb") as fh:
        planes = [_parse_plane(v) for fnum, _wt, v
                  in xplane._fields(fh.read()) if fnum == 1]
    dev = xplane._device_planes(planes)
    notes = xplane._host_annotations(planes, prefixes)
    spans = [(s, e) for n, s, e in notes if n == window_annotation]
    if not dev or not spans:
        return None
    t0, t1 = max(spans, key=lambda se: se[1] - se[0])
    busy = 0.0
    by_scope: dict[str, float] = {}
    by_op: dict[str, dict[str, float]] = {}
    gaps: list[tuple[str, float]] = []
    for idx, (plane, lines) in enumerate(dev):
        events = []
        for ln in lines:
            base = ln["timestamp_ns"] * 1000
            for mid, off, dur in ln["events"]:
                events.append((mid, base + off, base + off + dur))
        cursor, cover = t0, []
        for mid, s, e in sorted(_leaves(events), key=lambda ev: ev[1]):
            a, b = max(s, cursor), min(e, t1)
            if b <= a:
                continue
            sc = scope_of(plane["tf_op"].get(mid))
            by_scope[sc] = by_scope.get(sc, 0.0) + (b - a)
            op = xplane.short_name(plane["event_names"].get(mid, f"op#{mid}"))
            ops = by_op.setdefault(sc, {})
            ops[op] = ops.get(op, 0.0) + (b - a)
            cover.append((a, b))
            cursor = b
        cover = xplane.union(cover)
        busy += sum(e - s for s, e in cover) / 1e12
        if idx == 0:
            edges = [(t0, t0)] + cover + [(t1, t1)]
            for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
                if s1 <= e0:
                    continue
                mid = (e0 + s1) // 2
                inner = max(((s, n) for n, s, e in notes if s <= mid < e),
                            default=None)
                gaps.append((inner[1] if inner else "(none)",
                             (s1 - e0) / 1e12))
    k = len(dev)
    gaps.sort(key=lambda r: -r[1])
    return {
        "scopes": {sc: ps / 1e12 / k for sc, ps in by_scope.items()},
        "scope_ops": {sc: [[n, ps / 1e12 / k] for n, ps in sorted(
            ops.items(), key=lambda kv: -kv[1])[:5]]
            for sc, ops in by_op.items()},
        "busy_s": busy / k,
        "window_s": (t1 - t0) / 1e12,
        "idle_gaps": gaps,
        "host_idle_s": sum(s for n, s in gaps
                           if n.startswith(SCOPE_PREFIX)),
    }


def scope_ms(ctx: dict, *names: str) -> float | None:
    """Device ms an iteration of the traced window under the named scopes
    (`lgbm/` left out), or None where the trace was not read by scope or
    holds none of them (a program without those scopes)."""
    red = (ctx.get("trace") or {}).get("scopes")
    if not red or not ctx.get("iters_traced"):
        return None
    hit = [red["scopes"][SCOPE_PREFIX + n] for n in names
           if SCOPE_PREFIX + n in red["scopes"]]
    if not hit:
        return None
    return sum(hit) * 1e3 / ctx["iters_traced"]
