"""Reduction from a profiler dump (`*.xplane.pb`) to device times.

The wire-format reader is a copy of the one in the program's
`lightgbm_tpu/obs/trace_attr.py` (stdlib only; the TensorBoard converter
cannot be imported here). What differs is the arithmetic: the program's
reader sums event durations, which counts a `while` and the ops inside
it twice. Here
  - the window is the benchmark's own host annotation round the traced
    calls (dispatch to `block_until_ready`), so the host's work between
    two chunks lies inside it,
  - busy is the UNION of the device's LEAF op intervals (an event that
    encloses another, as the `while` of a scan does, is no work of its
    own), cut to the window, so busy <= window,
  - an op's time is its SELF time: its duration less the part its
    children cover, so a kernel (always a leaf) keeps its whole duration,
  - idle gaps are the complement of the union inside the window, each
    named by the benchmark's host annotation that was open at its middle.
"""
from __future__ import annotations

import os
from typing import Any, Iterator


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = s = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if not b & 0x80:
            return x, i
        s += 7


def _fields(buf: bytes) -> Iterator[tuple[int, int, Any]]:
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fnum, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt} at byte {i}")
        yield fnum, wt, v


def _parse_event(buf: bytes) -> tuple[int, int, int]:
    mid = off = dur = 0
    for fnum, _wt, v in _fields(buf):
        if fnum == 1:
            mid = v
        elif fnum == 2:
            off = v
        elif fnum == 3:
            dur = v
    return mid, off, dur


def _parse_line(buf: bytes) -> dict:
    out = {"name": "", "timestamp_ns": 0, "events": []}
    for fnum, _wt, v in _fields(buf):
        if fnum == 2:
            out["name"] = v.decode("utf-8", "replace")
        elif fnum == 11 and not out["name"]:
            out["name"] = v.decode("utf-8", "replace")
        elif fnum == 3:
            out["timestamp_ns"] = v
        elif fnum == 4:
            out["events"].append(_parse_event(v))
    return out


def _parse_plane(buf: bytes) -> dict:
    out = {"name": "", "lines": [], "event_names": {}}
    for fnum, _wt, v in _fields(buf):
        if fnum == 2:
            out["name"] = v.decode("utf-8", "replace")
        elif fnum == 3:
            out["lines"].append(_parse_line(v))
        elif fnum == 4:
            key, name, disp = 0, "", ""
            for f2, _w2, v2 in _fields(v):
                if f2 == 1:
                    key = v2
                elif f2 == 2:
                    for f3, _w3, v3 in _fields(v2):
                        if f3 == 1:
                            key = key or v3
                        elif f3 == 2:
                            name = v3.decode("utf-8", "replace")
                        elif f3 == 4:
                            disp = v3.decode("utf-8", "replace")
            out["event_names"][key] = name or disp
    return out


def parse_xspace(data: bytes) -> list[dict]:
    return [_parse_plane(v) for fnum, _wt, v in _fields(data) if fnum == 1]


def newest_xplane(path: str) -> str | None:
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


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------
def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Disjoint sorted cover of a list of [start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: list[tuple[str, int, int]]
               ) -> tuple[dict[str, list[float]], list[tuple[int, int]]]:
    """(name -> [self picoseconds, calls], the leaf events' intervals)
    over one line's (name, start, end) events, which nest (a `while`
    encloses its body) and do not cross. A leaf encloses no other event."""
    out: dict[str, list[float]] = {}
    leaves: list[tuple[int, int]] = []
    stack: list[list] = []        # [name, start, end, self_ps, is_leaf]

    def close(upto: int) -> None:
        while stack and stack[-1][2] <= upto:
            name, s, e, self_ps, is_leaf = stack.pop()
            ent = out.setdefault(name, [0.0, 0])
            ent[0] += max(self_ps, 0)
            ent[1] += 1
            if is_leaf:
                leaves.append((s, e))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            # a child takes its span out of the parent's self time
            stack[-1][3] -= min(e, stack[-1][2]) - s
            stack[-1][4] = False
        stack.append([name, s, e, e - s, True])
    close(1 << 62)
    return out, leaves


def short_name(name: str) -> str:
    """The op's own name out of the profiler's event name, which on a TPU
    is the whole HLO line: "%fusion.3 = f32[...] fusion(...)" -> "fusion.3"."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def _device_planes(planes: list[dict]) -> list[tuple[dict, list[dict]]]:
    found = []
    for plane in planes:
        if "/device:" not in plane["name"]:
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == "XLA Ops"]
        if lines and any(ln["events"] for ln in lines):
            found.append((plane, lines))
    return found


def _host_annotations(planes: list[dict], prefix: str
                      ) -> list[tuple[str, int, int]]:
    out = []
    for plane in planes:
        if "/device:" in plane["name"]:
            continue
        for ln in plane["lines"]:
            base = ln["timestamp_ns"] * 1000
            for mid, off, dur in ln["events"]:
                name = plane["event_names"].get(mid, "")
                if name.startswith(prefix):
                    out.append((name, base + off, base + off + dur))
    return out


def reduce_trace(path: str, window_annotation: str,
                 annotation_prefix: str = "bench/") -> dict | None:
    """Device times of the newest dump under `path`, or None where no
    device plane holds an op (a CPU trace has host threads only) or the
    host annotation `window_annotation` is not in the dump.

    Keys: window_s (the annotation's length), busy_s (mean over the device
    planes of the union of leaf op intervals inside the window), ops
    ([name, seconds, calls] by self time, summed over planes and divided
    by their number), idle_gaps ([host annotation or "(none)", seconds],
    longest first, first plane; the stretches before the first op and
    after the last are gaps too), n_devices.
    """
    f = newest_xplane(path)
    if f is None:
        return None
    with open(f, "rb") as fh:
        planes = parse_xspace(fh.read())
    dev = _device_planes(planes)
    notes = _host_annotations(planes, annotation_prefix)
    spans = [(s, e) for n, s, e in notes if n == window_annotation]
    if not dev or not spans:
        return None
    t0, t1 = max(spans, key=lambda se: se[1] - se[0])
    busy = 0.0
    ops: dict[str, list[float]] = {}
    gaps: list[tuple[str, float]] = []
    for idx, (plane, lines) in enumerate(dev):
        events = []
        for ln in lines:
            base = ln["timestamp_ns"] * 1000
            for mid, off, dur in ln["events"]:
                events.append((short_name(plane["event_names"].get(
                    mid, f"op#{mid}")),
                               base + off, base + off + dur))
        times, leaves = self_times(events)
        cover = union([(max(s, t0), min(e, t1)) for s, e in leaves
                       if e > t0 and s < t1])
        busy += sum(e - s for s, e in cover) / 1e12
        for name, (ps, calls) in times.items():
            ent = ops.setdefault(name, [0.0, 0])
            ent[0] += ps / 1e12
            ent[1] += calls
        if idx == 0:
            edges = [(t0, t0)] + cover + [(t1, t1)]
            for (_s0, e0), (s1, _e1) in zip(edges, edges[1:]):
                if s1 <= e0:
                    continue
                mid = (e0 + s1) // 2
                # the innermost (latest-started) annotation names the gap
                inner = max(((s, n) for n, s, e in notes if s <= mid < e),
                            default=None)
                name = inner[1] if inner else "(none)"
                gaps.append((name, (s1 - e0) / 1e12))
    k = len(dev)
    return {
        "busy_s": busy / k,
        "window_s": (t1 - t0) / 1e12,
        "n_devices": k,
        "ops": sorted(([n, v[0] / k, int(v[1])] for n, v in ops.items()),
                      key=lambda r: -r[1]),
        "idle_gaps": sorted(gaps, key=lambda r: -r[1]),
    }
