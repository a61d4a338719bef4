#!/usr/bin/env python
"""Perf-regression sentinel over scripts/check_timings.log obs lines.

scripts/check.sh appends one machine-readable ``obs {...}`` JSON line
per run (dots, seconds, bench iters/sec, compile requests, peak-HBM).
This sentinel turns that log from a thing a reviewer *may* eyeball into
a gate: compare the NEWEST run against the trailing median of the
previous runs (same mode) and exit non-zero when a watched signal
regressed past its threshold —

- ``bench_iters_per_sec`` DOWN by more than ``--max-ips-drop``
  (default 15%: a 20% regression must fail, run-to-run noise must
  not);
- ``compile_requests`` UP by more than ``--max-compile-up`` (fraction)
  plus ``--compile-slack`` absolute requests (cold-cache runs jitter
  by a couple);
- ``peak_hbm_gib`` UP by more than ``--max-hbm-up``;
- ``copy_share`` (fraction of device busy in loop-state ``%copy`` ops,
  the signal the ``tpu_donate`` pass squeezes — docs/perf.md
  "Iteration floor") UP by more than ``--max-copy-up`` (fraction)
  plus ``--copy-slack`` absolute (the share sits near zero once
  donation lands; a pure ratio would flag noise);
- ``wall_busy_gap_ms`` (the per-iteration wall-vs-device-busy gap from
  trace attribution — the dispatch/collective stall residue the
  ``tpu_stream_overlap`` pipeline hides; docs/perf.md "Communication/
  compute overlap") UP by more than ``--max-gap-up`` (fraction) plus
  ``--gap-slack-ms`` absolute — the copy_share guard's shape: the gap
  sits near zero once overlap lands, so a pure ratio would flag timer
  noise while a pure absolute would miss a doubling;
- ``queue_wait_p99_ms`` (the serving smoke's windowed queue-wait p99,
  docs/observability.md "Request tracing") UP by more than
  ``--max-qw-up`` (fraction) plus ``--qw-slack-ms`` absolute — the
  same near-zero-slack shape as the copy_share guard: the p99 sits
  near the micro-batch budget, so a pure ratio would flag timer
  jitter while a pure absolute would miss a doubling;
- ``secs`` (suite wall clock) UP by more than ``--max-secs-up`` at a
  non-lower dot count (fewer dots = different suite, not a slowdown);
- ``stream_dryrun`` == 0 in the NEWEST run (absolute, no baseline
  needed): the streamed-sharded dryrun check.sh runs diverged from
  single-shard streaming or crashed;
- ``chaos_smoke`` == 0 in the NEWEST run (absolute, like
  stream_dryrun): the kill + resume + hot-swap chaos smoke check.sh
  runs lost bit-equality, dropped a request, or crashed;
- ``elastic_smoke`` == 0 in the NEWEST run (absolute, like
  chaos_smoke): the elastic resize cycle riding the same smoke
  (kill -> resume the gang NARROWER -> topology re-cut;
  docs/robustness.md "Elastic topology") lost bit-equality with the
  uninterrupted full-width run, dropped a predict, or crashed;
- ``serve_smoke`` == 0 in the NEWEST run (absolute, like chaos_smoke):
  the concurrent serving smoke (``benchmarks/serve_bench.py --smoke``
  — coalesce + LRU-evict + mid-traffic hot-swap under load) dropped a
  request, compiled a warm-path program, or crashed;
- ``shap_smoke`` == 0 in the NEWEST run (absolute, like serve_smoke):
  the mixed predict+explain leg of the same smoke (device SHAP
  through the service's ``(model, kind)`` lanes; docs/serving.md
  "Mixed predict + explain workloads") dropped a request, compiled a
  warm-path program, or served wrong contributions;
- ``fleet_smoke`` == 0 in the NEWEST run (absolute, like
  elastic_smoke): the serving-fleet kill/join cycle riding the chaos
  smoke (3 replicas behind the router, one SIGKILLed mid-load →
  relaunch + degrade; docs/serving.md "Fleet deployment") dropped a
  request, admitted traffic at an unready replica, or crashed;
- ``lint_findings`` != 0 in the NEWEST run (absolute): the static
  analysis suite (``python -m tools.analyze``;
  docs/static-analysis.md) reported drift findings — or crashed
  (recorded as -1). A drifted gate literal / raw knob read /
  branch-wrapped collective is broken NOW, whatever the history says.

No (or not enough) history exits 0 — the first run after a wipe stays
green. A signal missing from either side of the comparison is skipped
(benches evolve), and malformed obs lines are warned about and
skipped, never crash the gate.

A FAILING run writes a ``trend-reject {...}`` marker (keyed on the
entry's ts/rev/mode) back into the log, and rejected entries are
excluded from every later baseline — re-running the gate against a
persistent regression cannot launder the regressed numbers into the
trailing median it is compared against.

Usage (scripts/check.sh runs it behind CHECK_TREND=1):
    python scripts/obs_trend.py [--log scripts/check_timings.log]
        [--window 5] [--max-ips-drop 0.15] [--max-compile-up 0.5]
        [--compile-slack 2] [--max-hbm-up 0.2] [--max-secs-up 0.35]
        [--max-copy-up 0.5] [--copy-slack 0.005]
        [--max-gap-up 0.5] [--gap-slack-ms 3.0]
        [--max-qw-up 0.5] [--qw-slack-ms 2.0]
Exit codes: 0 = no regression (or no history), 1 = regression, 2 = bad
invocation (unreadable log path given explicitly).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from typing import Any, Dict, List, Optional

DEFAULT_LOG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "check_timings.log")


def _entry_key(entry: Dict[str, Any]) -> tuple:
    return (entry.get("ts"), entry.get("rev"), entry.get("mode"))


def parse_obs_lines(text: str) -> List[Dict[str, Any]]:
    """All well-formed ``obs {...}`` entries, oldest first, minus
    entries covered by a ``trend-reject`` marker (a previous sentinel
    failure — they must not become baseline). Malformed entries warn
    to stderr and are skipped."""
    # markers are APPENDED after the entries they reject, so collect
    # them in a first pass before flagging entries
    rejected = set()
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("trend-reject "):
            try:
                rejected.add(_entry_key(
                    json.loads(line[len("trend-reject "):])))
            except ValueError:
                pass
    out: List[Dict[str, Any]] = []
    for i, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line.startswith("obs "):
            continue
        try:
            entry = json.loads(line[len("obs "):])
            if not isinstance(entry, dict):
                raise ValueError("not a JSON object")
        except ValueError as e:
            sys.stderr.write(f"obs_trend: skipping malformed obs line "
                             f"{i} ({e})\n")
            continue
        entry["_rejected"] = _entry_key(entry) in rejected
        out.append(entry)
    return out


def _num(entry: Dict[str, Any], key: str) -> Optional[float]:
    v = entry.get(key)
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def _median_of(history: List[Dict[str, Any]],
               key: str) -> Optional[float]:
    vals = [v for v in (_num(e, key) for e in history) if v is not None]
    return statistics.median(vals) if vals else None


def check_trend(entries: List[Dict[str, Any]], window: int,
                max_ips_drop: float, max_compile_up: float,
                compile_slack: float, max_hbm_up: float,
                max_secs_up: float, max_copy_up: float = 0.5,
                copy_slack: float = 0.005, max_qw_up: float = 0.5,
                qw_slack_ms: float = 2.0, max_gap_up: float = 0.5,
                gap_slack_ms: float = 3.0) -> List[str]:
    """Regression messages for the newest entry vs the trailing median
    of up to ``window`` earlier same-mode entries; [] = green."""
    if not entries:
        return []
    newest = entries[-1]
    failures: List[str] = []
    # the streamed-sharded dryrun pin needs no baseline: a 0 in the
    # newest run means sharded streaming diverged from single-shard
    # (or crashed) — an absolute failure, not a trend
    if _num(newest, "stream_dryrun") == 0.0:
        failures.append(
            "streamed-sharded dryrun FAILED (stream_dryrun=0): the "
            "2-device streaming case diverged from single-shard "
            "streaming or crashed")
    # the chaos-smoke pin is absolute for the same reason: a resume
    # that lost bit-equality or a hot-swap that dropped/corrupted a
    # request is broken NOW, whatever the trailing median says
    if _num(newest, "chaos_smoke") == 0.0:
        failures.append(
            "chaos smoke FAILED (chaos_smoke=0): kill + resume + "
            "hot-swap lost bit-equality or crashed "
            "(benchmarks/chaos_bench.py --smoke)")
    # elastic resume is absolute too: a resize cycle that resumed the
    # gang narrower and lost bit-equality (or dropped a predict) is a
    # broken topology re-cut NOW, whatever the trailing median says
    if _num(newest, "elastic_smoke") == 0.0:
        failures.append(
            "elastic smoke FAILED (elastic_smoke=0): the resize cycle "
            "(kill -> resume narrower -> topology re-cut) lost "
            "bit-equality, dropped a predict, or crashed "
            "(benchmarks/chaos_bench.py --smoke; docs/robustness.md "
            "'Elastic topology')")
    # the fleet smoke is absolute like the elastic one: a replica kill
    # that dropped a request, or traffic routed at a replica that
    # never passed /readyz, is a broken failover NOW
    if _num(newest, "fleet_smoke") == 0.0:
        failures.append(
            "fleet smoke FAILED (fleet_smoke=0): the serving-fleet "
            "kill/join cycle (3 replicas, kill one mid-load -> "
            "relaunch + degrade) dropped a request or crashed "
            "(benchmarks/chaos_bench.py --smoke; docs/serving.md "
            "'Fleet deployment')")
    # the serving smoke is absolute the same way: a dropped request or
    # a warm-path compile under coalesce + evict + swap load is broken
    # NOW, whatever the trailing median says
    if _num(newest, "serve_smoke") == 0.0:
        failures.append(
            "serving smoke FAILED (serve_smoke=0): concurrent "
            "coalesce + LRU-evict + mid-traffic-swap load dropped a "
            "request, compiled a warm-path program, or crashed "
            "(benchmarks/serve_bench.py --smoke)")
    # the explain leg of the same smoke is absolute too: a warm SHAP
    # dispatch that compiles, a mixed-lane drop, or served
    # contributions diverging from the published model is broken NOW
    if _num(newest, "shap_smoke") == 0.0:
        failures.append(
            "mixed predict+explain smoke FAILED (shap_smoke=0): the "
            "device-SHAP serving leg dropped a request, compiled a "
            "warm-path program, or served wrong contributions "
            "(benchmarks/serve_bench.py --smoke; docs/serving.md "
            "'Mixed predict + explain workloads')")
    # static analysis is absolute the same way: findings are drift
    # bugs NOW (gate literal outside the capability table, raw knob
    # read, collective inside a lax.switch branch...), and -1 means
    # the analyzer itself crashed
    lint = _num(newest, "lint_findings")
    if lint is not None and lint != 0.0:
        failures.append(
            f"static analysis FAILED (lint_findings={lint:g}): "
            f"run `python -m tools.analyze` and fix (or explicitly "
            f"allowlist) every finding — docs/static-analysis.md")
    mode = newest.get("mode")
    # rejected entries (previous sentinel failures) never become
    # baseline — a persistent regression re-run N times must keep
    # failing against the last GREEN history, not against itself
    history = [e for e in entries[:-1]
               if e.get("mode") == mode and not e.get("_rejected")]
    history = history[-window:]
    if not history:
        # first run (or first in this mode): no trend baseline — only
        # the absolute checks above apply
        return failures

    ips_now = _num(newest, "bench_iters_per_sec")
    ips_med = _median_of(history, "bench_iters_per_sec")
    if ips_now is not None and ips_med:
        floor = ips_med * (1.0 - max_ips_drop)
        if ips_now < floor:
            failures.append(
                f"bench_iters_per_sec regressed: {ips_now:.3g} < "
                f"{floor:.3g} (trailing median {ips_med:.3g} over "
                f"{len(history)} run(s), -{max_ips_drop:.0%} allowed)")

    comp_now = _num(newest, "compile_requests")
    comp_med = _median_of(history, "compile_requests")
    if comp_now is not None and comp_med is not None:
        ceil = comp_med * (1.0 + max_compile_up) + compile_slack
        if comp_now > ceil:
            failures.append(
                f"compile_requests regressed: {comp_now:g} > {ceil:g} "
                f"(trailing median {comp_med:g}; a compile-count jump "
                f"is a warm-path recompile leak)")

    cs_now = _num(newest, "copy_share")
    cs_med = _median_of(history, "copy_share")
    if cs_now is not None and cs_med is not None:
        ceil = cs_med * (1.0 + max_copy_up) + copy_slack
        if cs_now > ceil:
            failures.append(
                f"copy_share regressed: {cs_now:.4f} > {ceil:.4f} "
                f"(trailing median {cs_med:.4f} over {len(history)} "
                f"run(s)): loop-state %copy crept back — a donation "
                f"gate dropped a carry (docs/perf.md 'Iteration "
                f"floor')")

    gap_now = _num(newest, "wall_busy_gap_ms")
    gap_med = _median_of(history, "wall_busy_gap_ms")
    if gap_now is not None and gap_med is not None:
        ceil = gap_med * (1.0 + max_gap_up) + gap_slack_ms
        if gap_now > ceil:
            failures.append(
                f"wall_busy_gap_ms regressed: {gap_now:.3g} > "
                f"{ceil:.3g} (trailing median {gap_med:.3g} over "
                f"{len(history)} run(s)): the per-iter wall-vs-busy "
                f"gap crept back — a host sync snuck into the "
                f"overlapped stream path (docs/perf.md "
                f"'Communication/compute overlap')")

    qw_now = _num(newest, "queue_wait_p99_ms")
    qw_med = _median_of(history, "queue_wait_p99_ms")
    if qw_now is not None and qw_med is not None:
        ceil = qw_med * (1.0 + max_qw_up) + qw_slack_ms
        if qw_now > ceil:
            failures.append(
                f"queue_wait_p99_ms regressed: {qw_now:.3g} > "
                f"{ceil:.3g} (trailing median {qw_med:.3g} over "
                f"{len(history)} run(s)): serving queue pressure "
                f"crept up — budget misconfig, dispatch slowdown, or "
                f"LRU thrash (docs/observability.md 'Request "
                f"tracing')")

    hbm_now = _num(newest, "peak_hbm_gib")
    hbm_med = _median_of(history, "peak_hbm_gib")
    if hbm_now is not None and hbm_med:
        ceil = hbm_med * (1.0 + max_hbm_up)
        if hbm_now > ceil:
            failures.append(
                f"peak_hbm_gib regressed: {hbm_now:.3g} > {ceil:.3g} "
                f"(trailing median {hbm_med:.3g})")

    secs_now = _num(newest, "secs")
    secs_med = _median_of(history, "secs")
    dots_now = _num(newest, "dots")
    dots_med = _median_of(history, "dots")
    if (secs_now is not None and secs_med
            and dots_now is not None and dots_med is not None
            and dots_now >= dots_med):
        ceil = secs_med * (1.0 + max_secs_up)
        if secs_now > ceil:
            failures.append(
                f"suite wall clock regressed: {secs_now:g}s > "
                f"{ceil:.0f}s (trailing median {secs_med:g}s at "
                f"dots>={dots_med:g})")
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="perf-regression sentinel over check_timings.log "
                    "obs lines (see module docstring)")
    ap.add_argument("--log", default=DEFAULT_LOG)
    ap.add_argument("--window", type=int, default=5,
                    help="trailing same-mode runs the median is over")
    ap.add_argument("--max-ips-drop", type=float, default=0.15)
    ap.add_argument("--max-compile-up", type=float, default=0.5)
    ap.add_argument("--compile-slack", type=float, default=2.0)
    ap.add_argument("--max-hbm-up", type=float, default=0.2)
    ap.add_argument("--max-secs-up", type=float, default=0.35)
    ap.add_argument("--max-copy-up", type=float, default=0.5)
    ap.add_argument("--copy-slack", type=float, default=0.005,
                    help="absolute copy_share headroom on top of the "
                         "ratio (the share sits near zero once "
                         "donation lands)")
    ap.add_argument("--max-gap-up", type=float, default=0.5)
    ap.add_argument("--gap-slack-ms", type=float, default=3.0,
                    help="absolute wall_busy_gap_ms headroom on top "
                         "of the ratio (the gap sits near zero once "
                         "overlap lands; pure ratios would flag "
                         "host-timer noise)")
    ap.add_argument("--max-qw-up", type=float, default=0.5)
    ap.add_argument("--qw-slack-ms", type=float, default=2.0,
                    help="absolute queue_wait_p99_ms headroom on top "
                         "of the ratio (the p99 sits near the "
                         "micro-batch budget; pure ratios would flag "
                         "timer jitter)")
    args = ap.parse_args(argv)

    try:
        with open(args.log) as f:
            text = f.read()
    except OSError as e:
        if args.log != DEFAULT_LOG:
            sys.stderr.write(f"obs_trend: cannot read {args.log}: "
                             f"{e}\n")
            return 2
        print("obs_trend: no timings log yet; nothing to compare")
        return 0

    entries = parse_obs_lines(text)
    if not entries:
        print(f"obs_trend: no obs lines in {args.log}; nothing to "
              f"compare")
        return 0
    # a single entry has no trend baseline, but the absolute checks
    # (the stream_dryrun pin) still apply to it
    failures = check_trend(entries, args.window, args.max_ips_drop,
                           args.max_compile_up, args.compile_slack,
                           args.max_hbm_up, args.max_secs_up,
                           args.max_copy_up, args.copy_slack,
                           args.max_qw_up, args.qw_slack_ms,
                           args.max_gap_up, args.gap_slack_ms)
    if failures:
        for msg in failures:
            print(f"obs_trend: REGRESSION — {msg}")
        print(f"obs_trend: newest run vs trailing median FAILED "
              f"({len(failures)} signal(s)); see {args.log}")
        # mark the failed entry so re-runs cannot launder it into the
        # baseline (best-effort: a read-only log still fails the gate)
        newest = entries[-1]
        if not newest.get("_rejected"):
            try:
                with open(args.log, "a") as f:
                    f.write("trend-reject " + json.dumps(
                        {"ts": newest.get("ts"),
                         "rev": newest.get("rev"),
                         "mode": newest.get("mode")}) + "\n")
            except OSError as e:
                sys.stderr.write(f"obs_trend: cannot write reject "
                                 f"marker: {e}\n")
        return 1
    print("obs_trend: newest run within thresholds of the trailing "
          "median — OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
