#!/usr/bin/env bash
# Pre-snapshot gate (VERDICT r4 item 1): a <2-minute smoke that MUST be
# green before any end-of-round snapshot or milestone commit.
#
#   bash scripts/check.sh          # smoke tests + tiny bench
#   bash scripts/check.sh --full   # full suite instead of the smoke set
#
# Rationale: round 4's final commit shipped an undefined variable in
# GBDT.predict() that failed 111/249 tests and blanked BENCH_r04. This
# script is the discipline that prevents a recurrence.
#
# Wall-clock guard: every run appends "date git-rev mode dots seconds"
# to scripts/check_timings.log (also summarized in the verify skill,
# .claude/skills/verify/SKILL.md). A suite that suddenly takes longer
# at the same dot count is a perf regression in the library the tests
# exercise (e.g. an ingest slowdown taxing every construct) — review
# the log's trend, not just the green.
set -euo pipefail
cd "$(dirname "$0")/.."

LOG=/tmp/_check_run.log
MODE=smoke
RC=0
T0=$(date +%s)
if [[ "${1:-}" == "--full" ]]; then
  MODE=full
  python -m pytest tests/ -x -q 2>&1 | tee "$LOG" || RC=$?
else
  python -m pytest tests/test_smoke_gate.py tests/test_engine.py \
    tests/test_ingest.py -x -q 2>&1 | tee "$LOG" || RC=$?
fi
T1=$(date +%s)
# log EVERY run, green or red — a failing/slow run is exactly the
# datapoint the trend review needs
DOTS=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c || true)
REV=$(git rev-parse --short HEAD 2>/dev/null || echo nogit)
printf '%s %s %s dots=%s secs=%s rc=%s\n' \
  "$(date -u +%Y-%m-%dT%H:%M:%SZ)" "$REV" "$MODE" "$DOTS" "$((T1 - T0))" \
  "$RC" >> scripts/check_timings.log
if [[ "$RC" != 0 ]]; then
  echo "check.sh: tests FAILED (rc=$RC; timing logged)"
  exit "$RC"
fi

# tiny bench: exercises the real flagship path end to end (train +
# predict + AUC) and proves bench.py emits its JSON line with rc=0.
# --metrics-json doubles as the obs-subsystem gate: the run must
# produce a well-formed metrics snapshot (docs/observability.md)
OBS_JSON=/tmp/_check_obs_metrics.jsonl
rm -f "$OBS_JSON"
python bench.py --rows 300000 --iters 5 --smoke --metrics-json "$OBS_JSON"

# streamed x sharded dryrun (docs/perf.md "Streamed x sharded"): the
# 2-device streaming case must stay BIT-EQUAL to single-shard
# streaming with one collective per level; its status rides the obs
# line below so scripts/obs_trend.py watches it run-over-run
STREAM_DRYRUN=1
XLA_FLAGS="--xla_force_host_platform_device_count=2 ${XLA_FLAGS:-}" \
JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}" \
python -c "import __graft_entry__ as g; g.dryrun_multichip(2, only=('streaming',))" \
  || STREAM_DRYRUN=0

# chaos smoke (docs/robustness.md "Chaos harness"): kill + resume +
# hot-swap in one process — streamed resume must stay BIT-EQUAL to the
# uninterrupted run, the swap must compile nothing, and a corrupted
# publish must degrade gracefully; its status rides the obs line so
# scripts/obs_trend.py fails absolutely on chaos_smoke=0. The smoke
# also runs the ELASTIC RESIZE cycle (kill -> resume narrower ->
# verify bit-equality + zero dropped predicts; docs/robustness.md
# "Elastic topology") and reports it as elastic_smoke in its final
# JSON record — parsed below onto the obs line, enforced absolutely
# by obs_trend.py and by exit 8 here
CHAOS_SMOKE=1
CHAOS_JSON=/tmp/_check_chaos_smoke.log
rm -f "$CHAOS_JSON"
JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}" \
python benchmarks/chaos_bench.py --smoke 2>&1 | tee "$CHAOS_JSON" \
  || CHAOS_SMOKE=0
ELASTIC_SMOKE=$(python - "$CHAOS_JSON" elastic_smoke <<'PY'
import json, sys
v = 0
try:
    for ln in open(sys.argv[1]):
        ln = ln.strip()
        if ln.startswith("{"):
            d = json.loads(ln)
            if sys.argv[2] in d:
                v = int(d[sys.argv[2]])
except Exception:
    v = 0
print(v)
PY
)
# serving-fleet kill/join cycle riding the same smoke (3 replicas,
# kill one mid-load, relaunch + degrade, ZERO dropped requests;
# docs/serving.md "Fleet deployment") — enforced absolutely by
# obs_trend.py and by exit 9 here
FLEET_SMOKE=$(python - "$CHAOS_JSON" fleet_smoke <<'PY'
import json, sys
v = 0
try:
    for ln in open(sys.argv[1]):
        ln = ln.strip()
        if ln.startswith("{"):
            d = json.loads(ln)
            if sys.argv[2] in d:
                v = int(d[sys.argv[2]])
except Exception:
    v = 0
print(v)
PY
)

# serving smoke (docs/serving.md): N concurrent clients through the
# micro-batching service with a 1-model LRU and a mid-traffic hot-swap
# — zero dropped requests, zero warm-path compiles, tracing overhead
# under 3%, stage decomposition summing to end-to-end; its status
# rides the obs line so scripts/obs_trend.py fails absolutely on
# serve_smoke=0, and its windowed queue-wait p99 rides along as
# queue_wait_p99_ms= so the sentinel catches queue-pressure creep
SERVE_SMOKE=1
SERVE_JSON=/tmp/_check_serve_smoke.log
rm -f "$SERVE_JSON"
JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.jax_cache}" \
python benchmarks/serve_bench.py --smoke 2>&1 | tee "$SERVE_JSON" \
  || SERVE_SMOKE=0
# mixed predict+explain leg riding the same smoke (device SHAP through
# the service: contrib warmup, half-explain load, zero drops + zero
# warm compiles; docs/serving.md "Mixed predict + explain workloads")
# — enforced absolutely by obs_trend.py and by exit 10 here
SHAP_SMOKE=$(python - "$SERVE_JSON" shap_smoke <<'PY'
import json, sys
v = 0
try:
    for ln in open(sys.argv[1]):
        ln = ln.strip()
        if ln.startswith("{"):
            d = json.loads(ln)
            if sys.argv[2] in d:
                v = int(d[sys.argv[2]])
except Exception:
    v = 0
print(v)
PY
)

# static analysis (docs/static-analysis.md): the five drift linters —
# capability-gate / config-knobs / obs-names / collective-safety /
# lock-discipline — must report ZERO findings. The count rides the obs
# line (lint_findings=) so scripts/obs_trend.py fails absolutely on
# lint_findings>0, and a non-zero count exits 6 below. A crash of the
# analyzer itself (no count file) records -1 — also a failure.
LINT_COUNT_FILE=/tmp/_check_lint_count
rm -f "$LINT_COUNT_FILE"
python -m tools.analyze --emit-count "$LINT_COUNT_FILE" || true
LINT_FINDINGS=$(cat "$LINT_COUNT_FILE" 2>/dev/null || echo -1)

# machine-readable obs line appended next to the plain timing line:
# dots/seconds from this run plus compile count and peak-HBM estimate
# read back from the snapshot. A malformed dump FAILS the gate — a
# check that silently skips its own telemetry is how telemetry rots.
python - "$OBS_JSON" "$MODE" "$DOTS" "$((T1 - T0))" "$REV" "$STREAM_DRYRUN" "$CHAOS_SMOKE" "$LINT_FINDINGS" "$SERVE_SMOKE" "$SERVE_JSON" "$ELASTIC_SMOKE" "$FLEET_SMOKE" "$SHAP_SMOKE" <<'PY' >> scripts/check_timings.log
import json, sys, time
path, mode, dots, secs, rev, stream_ok, chaos_ok, lint, serve_ok = sys.argv[1:10]
serve_json = sys.argv[10] if len(sys.argv) > 10 else ""
elastic_ok = sys.argv[11] if len(sys.argv) > 11 else "0"
fleet_ok = sys.argv[12] if len(sys.argv) > 12 else "0"
shap_ok = sys.argv[13] if len(sys.argv) > 13 else "0"
try:
    lines = [ln for ln in open(path).read().splitlines() if ln.strip()]
    snap = json.loads(lines[-1])
    if snap.get("schema") != "lightgbm-tpu-metrics-v1":
        raise ValueError(f"unexpected schema {snap.get('schema')!r}")
except Exception as e:
    sys.stderr.write(f"check.sh: MALFORMED obs metrics dump {path}: "
                     f"{type(e).__name__}: {e}\n")
    sys.exit(3)

def gauge(name):
    for m in snap.get("metrics", []):
        if m.get("name") == name and not m.get("labels"):
            return m.get("value")
    return None

def total(name):
    """Sum of one counter over its label sets."""
    vals = [m.get("value") or 0.0 for m in snap.get("metrics", [])
            if m.get("name") == name]
    return sum(vals) if vals else None

def serve_stat(key):
    """Read one field off the serving smoke's final JSON record (the
    queue-wait p99 decomposition signal); a failed/absent smoke run
    yields None — obs_trend skips missing signals, never crashes."""
    try:
        lines = [ln for ln in open(serve_json).read().splitlines()
                 if ln.strip().startswith("{")]
        return json.loads(lines[-1]).get(key)
    except Exception:
        return None

print("obs " + json.dumps({
    "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    "rev": rev, "mode": mode, "dots": int(dots), "secs": int(secs),
    "compile_requests": gauge("compile.requests"),
    "peak_hbm_gib": gauge("bench.peak_hbm_gib"),
    "bench_iters_per_sec": gauge("bench.iters_per_sec"),
    "predict_programs": gauge("compile.predict_programs"),
    # columns the training histogram calls were handed (the
    # hist.cols_scanned counter, summed over sampled=0|1): masked =
    # n_pad x rounds; a partition regression shows up here as this
    # number jumping back to the masked product
    "hist_rows_scanned": total("hist.cols_scanned"),
    "hist_partition": gauge("bench.hist_partition"),
    # loop-state %copy share of device busy (trace attribution,
    # scripts/trace_attr.py) — present when the bench ran with
    # --profile-dir; obs_trend.py fails on it regressing above its
    # trailing median like iters/sec
    "copy_share": gauge("train.copy_share"),
    # collective share of device busy (same trace attribution) and the
    # per-iter wall-vs-busy gap the tpu_stream_overlap pipeline
    # shrinks; obs_trend.py guards the gap like copy_share
    "comm_share": gauge("train.comm_share"),
    "wall_busy_gap_ms": gauge("train.wall_busy_gap_ms"),
    # streamed-training trajectory + the sharded-streaming dryrun pin
    "stream_rows_per_sec": gauge("bench.stream_rows_per_sec"),
    "stream_shards": gauge("bench.stream_shards"),
    "stream_dryrun": int(stream_ok),
    # kill + resume + hot-swap loop (benchmarks/chaos_bench.py --smoke)
    "chaos_smoke": int(chaos_ok),
    # elastic resize cycle riding the same smoke: kill -> resume
    # NARROWER -> bit-equality + zero dropped predicts
    "elastic_smoke": int(elastic_ok),
    # serving-fleet kill/join cycle riding the same smoke: 3 replicas,
    # kill one mid-load -> relaunch + degrade -> zero dropped requests
    "fleet_smoke": int(fleet_ok),
    # concurrent serving: coalesce + evict + swap under load with zero
    # drops and zero warm compiles (benchmarks/serve_bench.py --smoke)
    "serve_smoke": int(serve_ok),
    # mixed predict+explain leg of the same smoke: device SHAP through
    # the service lanes with zero drops and zero warm compiles
    "shap_smoke": int(shap_ok),
    # windowed serving queue-wait p99 from the smoke's SLO plane —
    # obs_trend.py flags it regressing past its trailing median
    # (queue-pressure creep: budget misconfig, dispatch slowdown)
    "queue_wait_p99_ms": serve_stat("queue_wait_p99_ms"),
    # drift-linter findings (python -m tools.analyze; -1 = analyzer
    # crashed). obs_trend.py fails absolutely on anything but 0
    "lint_findings": int(lint),
}))
PY

if [[ "$STREAM_DRYRUN" != 1 ]]; then
  echo "check.sh: streamed-sharded dryrun FAILED (status logged)"
  exit 4
fi
if [[ "$CHAOS_SMOKE" != 1 ]]; then
  echo "check.sh: chaos smoke FAILED (kill+resume+swap; status logged)"
  exit 5
fi
if [[ "$ELASTIC_SMOKE" != 1 ]]; then
  echo "check.sh: elastic smoke FAILED (kill+resume-narrower re-cut;" \
       "status logged)"
  exit 8
fi
if [[ "$FLEET_SMOKE" != 1 ]]; then
  echo "check.sh: serving-fleet smoke FAILED (kill/join cycle under" \
       "load; status logged)"
  exit 9
fi
if [[ "$LINT_FINDINGS" != 0 ]]; then
  echo "check.sh: static analysis FAILED ($LINT_FINDINGS finding(s);" \
       "run python -m tools.analyze — docs/static-analysis.md)"
  exit 6
fi
if [[ "$SERVE_SMOKE" != 1 ]]; then
  echo "check.sh: serving smoke FAILED (coalesce+evict+swap under" \
       "load; status logged)"
  exit 7
fi
if [[ "$SHAP_SMOKE" != 1 ]]; then
  echo "check.sh: mixed predict+explain smoke FAILED (device SHAP" \
       "through the service; status logged)"
  exit 10
fi

# perf-regression sentinel (CHECK_TREND=1 to enforce): compare the obs
# line just appended against the trailing same-mode median; a >15%
# iters/sec drop, compile-count jump, or peak-HBM creep FAILS the gate.
# First run (no history) stays green — the sentinel needs >= 2 lines.
if [[ "${CHECK_TREND:-0}" == "1" ]]; then
  python scripts/obs_trend.py
fi
echo "check.sh: OK (timing + obs line logged to scripts/check_timings.log)"
