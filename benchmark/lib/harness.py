"""What every entry shares: finding a cell's files, the look for a chip,
host spans on the profiler's clock, the compile listener, memory and
device readings, the per-layer readers, and the result line."""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_module(path: str):
    name = "bench_" + os.path.basename(path).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Harness:
    def __init__(self, root, here, bench, workload, seed, seconds, trace,
                 rehearse_rows=0, t_start=None, need_chip=True):
        self.root, self.here, self.bench = root, here, bench
        self.workload = workload
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.rehearse_rows = int(rehearse_rows)
        self.need_chip = need_chip and not rehearse_rows
        self.t_start = time.perf_counter() if t_start is None else t_start
        with open(os.path.join(here, "cells", workload["name"] + ".json")) as f:
            self.cell = json.load(f)
        cfg = next(c for c in bench["configs"]
                   if c["name"] == workload["config"])
        with open(os.path.join(root, cfg["file"])) as f:
            self.config = json.load(f)
        self.spans: dict[str, float] = {}
        self.compiles: list[tuple[float, float]] = []   # (when, seconds)
        self.device: dict = {}
        self._jax = None

    # ---- the chip ---------------------------------------------------------
    def look_for_chip(self) -> bool:
        """Import JAX with the compile cache at a fixed place inside the
        checkout (unless the machine names one), and refuse to go on
        without the accelerator and the chips the cell asks for."""
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(self.root, ".jax_cache"))
        try:
            import jax
            import lightgbm_tpu  # noqa: F401  the system under test
        except ImportError as e:
            print(f"cannot import the system under test: {e}", file=sys.stderr)
            return False
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        self._jax = jax
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        devs = jax.devices()
        chips = int(self.workload["chips"])
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        if self.need_chip and (devs[0].platform != "tpu" or len(devs) < chips):
            print(f"refusing to measure: JAX found {len(devs)} "
                  f"{devs[0].platform} device(s), the cell needs {chips} TPU "
                  f"chip(s)", file=sys.stderr)
            return False
        return True

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), float(duration)))

    def compiles_between(self, t0: float, t1: float) -> tuple[int, float]:
        """(programs compiled, backend-compile seconds) in [t0, t1]."""
        hit = [d for t, d in self.compiles if t0 <= t <= t1]
        return len(hit), float(sum(hit))

    def memory_peak_bytes(self) -> int:
        peak = 0
        for d in self._jax.local_devices():
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def memory_limit_bytes(self) -> int:
        stats = self._jax.local_devices()[0].memory_stats() or {}
        return int(stats.get("bytes_limit", 0))

    # ---- spans ------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """Host clock around a phase, and the same phase as an annotation
        in the profiler's trace, so an idle gap can be given its name."""
        t0 = time.perf_counter()
        with self._jax.profiler.TraceAnnotation("bench/" + name):
            yield
        self.spans[name] = self.spans.get(name, 0.0) + time.perf_counter() - t0

    @contextlib.contextmanager
    def profiler(self, window: str):
        """Trace the body into a directory under TMPDIR; yields a dict that
        holds the reduced trace under "reduced" afterwards. `window` names
        the span, opened inside the body, that the traced window is."""
        from lib import xplane
        out: dict = {}
        d = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            self._jax.profiler.start_trace(d)
            try:
                yield out
            finally:
                self._jax.profiler.stop_trace()
            out["reduced"] = xplane.reduce_trace(d, "bench/" + window)
        finally:
            shutil.rmtree(d, ignore_errors=True)

    # ---- the result ---------------------------------------------------------
    def _reports(self, metric: dict) -> bool:
        return ("workloads" not in metric
                or self.workload["name"] in metric["workloads"])

    def per_layer(self, ctx: dict) -> dict:
        """Each per-layer metric of this cell, from its reader
        metrics/<name>.py; one that finds nothing to read is left out."""
        out = {}
        for m in self.bench["per_layer"]:
            if not self._reports(m):
                continue
            reader = load_module(os.path.join(self.here, "metrics",
                                              m["name"] + ".py"))
            try:
                v = reader.read(ctx)
            except KeyError as e:
                if not self.rehearse_rows:
                    raise
                # a rehearsal's device has no peaks: no device metric
                print(f"rehearsal: {m['name']} not read: {e}", file=sys.stderr)
                continue
            if v is not None:
                out[m["name"]] = {"value": float(v), "unit": m["unit"]}
        return out

    def result_line(self, result: dict) -> dict:
        if self.trace:
            metrics = self.per_layer(result["ctx"])
        else:
            metrics = {m["name"]: {"value": float(result["end_to_end"][m["name"]]),
                                   "unit": m["unit"]}
                       for m in self.bench["end_to_end"] if self._reports(m)}
        device = dict(self.device)
        device["memory_peak_bytes"] = int(result["memory_peak_bytes"])
        line = {"correct": bool(result["correct"]),
                "attempted": int(result["attempted"]),
                "failed": int(result["failed"]), "metrics": metrics,
                "device": device}
        red = result["ctx"].get("trace")
        if self.trace and red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, s] for n, s, _c in red["ops"][:10]],
                "idle_gaps": [[n, s] for n, s in red["idle_gaps"][:10]]}
        line["seed"] = self.seed
        line["window"] = result.get("window", {})
        line["compared"] = result["numbers"]
        return line

    def print_numbers(self, result: dict) -> None:
        """Each number compared beside its limit, last on standard error."""
        print(f"correct={bool(result['correct'])} workload="
              f"{self.workload['name']} seed={self.seed}", file=sys.stderr)
        for name, (value, limit) in result["numbers"].items():
            verdict = "ok" if _within(value, limit) else "OVER"
            print(f"  {name} = {value!r}  limit {limit!r}  {verdict}",
                  file=sys.stderr)


def _within(value, limit) -> bool:
    return value is not None and value == value and value <= limit


def judge(numbers: dict) -> bool:
    """`correct`: every number compared is there and within its limit."""
    return all(_within(v, lim) for v, lim in numbers.values())
