"""Back-compat shim over the observability subsystem.

The phase-timer implementation that used to live here (its own
``_ACCUM``/``_COUNT`` dicts on ``perf_counter``) is gone: the obs
subsystem's span histograms are the one clock and one format
(``lightgbm_tpu/obs``, docs/observability.md). ``timed(name)`` now IS
``obs.span(name, force=True)`` — forced, because a caller reaching for
an explicit timer has asked for a measurement regardless of the global
``tpu_metrics`` gate — and the totals/log helpers read the registry's
histograms.

Reference lineage unchanged: the reference's global timer macros
(include/LightGBM/utils/log.h, UNVERIFIED — empty mount, see SURVEY.md
banner) printing per-phase timings in debug builds.

The profiler session itself is ``tpu_profile_dir`` (engine.train); every
obs span is an annotation in its dump (obs/__init__.py).
"""
from __future__ import annotations

from typing import Dict

from . import log


def timed(name: str):
    """Accumulate wall time under ``name`` (nestable). Records into the
    obs histogram of the same name (always — see module docstring) and,
    when tracing is on, a Chrome-trace span."""
    from .. import obs
    return obs.span(name, force=True)


def timer_totals() -> Dict[str, float]:
    """Total seconds per histogram name from the obs registry (the old
    accumulated-phase-times dict, same keys)."""
    from ..obs.metrics import Histogram, registry
    out: Dict[str, float] = {}
    for m in registry().metrics():
        if isinstance(m, Histogram):
            out[m.name] = out.get(m.name, 0.0) + m.sum
    return out


def reset_timers() -> None:
    """Clear the collected phase timers — the registry's HISTOGRAMS
    only. Counters and gauges (cumulative compile.requests, restart
    telemetry, bench gauges) are not timers and survive."""
    from ..obs.metrics import registry
    registry().reset(kind="histogram")


def log_timers() -> None:
    """Debug-log accumulated phase times from the obs registry (the
    reference prints its global timer table at shutdown in debug
    builds)."""
    from ..obs.metrics import Histogram, registry
    hists = [m for m in registry().metrics() if isinstance(m, Histogram)]
    for m in sorted(hists, key=lambda m: -m.sum):
        log.debug(f"{m.name}: {m.sum:.3f}s ({m.count} calls)")
