"""Helpers the per-layer readers in metrics/ share."""
from __future__ import annotations

import re

from lib import work


def op_seconds(ctx: dict, patterns: list[str]) -> float | None:
    """Self time of the traced device ops whose names match any pattern,
    or None where there is no trace or no such op."""
    trace = ctx.get("trace")
    if not trace:
        return None
    rx = [re.compile(p) for p in patterns]
    hit = [s for name, s, _calls in trace["ops"]
           if any(r.search(name) for r in rx)]
    return sum(hit) if hit else None


def bandwidth_floor_s(ctx: dict, n_bytes: int) -> float:
    """Least seconds this chip could take to move n_bytes (memory-bound)."""
    return n_bytes / work.peaks(ctx["device_kind"])["hbm_bytes_per_s"]


# device op names of the two Pallas kernels, as the profiler shows them
HIST_KERNEL = [r"^multi_leaf_histogram(\.\d+)?$"]
COMPACT_KERNEL = [r"^compact_rows(\.\d+)?$"]
