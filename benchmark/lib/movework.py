"""What the readers of the leaf-ordered partition's metrics share: the
mover's device op name, the least bytes a move has to move (counted the same
whatever implements it: a stable two-way move of the whole histogram source
reads every row's bins and value channels once and writes them once), and
the program's own counters."""
from __future__ import annotations

# the mover's two kernel passes, as the profiler shows them
MOVE_KERNEL = [r"^partition_move(\.\d+)?$"]

# the value channels that ride with a row: gradient, hessian, count mask
# and the row's leaf id, 4 B each
MOVE_VALUE_BYTES_PER_ROW = 4 * 4


def move_bytes(rows_moved: float, n_features: int, bin_bytes: int = 1
               ) -> float:
    """Bytes for `rows_moved` rows handed to the mover (read + write)."""
    return 2.0 * rows_moved * (n_features * bin_bytes
                               + MOVE_VALUE_BYTES_PER_ROW)


def counter(name: str, **labels) -> float | None:
    """A counter of the program's registry, or None where the program
    keeps none of that name (an older program, or a path not taken)."""
    try:
        from lightgbm_tpu import obs
        found = obs.registry().get(name, **labels)
    except (ImportError, AttributeError):
        return None
    return None if found is None else float(found.value)


def ratio(num: str, den: str, **labels) -> float | None:
    """One counter over another, None where either is missing or the
    second reads 0."""
    a, b = counter(num, **labels), counter(den, **labels)
    return None if a is None or not b else a / b
