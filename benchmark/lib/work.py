"""The work of one boosting iteration, counted the same whatever
implements it, and the chip's peaks to set it against.

Histogram building with the subtraction trick has to read, for every
tree, the bins of the root's rows once and of the SMALLER child's rows
at every split. The trained model carries those counts, so the work
comes from the window's own trees and not from a counter of the program.
"""
from __future__ import annotations

# peaks by jax `device_kind`; a device that is not here is an error
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}

# per-row training state one iteration has to read and write once:
# score (r+w), label (r), gradient and hessian (w, then r by the
# histogram pass, counted there), 4 B each
STATE_BYTES_PER_ROW = 4 * 4 * 2
GRAD_PAIR_BYTES = 8


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}")
    return PEAKS[device_kind]


def rows_min(tree: dict) -> int:
    """Rows whose bins one tree's histograms have to read: the root's,
    and at each split the smaller child's."""
    if tree["num_leaves"] <= 1:
        return 0
    lc, rc = tree["left_child"], tree["right_child"]
    icount, lcount = tree["internal_count"], tree["leaf_count"]

    def count(child: int) -> int:
        return int(icount[child] if child >= 0 else lcount[~child])

    total = int(icount[0])
    for node in range(len(lc)):
        total += min(count(int(lc[node])), count(int(rc[node])))
    return total


def hist_bytes(trees: list[dict], n_features: int, bin_bytes: int = 1) -> int:
    """Least bytes the histogram passes of these trees have to move:
    the bins of every row read, and its gradient pair."""
    r = sum(rows_min(t) for t in trees)
    return r * n_features * bin_bytes + r * GRAD_PAIR_BYTES


def step_bytes(trees: list[dict], n_features: int, n_rows: int) -> int:
    """Least bytes of whole iterations: histograms plus one pass over the
    per-row state for each tree."""
    return (hist_bytes(trees, n_features)
            + len(trees) * n_rows * STATE_BYTES_PER_ROW)
