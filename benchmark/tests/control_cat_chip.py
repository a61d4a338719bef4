"""tests/control_chip.py for a cell whose entry is `train-fused-cat`: the
same control and faults of the program, planted by that file's
`WindowFaults`, and two faults of the READING of a set-split, planted in
lib/reference_cat.py's traversal, each of which has to come out
`correct` false through the entry and `judge`. One table a seed, driven
several ways in one process; one JSON line a seed and variant. Not run by
the benchmark.

    python benchmark/tests/control_cat_chip.py --workload <cell> --seeds 1,2 \
        [--variants stated,control,half_batch,ids_as_numbers,bitset_word_dropped]

`ids_as_numbers`: the reference reads a categorical node as
`value <= threshold` (the threshold is the index of the node's bitset), as
a program that routed ids numerically would. `bitset_word_dropped`: the
reference reads every tree's first set-split with the first word of its
bitset (ids 0 to 31) empty, as a model that lost a word on its way from
the device to the text would read.
"""
import argparse
import gc
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from lib import reference, reference_cat  # noqa: E402
from lib.harness import Harness, load_module  # noqa: E402

_sound_leaves = reference_cat.leaves


def _ids_as_numbers(tree: dict, X: np.ndarray) -> np.ndarray:
    if "_numeric" not in tree:
        tree["_numeric"] = {k: v for k, v in tree.items() if k != "_c"}
        tree["_numeric"]["decision_type"] = tree["decision_type"] & ~1
    return reference.leaves(tree["_numeric"], X)


def _bitset_word_dropped(tree: dict, X: np.ndarray) -> np.ndarray:
    if "_dropped" not in tree:
        t = {k: v for k, v in tree.items() if k != "_c"}
        sets = np.flatnonzero(reference_cat.is_categorical(tree))
        if len(sets):
            t["cat_threshold"] = tree["cat_threshold"].copy()
            k = int(tree["threshold"][sets[0]])
            t["cat_threshold"][int(tree["cat_boundaries"][k])] = 0
        tree["_dropped"] = t
    return _sound_leaves(tree["_dropped"], X)


REFERENCE_FAULTS = {"ids_as_numbers": _ids_as_numbers,
                    "bitset_word_dropped": _bitset_word_dropped}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control,half_batch,"
                    "ids_as_numbers,bitset_word_dropped")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args()
    chip = load_module(os.path.join(HERE, "control_chip.py"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
    rc = 0
    faults = None
    for seed in (int(s) for s in args.seeds.split(",")):
        h = Harness(ROOT, BENCH, bench, workload, seed, args.seconds, False,
                    rehearse_rows=args.rows)
        if not h.look_for_chip():
            return 2
        faults = faults or chip.WindowFaults()
        entry = load_module(os.path.join(BENCH, "entries",
                                         h.cell["entry"] + ".py"))
        prep = entry.prepare(h)
        stated = prep.pop("params")
        for variant in args.variants.split(","):
            faults.fault = variant if variant == "half_batch" else None
            reference_cat.leaves = REFERENCE_FAULTS.get(variant,
                                                        _sound_leaves)
            params = dict(stated, **(chip.CONTROL if variant == "control"
                                     else {}))
            try:
                r = entry.drive(h, prep, params)
            finally:
                reference_cat.leaves = _sound_leaves
            over = sorted(k for k, (v, lim) in r["numbers"].items()
                          if v is None or not v <= lim)
            if r["correct"] != (variant == "stated"):
                rc = 1
            print(json.dumps({
                "seed": seed, "variant": variant, "correct": r["correct"],
                "over": over, "numbers": r["numbers"], "rows": prep["rows"],
                "iters": r["attempted"], "device": h.device,
                "train_iter_ms": r["end_to_end"]["train_iter_ms"],
                "followed": r["window"]["followed"]}), flush=True)
            gc.collect()
        del prep
        gc.collect()
    faults.lift()
    return rc


if __name__ == "__main__":
    sys.exit(main())
