"""On the chip: one cell as `run.py --trace 1` runs it, with the traced
window ALSO read by the program's own names (`lib/scopes.py`), and the
per-layer metrics that read that reduction (`metrics/partition_ms.py`,
`move_ms.py`, `split_search_ms.py`, `score_update_ms.py`,
`goss_sample_ms.py`, `unscoped_ms.py`, `host_idle_ms.py`) reported beside
the cell's own.

`run.py` reads the dump with `lib/xplane.py::reduce_trace` alone and removes
it; here that call is wrapped so that the same dump is read a second time
while it is there, and the reduction rides in the trace's readings under
"scopes". The line's `breakdown.idle_gaps` then carry the program's span
names, and `breakdown.scopes` the device ms an iteration of every scope.

Prints the result line last, and with --out DIR writes it to
DIR/<cell>.<seed>.json too. Not run by the benchmark.

    python benchmark/tests/scopes_chip.py --workload <cell> --seed N \
        [--seconds 40] [--out DIR]
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from lib import scopes, xplane  # noqa: E402
from lib.harness import Harness, load_module  # noqa: E402

# the per-layer metrics that read the reduction by scope, as BENCHMARK.json
# entries; a cell that does not report one here is left out by its list
ALL = None
BY_SCOPE = [
    ("partition_ms", "grower", ALL),
    ("move_ms", "grower", ["criteo-tb-1700m.train-plain"]),
    ("split_search_ms", "grower", ALL),
    ("score_update_ms", "engine", ALL),
    ("goss_sample_ms", "engine", ["airline-115m.train-goss",
                                  "criteo-kaggle-45m.train-goss",
                                  "airline-115m.train-valid"]),
    ("unscoped_ms", "whole step", ALL),
    ("host_idle_ms", "device", ALL),
]


def entries() -> list[dict]:
    out = []
    for name, layer, cells in BY_SCOPE:
        m = {"name": name, "unit": "ms/iter", "better": "lower",
             "source": "device_trace", "layer": layer,
             "moves": "train_iter_ms"}
        if cells is not ALL:
            m["workloads"] = cells
        out.append(m)
    return out


_reduce_trace = xplane.reduce_trace


def _reduce_both(path, window_annotation, annotation_prefix="bench/"):
    red = _reduce_trace(path, window_annotation, annotation_prefix)
    if red is not None:
        red["scopes"] = scopes.reduce_scopes(path, window_annotation)
    return red


class ScopedHarness(Harness):
    def result_line(self, result: dict) -> dict:
        line = super().result_line(result)
        red = (result["ctx"].get("trace") or {}).get("scopes")
        if self.trace and red:
            iters = result["ctx"]["iters_traced"]
            line["breakdown"]["idle_gaps"] = [
                [n, s] for n, s in red["idle_gaps"][:10]]
            line["breakdown"]["scopes"] = {
                sc: s * 1e3 / iters for sc, s in sorted(
                    red["scopes"].items(), key=lambda kv: -kv[1])}
            line["breakdown"]["scope_ops"] = red["scope_ops"]
            line["breakdown"]["scopes_busy_s"] = [
                sum(red["scopes"].values()), red["busy_s"],
                result["ctx"]["trace"]["busy_s"]]
        return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="ROWS")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] += entries()
    workload = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
    h = ScopedHarness(ROOT, BENCH, bench, workload, args.seed, args.seconds,
                      True, args.rehearse, T_START)
    if not h.look_for_chip():
        return 2
    xplane.reduce_trace = _reduce_both
    entry = load_module(os.path.join(BENCH, "entries",
                                     h.cell["entry"] + ".py"))
    result = entry.run(h)
    line = h.result_line(result)
    h.print_numbers(result)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, f"{args.workload}.{args.seed}.json"),
                  "w") as f:
            json.dump(line, f)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
