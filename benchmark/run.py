"""Run ONE cell of BENCHMARK.json once, in this process, on the chip.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (correct, attempted,
failed, metrics, device, and with --trace 1 breakdown). Without a TPU, or
with fewer chips than the cell asks for, nothing is printed there and the
exit code is 2. This file knows no configuration, cell, entry or metric
by name: it finds them as files from the names in BENCHMARK.json
(see README.md). `--rehearse ROWS` is the CPU rehearsal of the control
flow at a tiny size: it prints its findings to standard error only and
always exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

from lib.harness import Harness, load_module  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, default=0, metavar="ROWS")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    h = Harness(ROOT, HERE, bench, cells[args.workload], args.seed,
                args.seconds, bool(args.trace), args.rehearse, T_START)
    if not h.look_for_chip():
        return 2
    entry = load_module(os.path.join(HERE, "entries",
                                     h.cell["entry"] + ".py"))
    result = entry.run(h)
    line = h.result_line(result)
    h.print_numbers(result)
    if args.rehearse:
        print("REHEARSAL (no device metric, no result line): "
              + json.dumps(line), file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
