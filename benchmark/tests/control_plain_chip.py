"""On the chip, at the cell's own size and through the cell's own entry and
`judge`: the control and the faults of the two paths tests/control_chip.py
does not reach (its faults live in the SAMPLED chunk program alone).
Prints one JSON line a seed and variant, as it does. Not run by the
benchmark.

    python benchmark/tests/control_plain_chip.py --workload <cell> \
        --seeds 1,2,3 --variants stated,control,half_batch

Variants, for an unsampled fused cell (`criteo-tb-1700m.train-plain`):
`stated` the configuration as it stands; `control` the program with
num_grad_quant_bins 2 for the stated 4; `half_batch` every second row
gives no gradient, in every chunk program of that drive (an unsampled run
has one program from its first round on); `masked` the same table with
tpu_hist_partition=false, the masked full scans the leaf-ordered partition
replaces (a control of SPEED: it has to come out correct, and its
`train_iter_ms` is printed beside the stated path's). For a cell with a validation set
(`airline-115m.train-valid`): `valid_miss_tree` the validation scores miss
the tree of the window's third round, so every later round reports the AUC
of a forest with a hole in it.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from lib.harness import Harness, load_module  # noqa: E402

CONTROL = {"use_quantized_grad": True, "num_grad_quant_bins": 2}


class Faults:
    """Nothing is planted while `fault` is None."""

    def __init__(self):
        from lightgbm_tpu.boosting.gbdt import GBDT
        from lightgbm_tpu.objective import Binary
        self.fault = None
        self.miss_at = -1           # the round whose tree the scores miss
        self._gbdt, self._binary = GBDT, Binary
        self._real = (Binary.get_gradients, GBDT.train_one_iter)
        real_grad, real_iter = self._real
        faults = self

        def get_gradients(obj, score, label, weight):
            import jax.numpy as jnp
            g, h = real_grad(obj, score, label, weight)
            if faults.fault == "half_batch":
                keep = (jnp.arange(g.shape[0]) % 2 == 0).astype(g.dtype)
                return g * keep, h * keep
            return g, h

        def train_one_iter(engine, *a, **kw):
            if not (faults.fault == "valid_miss_tree" and engine.valid_data
                    and engine.iter_ == faults.miss_at):
                return real_iter(engine, *a, **kw)
            update = engine._valid_update
            engine._valid_update = lambda scores, stacked: scores
            try:
                return real_iter(engine, *a, **kw)
            finally:
                engine._valid_update = update

        Binary.get_gradients = get_gradients
        GBDT.train_one_iter = train_one_iter

    def lift(self):
        self._binary.get_gradients, self._gbdt.train_one_iter = self._real


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control,half_batch")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args()
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
        faults = faults or Faults()
        faults.miss_at = (int(h.cell["warm_rounds"])
                          + int(h.cell.get("probe_rounds", 5)) + 2)
        entry = load_module(os.path.join(BENCH, "entries",
                                         h.cell["entry"] + ".py"))
        prep = entry.prepare(h)
        stated = prep.pop("params")
        for variant in args.variants.split(","):
            faults.fault = variant if variant in ("half_batch",
                                                  "valid_miss_tree") else None
            params = dict(stated, **(CONTROL if variant == "control" else {}))
            expect = h.cell.get("expect", {})
            if variant == "masked":
                params["tpu_hist_partition"] = "false"
                h.cell["expect"] = dict(expect, hist_partition=False)
            r = entry.drive(h, prep, params)
            h.cell["expect"] = expect
            over = sorted(k for k, (v, lim) in r["numbers"].items()
                          if v is None or not v <= lim)
            if r["correct"] != (variant in ("stated", "masked")):
                rc = 1
            print(json.dumps({
                "seed": seed, "variant": variant, "correct": r["correct"],
                "over": over, "numbers": r["numbers"], "rows": prep["rows"],
                "iters": r["attempted"], "device": h.device,
                "train_iter_ms": r["end_to_end"]["train_iter_ms"],
                "path": r["window"]["path"],
                "reference_s": round(r["window"]["reference_s"], 1),
                "spans": r["window"]["spans"],
                "followed": r["window"]["followed"]}), flush=True)
            gc.collect()
        del prep
        gc.collect()
    faults.lift()
    return rc


if __name__ == "__main__":
    sys.exit(main())
