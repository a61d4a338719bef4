"""On the chip, at the cell's own size and through the cell's own entry and
`judge`: the control one precision below the stated one and the faults a
training cell can have, each planted in the program the WINDOW runs, seed
after seed in one process (one table a seed, driven several ways). Prints
one JSON line a seed and variant: `correct`, the numbers compared beside
their limits, and which went over. Not run by the benchmark.

    python benchmark/tests/control_chip.py --workload <cell> --seeds 1,2,3 \
        [--variants stated,control,half_batch,stale_state] [--rows N]

Variants: `stated` the configuration as it stands; `control` the program
with num_grad_quant_bins 2 for the stated 4; `half_batch` every second row
gives no gradient in the sampled (GOSS) chunk program; `stale_state` that
program's scan returns its carry unchanged.
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


class WindowFaults:
    """Faults that live only in the chunk program the window runs, the
    sampled (GOSS) one: they are planted while that program is traced,
    which is inside its first call, so `train_chunk` is cut in two where
    sampling begins. Nothing is planted while `fault` is None."""

    def __init__(self):
        import jax
        from lightgbm_tpu.boosting.gbdt import GBDT
        from lightgbm_tpu.objective import Binary
        self.fault = None
        self._sampling = False
        self._jax, self._gbdt, self._binary = jax, GBDT, Binary
        self._real = (jax.lax.scan, GBDT.train_chunk, Binary.get_gradients)
        real_scan, real_chunk, real_grad = self._real
        faults = self

        def train_chunk(engine, n_iters):
            c = engine.config
            if c.data_sample_strategy == "goss":
                start = int(1.0 / max(c.learning_rate, 1e-6))
                plain = min(max(start - engine.iter_, 0), n_iters)
                if plain:
                    real_chunk(engine, plain)
                faults._sampling = True
            try:
                real_chunk(engine, n_iters - (plain if faults._sampling
                                              else 0))
            finally:
                faults._sampling = False

        def scan(body, init, xs=None, *a, **kw):
            chunk_keys = (getattr(xs, "ndim", 0) == 2 and xs.shape[1] == 2
                          and str(xs.dtype) == "uint32"
                          and getattr(init, "ndim", 0) == 2)
            if chunk_keys and faults._sampling \
                    and faults.fault == "stale_state":
                return real_scan(lambda c, x: (c, body(c, x)[1]), init, xs,
                                 *a, **kw)
            return real_scan(body, init, xs, *a, **kw)

        def get_gradients(obj, score, label, weight):
            import jax.numpy as jnp
            g, h = real_grad(obj, score, label, weight)
            if faults._sampling and faults.fault == "half_batch":
                keep = (jnp.arange(g.shape[0]) % 2 == 0).astype(g.dtype)
                return g * keep, h * keep
            return g, h

        jax.lax.scan = scan
        GBDT.train_chunk = train_chunk
        Binary.get_gradients = get_gradients

    def lift(self):
        (self._jax.lax.scan, self._gbdt.train_chunk,
         self._binary.get_gradients) = self._real


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
        faults = faults or WindowFaults()
        entry = load_module(os.path.join(BENCH, "entries",
                                         h.cell["entry"] + ".py"))
        prep = entry.prepare(h)
        stated = prep.pop("params")
        for variant in args.variants.split(","):
            faults.fault = variant if variant in ("half_batch",
                                                  "stale_state") else None
            params = dict(stated, **(CONTROL if variant == "control" else {}))
            r = entry.drive(h, prep, params)
            over = sorted(k for k, (v, lim) in r["numbers"].items()
                          if v is None or not v <= lim)
            if r["correct"] != (variant == "stated"):
                rc = 1
            print(json.dumps({
                "seed": seed, "variant": variant, "correct": r["correct"],
                "over": over, "numbers": r["numbers"], "rows": prep["rows"],
                "iters": r["attempted"], "device": h.device,
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
