"""Out-of-core (tpu_streaming) throughput probe — VERDICT r4 item 3.

Builds a synthetic dataset whose BINNED matrix can exceed device HBM
(v5e: 16 GiB; --gib 32 is the 2x-over-HBM proof shape), ingests it via
the streaming push_rows path (raw floats are dropped chunk by chunk —
host RAM holds only the uint8 bins + per-row f32 state), trains a few
trees with the streaming engine, and prints one JSON line:

  rows, binned_gib, s_per_tree, iters_per_sec, stream_gib_s (effective
  host->device bandwidth achieved during sweeps), sweeps_per_tree.

Context for reading the numbers: s_per_tree is bound by the
host->device link (what it sustains on today's chip is to be
re-measured), so the probe reports stream_gib_s beside it.

With ``--shards "1,2"`` the probe re-trains the SAME rows at each
shard count (sharded streamed training, one packed collective per
level — docs/perf.md "Streamed x sharded") and prints one JSON line
per point, including ``stream_rows_per_sec`` and the comm counters.
Shard counts above a CPU platform's device count force fake CPU host
devices, so the grid rehearses anywhere (scaling numbers on fake
devices measure the orchestration, not real ICI — read them as
overhead bounds). On a TPU platform each shard is a chip, and asking
for more shards than chips is an error: a chip run never reports
fake-device numbers.

Usage:
  python benchmarks/streaming_probe.py --gib 2 --trees 3   # quick
  python benchmarks/streaming_probe.py --gib 32 --trees 2  # >HBM proof
  python benchmarks/streaming_probe.py --gib 1 --shards 1,2,4
  python benchmarks/streaming_probe.py --gib 1 --shards 2 --no-overlap
                                  # A/B arm: synchronous dispatch
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])
# amortize TPU compiles across probe runs (the level sweeps compile
# one specialization per power-of-two frontier size)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(os.path.dirname(os.path.dirname(
                          os.path.abspath(__file__))), ".jax_cache"))

F = 28


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gib", type=float, default=2.0,
                    help="target binned size in GiB (rows = gib/F)")
    ap.add_argument("--trees", type=int, default=3)
    ap.add_argument("--leaves", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=20_000_000)
    ap.add_argument("--shards", type=str, default="1",
                    help="comma list of shard counts to grid over the "
                         "SAME total rows (tree_learner=data + "
                         "tpu_mesh_shape); more than a CPU platform "
                         "has uses fake CPU host devices, more than "
                         "a TPU platform has is an error")
    ap.add_argument("--no-overlap", action="store_true",
                    help="train with tpu_stream_overlap=false (fully "
                         "synchronous per-block dispatch) — the A/B "
                         "arm for docs/perf.md 'Communication/compute "
                         "overlap'")
    args = ap.parse_args()
    shard_grid = [max(1, int(s)) for s in args.shards.split(",") if s]
    if max(shard_grid) > 1:
        # fake host devices ONLY when a CPU platform cannot seat the
        # grid — probed in a subprocess so this process's backend is
        # still uninitialized when the flags must land (the child has
        # exited, and let go of any chip, before this process touches
        # jax). A real multi-chip host keeps its real devices (those
        # are the numbers the probe exists to publish).
        import subprocess
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend(), "
             "jax.device_count())"],
            capture_output=True, text=True, timeout=120)
        if probe.returncode != 0:
            sys.exit(f"streaming_probe: the device probe failed:\n"
                     f"{probe.stderr[-2000:]}")
        platform, real = probe.stdout.split()[-2:]
        real = int(real)
        if platform == "tpu" and real < max(shard_grid):
            sys.exit(f"streaming_probe: --shards {args.shards} needs "
                     f"{max(shard_grid)} chips and this TPU platform "
                     f"has {real}; refusing to fall back to fake CPU "
                     f"devices on a chip run")
        if real < max(shard_grid):
            flags = os.environ.get("XLA_FLAGS", "")
            if "host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + f" --xla_force_host_platform_device_count="
                    f"{max(shard_grid)}").strip()
                os.environ.setdefault("JAX_PLATFORMS", "cpu")
            print(f"# streaming_probe: platform has {real} device(s) < "
                  f"{max(shard_grid)} shards -> FAKE CPU host devices; "
                  f"scaling numbers measure orchestration overhead, "
                  f"not real multi-chip throughput", file=sys.stderr)

    import lightgbm_tpu as lgb

    n = int(args.gib * 2**30 / F)
    rng = np.random.default_rng(0)
    params = {"objective": "binary", "num_leaves": args.leaves,
              "max_bin": 255, "verbosity": 1, "tpu_streaming": "true",
              "learning_rate": 0.1,
              "tpu_stream_overlap":
                  "false" if args.no_overlap else "auto"}

    t0 = time.time()
    # reference dataset: bin mappers from a 2M-row sample of the
    # generator (the loader-level sample the reference would take)
    w = rng.normal(size=F).astype(np.float32)

    def gen(m, seed):
        r = np.random.default_rng(seed)
        X = r.random(size=(m, F), dtype=np.float32)
        logit = (X - 0.5) @ w * 3.0 + 2.0 * (X[:, 0] - 0.5) * (X[:, 1] - 0.5)
        y = (logit + r.normal(scale=0.5, size=m).astype(np.float32)
             > 0).astype(np.float64)
        return X, y

    Xs, ys = gen(min(n, 2_000_000), 1)
    ref = lgb.Dataset(Xs, label=ys, params=dict(params))
    ref.construct()
    ds = lgb.Dataset(None, reference=ref, params=dict(params))
    done = 0
    ci = 0
    while done < n:
        m = min(args.chunk, n - done)
        Xc, yc = gen(m, 100 + ci)
        ds.push_rows(Xc, label=yc)
        done += m
        ci += 1
    ds.construct()
    build_s = time.time() - t0
    binned_gib = ds.binned.nbytes / 2**30

    for shards in shard_grid:
        p = dict(params)
        if shards > 1:
            p["tree_learner"] = "data"
            p["tpu_mesh_shape"] = shards
        t0 = time.time()
        bst = lgb.train(p, ds, num_boost_round=args.trees)
        train_s = time.time() - t0
        eng = bst.engine
        # sweeps per tree = depth levels + final; measure from depth
        depth = int(np.ceil(np.log2(max(args.leaves, 2))))
        sweeps = depth + 1      # level sweeps (incl. root) + final
        gib_swept = binned_gib * sweeps * args.trees
        cs = eng.comm_stats
        out = {
            "rows": n,
            "binned_gib": round(binned_gib, 2),
            "build_s": round(build_s, 1),
            "s_per_tree": round(train_s / args.trees, 2),
            "iters_per_sec": round(args.trees / train_s, 4),
            "stream_gib_s": round(gib_swept / train_s, 2),
            "sweeps_per_tree": sweeps,
            "n_blocks": eng.n_blocks,
            "stream_shards": shards,
            "overlap": "off" if args.no_overlap else "on",
            "stream_rows_per_sec": round(n * args.trees / train_s, 1),
            "allreduce_calls": cs["allreduce_calls"],
            "allreduce_bytes": cs["allreduce_bytes"],
            "acc_proxy": round(float(np.mean(
                (bst.predict(Xs) > 0.5) == ys)), 4),
        }
        print(json.dumps(out))


if __name__ == "__main__":
    main()
