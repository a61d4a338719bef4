#!/usr/bin/env python
"""Trace-level attribution CLI over a ``tpu_profile_dir`` dump.

The promoted form of docs/perf.md's "~20 line raw XSpace parse" (the
tensorboard converter is protobuf-incompatible here): per-op busy
aggregation over the device plane's "XLA Ops" line, the ``%copy``
share the donation pass squeezes, and the per-iteration wall-vs-busy
gap. Parsing lives in ``lightgbm_tpu/obs/trace_attr.py`` (stdlib-only,
no protobuf/jax import) so ``engine.train`` and ``bench.py
--profile-dir`` feed the same numbers into the ``train.copy_share`` /
``train.wall_busy_gap_ms`` gauges that scripts/obs_trend.py guards.

    python scripts/trace_attr.py /tmp/prof                 # whole dump
    python scripts/trace_attr.py /tmp/prof --iters 40      # + gap/iter
    python scripts/trace_attr.py /tmp/prof --iters 40 --wall-ms 1760
    python scripts/trace_attr.py /tmp/prof --json          # machine use

``--wall-ms`` overrides the trace-window wall estimate with a
host-measured one (trust host timers around ``block_until_ready`` for
WALL and the trace for op time — perf.md "Trace-level attribution").
Exit codes: 0 = attributed, 3 = nothing to attribute (no dump / no
device plane — e.g. a CPU-backend trace), 2 = bad invocation.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from lightgbm_tpu.obs.trace_attr import attribute  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-op busy attribution of a jax.profiler xplane "
                    "dump (see module docstring)")
    ap.add_argument("path", help="a *.xplane.pb file or a "
                                 "tpu_profile_dir tree (newest dump "
                                 "inside is used)")
    ap.add_argument("--iters", type=int, default=0,
                    help="boosting iterations the traced window "
                         "covered (enables the per-iter gap)")
    ap.add_argument("--wall-ms", type=float, default=None,
                    help="host-measured wall ms of the traced window "
                         "(default: trace span)")
    ap.add_argument("--top", type=int, default=12,
                    help="ops to print (default 12)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full attribution dict as JSON")
    args = ap.parse_args(argv)

    res = attribute(args.path, iters=args.iters or None,
                    wall_ms=args.wall_ms)
    if args.json:
        print(json.dumps(res, indent=2))
        return 0 if res.get("found") else 3
    if not res.get("found"):
        print(f"trace_attr: {res.get('reason')}")
        return 3
    print(f"source: {res['source']}")
    print(f"device plane: {res['device_plane']}")
    print(f"{'op':<44} {'total ms':>10} {'calls':>8} {'share':>7}")
    for op in res["ops"][:args.top]:
        print(f"{op['name'][:44]:<44} {op['ms']:>10.3f} "
              f"{op['calls']:>8d} {op['share']:>6.1%}")
    print(f"{'device busy':<44} {res['busy_ms']:>10.3f}")
    print(f"{'%copy (loop-state copies)':<44} {res['copy_ms']:>10.3f} "
          f"{'':>8} {res['copy_share']:>6.1%}")
    print(f"{'collectives (all-reduce et al.)':<44} "
          f"{res['comm_ms']:>10.3f} {'':>8} {res['comm_share']:>6.1%}")
    print(f"{'wall (traced window)':<44} {res['wall_ms']:>10.3f}")
    if "wall_busy_gap_ms" in res:
        print(f"wall-vs-busy gap: {res['wall_busy_gap_ms']:.2f} ms/iter "
              f"over {res['iters']} iterations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
