#!/usr/bin/env python
"""Trace-level attribution CLI over a ``tpu_profile_dir`` dump.

The operator's by-layer view: train with ``tpu_profile_dir=DIR``, then

    python scripts/trace_attr.py DIR                   # whole dump
    python scripts/trace_attr.py DIR --iters 40        # + ms an iteration
    python scripts/trace_attr.py DIR --window lgbm/train/fused_chunk
    python scripts/trace_attr.py DIR --iters 40 --wall-ms 1760
    python scripts/trace_attr.py DIR --json            # machine use

prints the device time of each ``lgbm/<layer>/<phase>`` scope (they add
up to device busy, a union of leaf ops that never passes the window),
the ops by self time with their scope, the ``%copy`` and collective
shares, the device's idle gaps named by the program's host span that
was open, and the host spans themselves. Parsing lives in
``lightgbm_tpu/obs/trace_attr.py`` (stdlib-only, no protobuf/jax
import); ``engine.train`` feeds the same numbers into the
``train.copy_share`` / ``train.wall_busy_gap_ms`` / ``train.layer_ms``
gauges.

``--window`` names the host annotation that is the window (default: the
outermost ``lgbm/train/*`` spans in the dump, else first op to last
op). ``--wall-ms`` overrides the window's length with a host-measured
one. Exit codes: 0 = attributed, 3 = nothing to attribute (no dump / no
op ran), 2 = bad invocation.
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
    ap.add_argument("--window", default=None,
                    help="host annotation that is the window (default: "
                         "the outermost lgbm/train/* spans)")
    ap.add_argument("--top", type=int, default=12,
                    help="ops, gaps and spans to print (default 12)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full attribution dict as JSON")
    args = ap.parse_args(argv)

    res = attribute(args.path, iters=args.iters or None,
                    wall_ms=args.wall_ms, window=args.window)
    if args.json:
        print(json.dumps(res, indent=2))
        return 0 if res.get("found") else 3
    if not res.get("found"):
        print(f"trace_attr: {res.get('reason')}")
        return 3
    print(f"source: {res['source']}")
    print(f"device plane: {res['device_plane']} "
          f"({res['n_devices']} device(s))")
    print(f"window: {res['window']}")
    per = "ms/iter" if args.iters else ""
    print(f"{'layer (scope)':<44} {'total ms':>10} {per:>8} {'share':>7}"
          f"  top ops")
    for lay in res["layers"]:
        it = (f"{lay['ms_per_iter']:>8.2f}" if args.iters else f"{'':>8}")
        print(f"{lay['scope'][:44]:<44} {lay['ms']:>10.3f} {it} "
              f"{lay['share']:>6.1%}  {' '.join(lay['ops'][:3])}")
    print(f"{'device busy (sum of the above)':<44} "
          f"{res['busy_ms']:>10.3f}")
    print(f"{'wall (window)':<44} {res['wall_ms']:>10.3f}")
    print()
    print(f"{'op (self time)':<44} {'total ms':>10} {'calls':>8} "
          f"{'share':>7}  scope")
    for op in res["ops"][:args.top]:
        print(f"{op['name'][:44]:<44} {op['ms']:>10.3f} "
              f"{op['calls']:>8d} {op['share']:>6.1%}  {op['scope']}")
    print(f"{'%copy (loop-state copies)':<44} {res['copy_ms']:>10.3f} "
          f"{'':>8} {res['copy_share']:>6.1%}")
    print(f"{'collectives (all-reduce et al.)':<44} "
          f"{res['comm_ms']:>10.3f} {'':>8} {res['comm_share']:>6.1%}")
    if res["idle_gaps"]:
        print()
        print(f"{'idle gap, by the host span open at its middle':<44} "
              f"{'ms':>10}")
        for gap in res["idle_gaps"][:args.top]:
            print(f"{gap['name'][:44]:<44} {gap['ms']:>10.3f}")
    if res["spans"]:
        print()
        print(f"{'host span':<44} {'total ms':>10} {'count':>8}")
        for sp in res["spans"][:args.top]:
            print(f"{sp['name'][:44]:<44} {sp['ms']:>10.3f} "
                  f"{sp['count']:>8d}")
    if "wall_busy_gap_ms" in res:
        print(f"wall-vs-busy gap: {res['wall_busy_gap_ms']:.2f} ms/iter "
              f"over {res['iters']} iterations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
