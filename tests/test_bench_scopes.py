"""The benchmark's reading of a profiler dump by the program's own names
(`benchmark/lib/scopes.py`) on dumps whose answers are known: a synthetic
one whose device events carry `tf_op` stats and whose host carries both the
benchmark's (`bench/`) and the program's (`lgbm/`) annotations, the
benchmark's recorded synthetic dump, and a dump recorded on a v5e chip.

- an op takes the innermost `lgbm/<layer>/<phase>` of its `tf_op`;
- the scopes and `unscoped` add up to the busy time `reduce_trace` reads;
- a gap keeps `reduce_trace`'s duration and is named by the innermost
  annotation of either kind;
- `reduce_trace` and every reader the benchmark has read what they read
  before.
"""
import gzip
import json
import os
import shutil
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import scopes, xplane  # noqa: E402
from lib.harness import load_module  # noqa: E402

RECORDED = os.path.join(BENCH, "testdata", "synthetic.xplane.pb")
CHIP_DUMP = os.path.join(os.path.dirname(__file__), "data",
                         "chip_two_chunks.xplane.pb.gz")
WINDOW = "bench/window/traced"
US = 1_000_000  # picoseconds
MK = load_module(os.path.join(BENCH, "tests", "make_synthetic_trace.py"))
field = MK.field

TF_OP = 7       # the stat metadata id the device plane gives `tf_op`
# (name, tf_op or None) of each event metadata of the device plane
DEVICE_OPS = {
    1: ("while.1", "jit(chunk)/while"),
    2: ("multi_leaf_histogram.23",
        "jit(chunk)/while/body/lgbm/grower/histogram/pallas_call"),
    3: ("fusion.2", "jit(chunk)/while/body/lgbm/grower/split_search/"
                    "lgbm/grower/cat_search/reduce"),
    4: ("copy.3", None),
    5: ("partition_move.9",
        "jit(chunk)/while/body/lgbm/grower/move/partition_move"),
    6: ("fusion.4", "jit(chunk)/lgbm/engine/score_update/add"),
}
DEVICE_EVENTS = [
    (1, 20 * US, 100 * US),    # while.1            20..120 us, encloses:
    (2, 30 * US, 30 * US),     #   histogram         30..60
    (3, 70 * US, 20 * US),     #   cat_search        70..90
    (5, 95 * US, 20 * US),     #   move              95..115
    (4, 170 * US, 50 * US),    # copy.3, no tf_op   170..220
    (6, 250 * US, 10 * US),    # score_update       250..260, past the window
]
# (name, start, length) on the host, in us
HOST_NOTES = [
    ("bench/window/traced", 0, 240),
    ("bench/window/sync", 140, 100),
    ("lgbm/train/fused_chunk", 5, 130),
    ("lgbm/eval/metric", 141, 19),
]


def _plane(name, line_name, events, metas, stats=True):
    """An XPlane with one line; `metas` {id: (name, tf_op or None)}."""
    meta = b""
    for k, (n, tf_op) in metas.items():
        body = field(1, k) + field(2, n.encode())
        if stats and tf_op is not None:
            body += field(5, field(1, TF_OP) + field(5, tf_op.encode()))
        meta += field(4, field(1, k) + field(2, body))
    stat_meta = field(5, field(1, TF_OP) + field(2, field(1, TF_OP)
                                                  + field(2, b"tf_op")))
    return (field(2, name.encode())
            + field(3, MK.line(line_name, 1000,
                               [MK.event(*ev) for ev in events]))
            + meta + (stat_meta if stats else b""))


def _dump(tmp_path, name="scoped.xplane.pb", stats=True, lgbm=True):
    notes = [n for n in HOST_NOTES if lgbm or not n[0].startswith("lgbm/")]
    host_metas = {i + 1: (n, None) for i, (n, _s, _l) in enumerate(notes)}
    host_events = [(i + 1, s * US, ln * US)
                   for i, (_n, s, ln) in enumerate(notes)]
    data = (field(1, _plane("/device:TPU:0", "XLA Ops", DEVICE_EVENTS,
                            DEVICE_OPS, stats))
            + field(1, _plane("/host:CPU", "python", host_events,
                              host_metas, stats=False)))
    p = tmp_path / name
    p.write_bytes(data)
    return str(p)


def test_an_op_takes_the_innermost_scope_of_its_tf_op(tmp_path):
    assert scopes.scope_of("jit(f)/lgbm/grower/split_search/"
                           "lgbm/grower/cat_search/sort") \
        == "lgbm/grower/cat_search"
    assert scopes.scope_of("jit(f)/while/body/add") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED
    r = scopes.reduce_scopes(_dump(tmp_path), WINDOW)
    assert r["scopes"] == {
        "lgbm/grower/histogram": pytest.approx(30e-6),
        "lgbm/grower/cat_search": pytest.approx(20e-6),
        "lgbm/grower/move": pytest.approx(20e-6),
        scopes.UNSCOPED: pytest.approx(50e-6)}
    # the `while` is no leaf; the op past the window is not read
    assert "lgbm/engine/score_update" not in r["scopes"]
    assert r["scope_ops"]["lgbm/grower/move"] == [
        ["partition_move.9", pytest.approx(20e-6)]]


def test_scopes_and_unscoped_add_up_to_busy(tmp_path):
    d = _dump(tmp_path)
    r = scopes.reduce_scopes(d, WINDOW)
    red = xplane.reduce_trace(d, WINDOW)
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"], rel=1e-12)
    assert r["busy_s"] == red["busy_s"] == pytest.approx(120e-6)
    assert r["window_s"] == red["window_s"]
    # the recorded dump carries no tf_op: all of busy is unscoped
    rec = scopes.reduce_scopes(RECORDED, WINDOW)
    assert rec["scopes"] == {
        scopes.UNSCOPED: pytest.approx(xplane.reduce_trace(
            RECORDED, WINDOW)["busy_s"])}


def test_a_gap_is_named_by_the_innermost_of_bench_and_lgbm(tmp_path):
    d = _dump(tmp_path)
    r = scopes.reduce_scopes(d, WINDOW)
    assert r["idle_gaps"] == [
        ("lgbm/eval/metric", pytest.approx(55e-6)),         # 115..170
        ("lgbm/train/fused_chunk", pytest.approx(30e-6)),   # 0..30
        ("bench/window/sync", pytest.approx(20e-6)),        # 220..240
        ("lgbm/train/fused_chunk", pytest.approx(10e-6)),   # 60..70
        ("lgbm/train/fused_chunk", pytest.approx(5e-6))]    # 90..95
    assert r["host_idle_s"] == pytest.approx(100e-6)
    # the durations are reduce_trace's, which names by `bench/` alone
    red = xplane.reduce_trace(d, WINDOW)
    assert [s for _n, s in r["idle_gaps"]] == [s for _n, s in
                                               red["idle_gaps"]]
    assert {n for n, _s in red["idle_gaps"]} <= {WINDOW, "bench/window/sync"}
    # with no program annotation in the dump the names are reduce_trace's
    rec = scopes.reduce_scopes(RECORDED, WINDOW)
    assert rec["idle_gaps"] == xplane.reduce_trace(RECORDED,
                                                   WINDOW)["idle_gaps"]
    assert rec["host_idle_s"] == 0.0
    assert scopes.reduce_scopes(d, "bench/nope") is None


def test_reduce_trace_reads_what_it_read_before(tmp_path):
    """`reduce_trace` on a dump with tf_op stats and `lgbm/` annotations
    gives what it gives on the same dump without them, and on the
    benchmark's recorded dump what test_xplane.py pins."""
    plain = xplane.reduce_trace(
        _dump(tmp_path, "plain.xplane.pb", stats=False, lgbm=False), WINDOW)
    assert xplane.reduce_trace(_dump(tmp_path), WINDOW) == plain
    rec = xplane.reduce_trace(RECORDED, WINDOW)
    assert (rec["busy_s"], rec["window_s"]) \
        == (pytest.approx(120e-6), pytest.approx(240e-6))
    assert [n for n, _s in rec["idle_gaps"]] == [
        "bench/window/sync", WINDOW, "bench/window/sync", WINDOW, WINDOW]


def _ctx(red, iters=2):
    import numpy as np
    tree = {"num_leaves": 3, "left_child": np.array([1, -1]),
            "right_child": np.array([-3, -2]),
            "internal_count": np.array([100, 70]),
            "leaf_count": np.array([60, 10, 30]),
            "decision_type": np.array([0, 1])}
    return {"spans": {}, "trace": red, "iters_traced": iters,
            "trees_traced": [tree] * iters, "traced_wall_s": 240e-6,
            "n_features": 13, "rows": 100, "compile_s": 1.5,
            "compiles_in_window": 0, "memory_peak_bytes": 1 << 30,
            "memory_limit_bytes": 1 << 34, "device_kind": "TPU v5 lite",
            "program_spans": {"train/eval": 0.1}}


def test_every_reader_reads_the_same_with_the_scopes_beside(tmp_path):
    """Each per-layer reader of BENCHMARK.json gets the trace's readings
    with and without the reduction by scope beside them: the same value,
    and the new readers find theirs only where it is there."""
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    d = _dump(tmp_path)
    before = xplane.reduce_trace(d, WINDOW)
    after = dict(before, scopes=scopes.reduce_scopes(d, WINDOW))
    read = 0
    for m in bench["per_layer"]:
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"))
        v0, v1 = reader.read(_ctx(before)), reader.read(_ctx(after))
        assert v0 == v1, m["name"]
        read += v0 is not None
    assert read >= 8
    by_scope = {
        "partition_ms": None, "move_ms": 10e-3, "split_search_ms": 10e-3,
        "score_update_ms": None, "goss_sample_ms": None,
        "unscoped_ms": 25e-3, "host_idle_ms": 50e-3}
    for name, want in by_scope.items():
        reader = load_module(os.path.join(BENCH, "metrics", name + ".py"))
        assert reader.read(_ctx(before)) is None, name
        got = reader.read(_ctx(after))
        assert got == (None if want is None else pytest.approx(want)), name


@pytest.mark.skipif(not os.path.exists(CHIP_DUMP),
                    reason="no recorded chip dump")
def test_chip_dump_by_scope_agrees_with_the_programs_reader(tmp_path):
    """Two sampled chunks recorded on a v5e: the scope an event's tf_op
    names is the one the program's reader gives it, the scopes add up to
    busy, and the gaps are reduce_trace's with the program's names."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import trace_attr
    p = tmp_path / "chip.xplane.pb"
    with gzip.open(CHIP_DUMP, "rb") as src, open(p, "wb") as dst:
        shutil.copyfileobj(src, dst)
    p, window = str(p), "lgbm/train/fused_chunk"
    with open(p, "rb") as f:
        data = f.read()
    mine = next(pl for pl in (scopes._parse_plane(v) for fnum, _w, v
                              in xplane._fields(data) if fnum == 1)
                if "/device:" in pl["name"])
    theirs = trace_attr._own_scopes(next(
        pl for pl in trace_attr.parse_xspace(data)
        if "/device:" in pl["name"]))
    assert len(mine["tf_op"]) == len(theirs) > 400
    assert all(theirs[n] == scopes.scope_of(mine["tf_op"].get(mid))
               for mid, n in mine["event_names"].items() if n in theirs)
    r = scopes.reduce_scopes(p, window)
    red = xplane.reduce_trace(p, window, "lgbm/")
    assert sum(r["scopes"].values()) == pytest.approx(red["busy_s"],
                                                      rel=1e-9)
    assert [s for _n, s in r["idle_gaps"]] == [s for _n, s in
                                               red["idle_gaps"]]
    assert set(r["scopes"]) - {scopes.UNSCOPED} \
        <= {"lgbm/" + k for k in obs.LAYERS}
    assert r["scopes"][scopes.UNSCOPED] < 0.05 * r["busy_s"]
    # the longest gap is the host turning a chunk's arrays into trees
    assert r["idle_gaps"][0][0] == "lgbm/train/append_trees"
