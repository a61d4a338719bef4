"""The trace reduction against recorded bytes whose answers are known:
busy is a union (a `while` and its body are not counted twice), op times
are self times and add up to busy, gaps carry the host annotation."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from lib import work, xplane  # noqa: E402

TRACE = os.path.join(HERE, "..", "testdata", "synthetic.xplane.pb")


WINDOW = "bench/window/traced"


def test_busy_is_a_union_of_leaves_inside_the_host_window():
    r = xplane.reduce_trace(TRACE, WINDOW)
    assert r["n_devices"] == 1
    # leaves 30..60, 70..90, 95..115, 170..220; the while is no work of
    # its own, and the op past the annotation's end is outside
    assert r["busy_s"] == pytest.approx(120e-6)
    assert r["window_s"] == pytest.approx(240e-6)    # the host annotation
    ops = {n: (s, c) for n, s, c in r["ops"]}
    assert ops["hist_kernel"] == (pytest.approx(50e-6), 2)
    assert ops["fusion.2"] == (pytest.approx(30e-6), 2)
    assert ops["while.1"] == (pytest.approx(30e-6), 1)   # 100 - 30 - 20 - 20
    assert ops["copy.3"] == (pytest.approx(50e-6), 1)
    # the program's own reader would have said 230 us busy of 240
    assert r["busy_s"] <= r["window_s"]


def test_idle_gaps_are_named_and_reach_the_window_edges():
    r = xplane.reduce_trace(TRACE, WINDOW)
    assert r["idle_gaps"] == [
        ("bench/window/sync", pytest.approx(55e-6)),     # 115..170
        ("bench/window/traced", pytest.approx(30e-6)),   # 0..30
        ("bench/window/sync", pytest.approx(20e-6)),     # 220..240
        ("bench/window/traced", pytest.approx(10e-6)),   # 60..70
        ("bench/window/traced", pytest.approx(5e-6))]    # 90..95
    assert (r["busy_s"] + sum(s for _n, s in r["idle_gaps"])
            == pytest.approx(r["window_s"]))


def test_a_trace_without_a_device_plane_gives_nothing(tmp_path):
    from lib.harness import load_module
    mk = load_module(os.path.join(HERE, "make_synthetic_trace.py"))
    field, host = mk.field, mk.host
    p = tmp_path / "host_only.xplane.pb"
    p.write_bytes(field(1, host))
    assert xplane.reduce_trace(str(p), WINDOW) is None


def test_rows_min_takes_the_smaller_child():
    import numpy as np
    tree = {"num_leaves": 3, "left_child": np.array([1, -1]),
            "right_child": np.array([-3, -2]),
            "internal_count": np.array([100, 70]),
            "leaf_count": np.array([60, 10, 30])}
    # root 100, split 0: min(70, 30), split 1: min(60, 10)
    assert work.rows_min(tree) == 100 + 30 + 10
    assert work.hist_bytes([tree], n_features=13) == 140 * 13 + 140 * 8
    with pytest.raises(KeyError):
        work.peaks("cpu")
