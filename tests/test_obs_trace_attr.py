"""Trace attribution (obs/trace_attr.py + scripts/trace_attr.py).

The attribution pipeline is pure parsing — so it is pinned against a
SYNTHETIC XSpace dump encoded with the same protobuf wire format the
reader decodes: known per-op durations in, exact ``copy_share`` /
``wall_busy_gap_ms`` out; busy is a union of leaf ops (a ``while``
and its body are not counted twice) and never passes the window.
tests/test_obs_layers.py holds the join to scopes against real dumps.
Also covers the degradation contract (a
host-only trace — the CPU backend's shape — must report "nothing to
attribute", never crash the run that produced it), the gauge feed into
the obs registry, and the CLI.
"""
import json
import os
import subprocess
import sys

import pytest

from lightgbm_tpu.obs.trace_attr import (aggregate_ops, attribute,
                                         newest_xplane, parse_xspace,
                                         profile_gauges)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# protobuf wire-format ENCODER (test-side twin of the module's reader)
# ---------------------------------------------------------------------------
def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b = x & 0x7F
        x >>= 7
        if x:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num: int, payload: bytes) -> bytes:
    return _varint((num << 3) | 2) + _varint(len(payload)) + payload


def _vfield(num: int, value: int) -> bytes:
    return _varint(num << 3) + _varint(value)


def _event(mid: int, offset_ps: int, duration_ps: int) -> bytes:
    return (_vfield(1, mid) + _vfield(2, offset_ps)
            + _vfield(3, duration_ps))


def _line(name: str, timestamp_ns: int, events) -> bytes:
    buf = _field(2, name.encode()) + _vfield(3, timestamp_ns)
    for ev in events:
        buf += _field(4, ev)
    return buf


def _metadata_entry(mid: int, name: str) -> bytes:
    meta = _vfield(1, mid) + _field(2, name.encode())
    return _vfield(1, mid) + _field(2, meta)


def _plane(name: str, lines, metadata) -> bytes:
    buf = _field(2, name.encode())
    for ln in lines:
        buf += _field(3, ln)
    for entry in metadata:
        buf += _field(4, entry)
    return buf


def _synthetic_xspace() -> bytes:
    """One host plane (must be ignored) + one device plane whose
    "XLA Ops" line carries: fusion.1 60 ms, copy.3 twice at 12.5 ms,
    copy-start.4 10 ms, dynamic-slice.9 5 ms -> busy 100 ms, copy
    35 ms, copy_share 0.35."""
    MS = 1_000_000_000  # ps per ms
    host = _plane("/host:CPU", [
        _line("python threads", 0, [_event(1, 0, 5 * MS)]),
    ], [_metadata_entry(1, "HostWork")])
    dev = _plane("/device:TPU:0 (fake)", [
        _line("XLA Ops", 1_000, [
            _event(1, 0, 60 * MS),
            _event(2, 60 * MS, 12_500_000_000),
            _event(2, 72_500_000_000, 12_500_000_000),
            _event(3, 85 * MS, 10 * MS),
            _event(4, 95 * MS, 5 * MS),
        ]),
        _line("Steps", 0, []),
    ], [
        _metadata_entry(1, "fusion.1"),
        _metadata_entry(2, "%copy.3"),
        _metadata_entry(3, "copy-start.4"),
        _metadata_entry(4, "dynamic-slice.9"),
    ])
    return _field(1, host) + _field(1, dev)


@pytest.fixture()
def dump_dir(tmp_path):
    # jax.profiler's layout: <dir>/plugins/profile/<ts>/<host>.xplane.pb
    d = tmp_path / "plugins" / "profile" / "2026_08_04"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_synthetic_xspace())
    return str(tmp_path)


def test_parse_and_aggregate_synthetic_dump():
    planes = parse_xspace(_synthetic_xspace())
    assert [p["name"] for p in planes] == ["/host:CPU",
                                           "/device:TPU:0 (fake)"]
    agg = aggregate_ops(planes)
    assert agg is not None
    assert agg["device_plane"] == "/device:TPU:0 (fake)"
    # name resolution through the metadata map; an op is its short
    # name and its scope (none in this dump)
    assert agg["ops"][("copy.3", "unscoped")] == [25_000_000_000.0, 2]
    assert agg["busy_ps"] == 100_000_000_000
    # no lgbm/ annotation in the dump: the window is first op to last
    assert agg["window_ps"] == 100_000_000_000
    # copy.3 + copy-start.4 count as copies; dynamic-slice does not
    assert agg["copy_ps"] == 35_000_000_000


def test_attribute_shares_and_gap(dump_dir):
    res = attribute(dump_dir, iters=10, wall_ms=150.0)
    assert res["found"]
    assert res["source"].endswith("host.xplane.pb")
    assert res["busy_ms"] == pytest.approx(100.0)
    assert res["copy_share"] == pytest.approx(0.35)
    # (150 wall - 100 busy) / 10 iters
    assert res["wall_busy_gap_ms"] == pytest.approx(5.0)
    # ops sorted by busy descending, share sums to 1
    assert res["ops"][0]["name"] == "fusion.1"
    assert sum(op["share"] for op in res["ops"]) == pytest.approx(1.0)


def test_comm_share_buckets_collectives(tmp_path):
    """Collectives — sync forms and XLA's async -start/-done splits —
    bucket into ``comm_share``; compute fusions do not, and the comm
    and copy buckets stay disjoint."""
    MS = 1_000_000_000
    dev = _plane("/device:TPU:0", [
        _line("XLA Ops", 0, [
            _event(1, 0, 55 * MS),
            _event(2, 55 * MS, 30 * MS),
            _event(3, 85 * MS, 10 * MS),
            _event(4, 95 * MS, 5 * MS),
        ]),
    ], [
        _metadata_entry(1, "fusion.2"),
        _metadata_entry(2, "%all-reduce.7"),
        _metadata_entry(3, "all-reduce-start.9"),
        _metadata_entry(4, "copy.11"),
    ])
    f = tmp_path / "comm.xplane.pb"
    f.write_bytes(_field(1, dev))
    res = attribute(str(f))
    assert res["found"]
    assert res["busy_ms"] == pytest.approx(100.0)
    # all-reduce.7 + all-reduce-start.9; neither fusion nor copy
    assert res["comm_ms"] == pytest.approx(40.0)
    assert res["comm_share"] == pytest.approx(0.40)
    assert res["copy_share"] == pytest.approx(0.05)


def test_newest_xplane_picks_latest(tmp_path):
    d = tmp_path / "plugins" / "profile"
    d.mkdir(parents=True)
    old = d / "old.xplane.pb"
    new = d / "new.xplane.pb"
    old.write_bytes(b"")
    new.write_bytes(b"")
    os.utime(old, (1, 1))
    os.utime(new, (2, 2))
    assert newest_xplane(str(tmp_path)) == str(new)
    assert newest_xplane(str(tmp_path / "missing")) is None


def test_host_only_trace_degrades_not_crashes(tmp_path):
    """The CPU-backend shape: a dump whose only plane is host threads
    must come back found=False with a reason — the run that produced
    the trace keeps going."""
    MS = 1_000_000_000
    host_only = _field(1, _plane("/host:CPU", [
        _line("python threads", 0, [_event(1, 0, MS)]),
    ], [_metadata_entry(1, "HostWork")]))
    f = tmp_path / "host.xplane.pb"
    f.write_bytes(host_only)
    res = attribute(str(f))
    assert not res["found"]
    assert "no device plane" in res["reason"]
    # and a truncated/garbage dump reports, never raises
    g = tmp_path / "garbage.xplane.pb"
    g.write_bytes(b"\x0a\xff\xff\xff")
    assert not attribute(str(g))["found"]


def test_busy_is_a_union_of_leaves_and_never_passes_the_window(tmp_path):
    """A scan's ``while`` encloses its body: the old reader summed
    durations, counted both, and could report more busy than wall.
    Here the while keeps only its self time, busy is the leaves' union
    cut to the window (a named host annotation), and the leading and
    trailing host waits show as idle gaps."""
    US = 1_000_000
    dev = _plane("/device:TPU:0", [_line("XLA Ops", 0, [
        _event(1, 20 * US, 100 * US),      # while.1  20..120, encloses
        _event(2, 30 * US, 30 * US),       #   fusion.2  30..60
        _event(3, 70 * US, 40 * US),       #   copy.3    70..110
        _event(2, 150 * US, 100 * US),     # fusion.2 150..250, past the end
    ])], [_metadata_entry(1, "%while.1 = (s32[]) while(...)"),
          _metadata_entry(2, "fusion.2"), _metadata_entry(3, "copy.3")])
    host = _plane("/host:CPU", [_line("python", 0, [
        _event(1, 0, 200 * US),            # lgbm/train/fused_chunk 0..200
        _event(2, 120 * US, 80 * US),      #   lgbm/train/fetch_trees
    ])], [_metadata_entry(1, "lgbm/train/fused_chunk"),
          _metadata_entry(2, "lgbm/train/fetch_trees")])
    f = tmp_path / "nested.xplane.pb"
    f.write_bytes(_field(1, dev) + _field(1, host))
    res = attribute(str(f))
    assert res["window"] == "lgbm/train/fused_chunk"
    assert res["wall_ms"] == pytest.approx(0.2)
    # leaves inside the window: 30..60, 70..110, 150..200
    assert res["busy_ms"] == pytest.approx(0.12)
    assert res["busy_ms"] <= res["wall_ms"]
    ops = {o["name"]: (o["ms"], o["calls"]) for o in res["ops"]}
    assert ops["while.1"] == (pytest.approx(0.03), 1)    # 100 - 30 - 40
    assert ops["fusion.2"] == (pytest.approx(0.13), 2)   # self, not cut
    assert [(lay["scope"], lay["ms"]) for lay in res["layers"]] \
        == [("unscoped", pytest.approx(0.12))]
    gaps = [(g["name"], g["ms"]) for g in res["idle_gaps"]]
    assert gaps == [
        ("lgbm/train/fetch_trees", pytest.approx(0.04)),    # 110..150
        ("lgbm/train/fused_chunk", pytest.approx(0.03)),    # 0..30
        ("lgbm/train/fused_chunk", pytest.approx(0.01))]    # 60..70
    assert res["busy_ms"] + sum(ms for _n, ms in gaps) \
        == pytest.approx(res["wall_ms"])
    spans = {sp["name"]: (sp["ms"], sp["count"]) for sp in res["spans"]}
    assert spans["lgbm/train/fetch_trees"] == (pytest.approx(0.08), 1)
    # a window that is not in the dump is nothing to attribute
    assert not attribute(str(f), window="lgbm/train/step")["found"]


def test_profile_gauges_feed_obs_registry(dump_dir, monkeypatch):
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import metrics as obs_metrics

    # a registry this test owns: whatever ran before it in the process
    # (forced gauges of another profile, an enabled pillar) is not here
    monkeypatch.setattr(obs_metrics, "_REGISTRY",
                        obs_metrics.MetricsRegistry())
    res = profile_gauges(dump_dir, iters=10, wall_ms=150.0)
    assert res["found"]
    snap = obs.registry().snapshot()
    assert {m["name"] for m in snap["metrics"]} == {
        "train.copy_share", "train.comm_share", "train.layer_ms",
        "train.wall_busy_gap_ms"}
    # the synthetic dump names no scope: all 100 ms, 10 ms an
    # iteration, are unscoped
    (layer,) = [m for m in snap["metrics"] if m["name"] == "train.layer_ms"]
    assert layer["labels"] == {"scope": "unscoped"}
    assert layer["value"] == pytest.approx(10.0)
    vals = {m["name"]: m["value"] for m in snap["metrics"]
            if not m.get("labels")}
    assert vals["train.copy_share"] == pytest.approx(0.35)
    # the synthetic dump has no collectives: comm_share feeds as 0,
    # not as a missing gauge
    assert vals["train.comm_share"] == pytest.approx(0.0)
    assert vals["train.wall_busy_gap_ms"] == pytest.approx(5.0)
    # degradation feeds nothing and reports why
    missing = profile_gauges(os.path.join(dump_dir, "nope"))
    assert not missing["found"]


def test_cli_text_and_json(dump_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "trace_attr.py"),
         dump_dir, "--iters", "10", "--wall-ms", "150", "--json"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout)
    assert res["copy_share"] == pytest.approx(0.35)
    # text mode renders the table + the gap line
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "trace_attr.py"),
         dump_dir, "--iters", "10", "--wall-ms", "150"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert out2.returncode == 0, out2.stderr
    assert "%copy (loop-state copies)" in out2.stdout
    assert "5.00 ms/iter" in out2.stdout
    assert "unscoped" in out2.stdout and "device busy" in out2.stdout
    # nothing to attribute -> exit 3 (the CPU-trace contract)
    out3 = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "trace_attr.py"),
         os.path.join(dump_dir, "missing")],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert out3.returncode == 3
