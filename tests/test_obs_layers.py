"""The layers named where they run (obs.LAYERS / obs.scope / obs.span)
and the program's own work counters.

- the device programs carry ``lgbm/<layer>/<phase>`` scopes from ONE
  table, on the path they take and nowhere else;
- ``obs.span`` is a profiler annotation on every call, so a dump taken
  with everything off holds the program's spans, and the reader
  (obs/trace_attr.py) joins device ops to scopes from the dump alone;
- the grower counts its own work (calls, slots, columns), always kept
  and labelled by the program that grew the trees;
- none of it changes a model byte.
"""
import gzip
import os
import re
import shutil
import sys

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import obs
from lightgbm_tpu.obs import trace_attr

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO_ROOT, "benchmark")
CHIP_DUMP = os.path.join(os.path.dirname(__file__), "data",
                         "chip_two_chunks.xplane.pb.gz")

GOSS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.5,
        "data_sample_strategy": "goss", "tpu_fuse_iters": 2,
        "use_quantized_grad": True, "verbosity": -1}


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _data(n=6000, f=8, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def _train(params, rounds, n=6000, **kw):
    X, y = _data(n)
    return lgb.train(dict(GOSS, **params), lgb.Dataset(X, label=y),
                     num_boost_round=rounds, **kw)


def _counter(name, **labels):
    m = obs.registry().get(name, **labels)
    return None if m is None else m.value


def _bench_lib():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from lib import reference, work, xplane
    return reference, work, xplane


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------
def test_scope_refuses_a_name_outside_the_table():
    assert set(obs.LAYERS.values()) <= {"engine", "grower", "ingest"}
    with pytest.raises(KeyError):
        obs.scope("engine/no_such_phase")
    with obs.scope("engine/gradients"):
        pass


def test_chunk_program_carries_the_scopes_of_its_path_and_no_other():
    """The lowered sampled chunk program of a small GOSS run: every
    scope of obs.LAYERS its path takes is there, and no ``lgbm/`` name
    that is not in the table."""
    bst = _train({}, rounds=4, n=40000, keep_training_booster=True)
    eng = bst.engine
    assert eng._use_goss_compact and eng.can_fuse_iters()
    chunk = eng._make_chunk(True)
    keys = jax.numpy.zeros((2, 2), jax.numpy.uint32)
    text = jax.jit(chunk).lower(eng.score, keys).as_text(debug_info=True)
    found = {m[len("lgbm/"):] for m in
             re.findall(r"lgbm/[\w.\-]+/[\w.\-]+", text)}
    assert found <= set(obs.LAYERS), found - set(obs.LAYERS)
    on_path = {"engine/gradients", "engine/goss_sample",
               "engine/goss_compact", "engine/score_update",
               "grower/histogram", "grower/split_search",
               "grower/partition", "grower/leaf_values"}
    assert on_path <= found, on_path - found
    # not on this path: no valid set, and ingest is another program
    assert not found & {"engine/valid_update", "ingest/assign"}


def test_ingest_program_carries_its_scope():
    from lightgbm_tpu.ops import ingest
    X, y = _data(10000)
    lgb.Dataset(X, label=y, params={"tpu_ingest_device": True,
                                    "verbosity": -1}).construct()
    jnp = jax.numpy
    f32, i32 = jnp.float32, jnp.int32
    args = (jnp.zeros((64, 8), f32), jnp.zeros((8, 4), f32),
            jnp.ones(8, i32), jnp.zeros(8, i32), jnp.zeros(8, i32),
            jnp.ones(8, i32), jnp.zeros(8, bool), jnp.zeros((8, 1), i32),
            jnp.zeros((8, 1), i32))
    text = ingest._ASSIGN_JIT.lower(
        *args, out_dtype=jnp.uint8, emit_transposed=True,
        cat_cols=()).as_text(debug_info=True)
    assert "lgbm/ingest/assign" in text


# ---------------------------------------------------------------------------
# work counters
# ---------------------------------------------------------------------------
def test_cols_needed_is_the_benchmarks_count_and_calls_are_exact():
    """hist.cols_needed equals benchmark/lib/work.py's count on the
    same trees; with tpu_leaf_batch=1 a tree makes one call for its
    root and one a split."""
    reference, work, _ = _bench_lib()
    bst = _train({"tpu_leaf_batch": 1}, rounds=6)
    trees = reference.parse_model(bst.model_to_string())
    assert len(trees) == 6
    needed = (_counter("hist.cols_needed", sampled=0)
              + _counter("hist.cols_needed", sampled=1))
    assert needed == sum(work.rows_min(t) for t in trees)
    calls = (_counter("hist.calls", sampled=0)
             + _counter("hist.calls", sampled=1))
    assert calls == sum(int(t["num_leaves"]) for t in trees)
    # one slot a call, and every slot held a leaf
    for s in (0, 1):
        assert _counter("hist.leaf_slots", sampled=s) \
            == _counter("hist.calls", sampled=s) \
            == _counter("hist.leaf_slots_filled", sampled=s)


@pytest.mark.parametrize("extra", [
    {"tpu_leaf_batch": 4},
    {"tpu_leaf_batch": 2, "tpu_hist_partition": "true"},
    {"tpu_leaf_batch": 4, "data_sample_strategy": "bagging"},
], ids=["goss", "goss-partition", "plain"])
def test_cols_scanned_is_at_least_cols_needed(extra):
    _train(extra, rounds=5)
    for s in (0, 1):
        scanned = _counter("hist.cols_scanned", sampled=s)
        if scanned is None:
            assert s == 1 and extra.get("data_sample_strategy")
            continue
        assert scanned >= _counter("hist.cols_needed", sampled=s) > 0
        assert _counter("hist.leaf_slots", sampled=s) \
            >= _counter("hist.leaf_slots_filled", sampled=s) > 0
        assert _counter("hist.onehot_elems", sampled=s) \
            == scanned * 8 * 256


def test_onehot_elems_counts_the_rows_the_kernel_builds(pallas_path):
    """The Pallas side of the line above: one-hot rows only for the bins
    a column has. The counter reads the layout function the kernel
    builds its one-hot from, so the two cannot part."""
    from lightgbm_tpu.ops.pallas_histogram import onehot_layout
    X, y = _data()
    X[:, 3] = np.floor(3 * X[:, 3])          # a short count column
    X[:, 6] = X[:, 6] > 0                    # a flag
    bst = lgb.train(dict(GOSS, tpu_leaf_batch=4), lgb.Dataset(X, label=y),
                    num_boost_round=5)
    cfg = bst.engine.grow_cfg
    assert cfg.use_pallas
    assert cfg.hist_col_bins == tuple(
        int(b) for b in bst.engine.train_set.feature_num_bins())
    rows = onehot_layout(cfg.hist_col_bins, cfg.num_bins).onehot_rows
    dense = len(cfg.hist_col_bins) * cfg.num_bins
    assert sum(cfg.hist_col_bins) <= rows < dense == 8 * 256
    for s in (0, 1):
        assert _counter("hist.onehot_elems", sampled=s) \
            == _counter("hist.cols_scanned", sampled=s) * rows > 0


def test_counters_are_kept_with_obs_off_and_carry_sampled():
    """learning_rate 0.5: GOSS starts at iteration 2, so of 6 rounds
    two trees come from the un-sampled program and four from the
    sampled one; per step and per chunk alike."""
    assert not obs.any_enabled()
    n = 40000            # enough rows for the compacted buffer to engage
    bst = _train({"tpu_leaf_batch": 1}, rounds=6, n=n)
    assert bst.engine._use_goss_compact and not obs.any_enabled()
    assert _counter("train.iterations") is None       # gated, as before
    n_leaves = [t.num_leaves for t in bst.engine.models]
    assert _counter("hist.calls", sampled=0) == sum(n_leaves[:2])
    assert _counter("hist.calls", sampled=1) == sum(n_leaves[2:])
    assert _counter("goss.rows_in") == 4 * n
    assert _counter("goss.rows_kept") == 4 * (int(n * 0.2) + int(n * 0.1))
    # two counting selects' reads of the rows, a sampled iteration
    from lightgbm_tpu.ops.select import PASSES
    assert _counter("goss.select_passes") == 4 * 2 * PASSES
    # the compacted buffer is shorter than the table: fewer columns a
    # call under sampling
    per_call = [_counter("hist.cols_scanned", sampled=s)
                / _counter("hist.calls", sampled=s) for s in (0, 1)]
    assert per_call[1] < per_call[0] == bst.engine.data.n_pad


def test_compact_counters_count_the_groups_a_block_fills():
    """``compact.*``: the sampled program's one compaction an iteration,
    by the plan's own account. GOSS keeps 0.3 of the rows, so a block
    fills under half of the destination groups its window has."""
    import math
    n = 40000
    bst = _train({"tpu_leaf_batch": 1}, rounds=6, n=n)
    eng = bst.engine
    assert eng._use_goss_compact
    R_c = math.gcd(1024, eng.grow_cfg.rows_per_block)
    blocks = _counter("compact.blocks", sampled=1)
    assert blocks == 4 * (eng.data.n_pad // R_c)
    rows = _counter("compact.onehot_rows", sampled=1)
    assert rows % 128 == 0
    assert _counter("goss.rows_kept") <= rows < blocks * (R_c + 128) / 2
    # the un-sampled program compacts nothing
    assert _counter("compact.blocks", sampled=0) is None
    assert _counter("compact.onehot_rows", sampled=0) is None


def test_ingest_counters_are_kept_with_obs_off():
    X, y = _data(10000)
    ds = lgb.Dataset(X, label=y, params={
        "tpu_ingest_device": True, "tpu_ingest_chunk_rows": 4096,
        "verbosity": -1}).construct()
    assert ds.device_ingested() is not None
    assert _counter("ingest.chunks") == 3
    assert _counter("ingest.cells") == 10000 * 8
    assert _counter("ingest.h2d_bytes") == 3 * 4096 * 8 * 4


# ---------------------------------------------------------------------------
# the profiler's dump, read by the program's reader
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cpu_dump(tmp_path_factory):
    """A CPU profiler session round two sampled chunks, obs off. The
    chunks run inside ``train/fused`` as under ``lgb.train``, so the
    moment between them belongs to a span however long the host is
    held there (a loaded machine stretched it past 1 ms, unnamed)."""
    d = str(tmp_path_factory.mktemp("prof"))
    bst = _train({"tpu_leaf_batch": 4}, rounds=4,
                 keep_training_booster=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with obs.span("train/fused"):
            bst.engine.train_chunk(4)
            jax.block_until_ready(bst.engine.score)
    finally:
        jax.profiler.stop_trace()
    return d


def test_session_holds_the_programs_spans_nested(cpu_dump):
    with open(trace_attr.newest_xplane(cpu_dump), "rb") as f:
        planes = trace_attr.parse_xspace(f.read())
    notes = trace_attr._host_annotations(planes, "lgbm/")
    by = {}
    for name, s, e in notes:
        by.setdefault(name, []).append((s, e))
    assert len(by["lgbm/train/fused_chunk"]) == 2
    for name in ("lgbm/train/dispatch", "lgbm/train/fetch_trees",
                 "lgbm/train/append_trees"):
        assert len(by[name]) == 2, name
        for s, e in by[name]:
            assert any(s0 <= s and e <= e0
                       for s0, e0 in by["lgbm/train/fused_chunk"]), name


def test_select_ops_are_joined_to_goss_sample(cpu_dump):
    res = trace_attr.attribute(cpu_dump, iters=4)
    assert res["found"] and res["window"] == "lgbm/train/fused"
    # GOSS's thresholds are counted, not sorted (PR 27): its scope
    # holds the select's compare-and-count reduces and no sort
    sample = [o["name"] for o in res["ops"]
              if o["scope"] == "lgbm/engine/goss_sample"]
    assert any("reduce" in name for name in sample)
    assert not any(name.startswith("sort") for name in sample)
    scopes = {lay["scope"] for lay in res["layers"]}
    assert {"lgbm/grower/histogram", "lgbm/engine/goss_sample",
            "lgbm/grower/partition"} <= scopes
    # the gaps between the chunks belong to the program's own spans
    assert all(g["name"].startswith("lgbm/train/")
               for g in res["idle_gaps"] if g["ms"] > 1.0)


def _chip_dump(tmp_path) -> str:
    p = tmp_path / "chip.xplane.pb"
    with gzip.open(CHIP_DUMP, "rb") as src, open(p, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(p)


def test_layers_and_unscoped_add_up_to_busy(cpu_dump, tmp_path):
    for d in [cpu_dump] + ([_chip_dump(tmp_path)]
                           if os.path.exists(CHIP_DUMP) else []):
        res = trace_attr.attribute(d)
        assert res["found"], res
        assert sum(lay["ms"] for lay in res["layers"]) \
            == pytest.approx(res["busy_ms"], rel=1e-9)
        assert res["busy_ms"] <= res["wall_ms"]
        assert sum(lay["share"] for lay in res["layers"]) \
            == pytest.approx(1.0)


def test_program_reader_agrees_with_the_benchmarks_on_its_dump():
    _, _, xplane = _bench_lib()
    dump = os.path.join(BENCH, "testdata", "synthetic.xplane.pb")
    window = "bench/window/traced"
    theirs = xplane.reduce_trace(dump, window)
    ours = trace_attr.attribute(dump, window=window, prefix="bench/")
    assert ours["found"] and ours["n_devices"] == theirs["n_devices"]
    assert ours["busy_ms"] == pytest.approx(theirs["busy_s"] * 1e3)
    assert ours["wall_ms"] == pytest.approx(theirs["window_s"] * 1e3)
    assert {o["name"]: (pytest.approx(o["ms"]), o["calls"])
            for o in ours["ops"]} \
        == {n: (s * 1e3, c) for n, s, c in theirs["ops"]}
    assert [(g["name"], pytest.approx(g["ms"]))
            for g in ours["idle_gaps"]] \
        == [(n, s * 1e3) for n, s in theirs["idle_gaps"]]
    # where the benchmark's reader finds no window it gives nothing;
    # so does the program's
    assert xplane.reduce_trace(dump, "bench/nope") is None
    assert not trace_attr.attribute(dump, window="bench/nope",
                                    prefix="bench/")["found"]


@pytest.mark.skipif(not os.path.exists(CHIP_DUMP),
                    reason="no recorded chip dump")
def test_chip_dump_reduces_by_layer(tmp_path):
    """A dump of two small sampled chunks recorded on a v5e chip
    (benchmark/tests/trace_chip.py, cut by tests/data/cut_xplane.py):
    the kernels keep their pinned names inside their scopes, the
    sorts are GOSS's, and little is left unscoped."""
    p = _chip_dump(tmp_path)
    res = trace_attr.attribute(p)
    assert res["found"] and "/device:TPU" in res["device_plane"]
    scope = {}
    for o in res["ops"]:                 # longest first
        scope.setdefault(o["name"].split(".")[0], o["scope"])
    assert scope["multi_leaf_histogram"] == "lgbm/grower/histogram"
    assert scope["compact_rows"] == "lgbm/engine/goss_compact"
    assert scope["sort"] == "lgbm/engine/goss_sample"
    # the chip's dump names an op's scope twice: on the event's own
    # metadata (tf_op) and in the embedded HLO; the two agree
    with open(p, "rb") as f:
        planes = trace_attr.parse_xspace(f.read())
    dev = next(pl for pl in planes if "/device:" in pl["name"])
    own = trace_attr._own_scopes(dev)
    (hlo,) = trace_attr.hlo_scopes(planes).values()
    assert len(own) > 400
    assert all((hlo.get(trace_attr._short_name(n)) or "unscoped") == sc
               for n, sc in own.items())
    lay = {x["scope"]: x["share"] for x in res["layers"]}
    assert lay.get("unscoped", 0.0) < 0.05
    assert set(lay) - {"unscoped"} <= {"lgbm/" + k for k in obs.LAYERS}
    assert all(g["name"].startswith("lgbm/")
               for g in res["idle_gaps"] if g["ms"] > 1.0)


# ---------------------------------------------------------------------------
# nothing moves a model
# ---------------------------------------------------------------------------
def test_model_is_byte_equal_with_a_session_open(tmp_path):
    plain = _train({"tpu_leaf_batch": 4}, rounds=6).model_to_string()
    traced = _train({"tpu_leaf_batch": 4,
                     "tpu_profile_dir": str(tmp_path / "prof")},
                    rounds=6).model_to_string()
    assert traced == plain
    # the operator's flow fed the by-layer gauges from that dump
    names = {(m.name, m.labels.get("scope"))
             for m in obs.registry().metrics()}
    assert ("train.layer_ms", "lgbm/grower/histogram") in names
    obs.reset()
    obs.enable(metrics=True, trace=True)
    on = _train({"tpu_leaf_batch": 4}, rounds=6).model_to_string()
    assert on == plain
