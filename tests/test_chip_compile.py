"""Compile the main path's TPU-only programs for a DESCRIBED v5e chip.

The suite pins the CPU, and the program picks its fast paths by
``jax.default_backend() == "tpu"``: the Pallas histogram and compaction
kernels, the partition move, the ingest chunk program, the one-hot
forest traversal. None of that runs here. The TPU compiler is
installed, though, and compiles for a chip that is described and not
attached (``/opt/skills/guides/on-chip-measurement`` section 2). These
tests hand it the real widths — what interpret mode cannot show: tiling,
VMEM budgets, whether a kernel can sit inside a manual-axes region — so
every later PR is checked against the chip's compiler at no chip time.
A compile that passes is not a chip run; ``chip_smoke.py`` is.

Rules this file keeps (the guide says why): the topology is described
inside a module-scoped, non-autouse fixture and nowhere at import time;
everything built from it is built in a fixture or a test; all cases
live in this ONE file (one xdist worker loads libtpu); the persistent
compilation cache is off around them (a described-chip executable is
written but cannot be read back without the chip).
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

# the benchmark cells' per-column bin counts
from test_hist_layout import AIRLINE as AIRLINE_BINS, CRITEO as CRITEO_BINS

N = 1 << 20          # Higgs-1M rows
SLAB = 1 << 17       # one kernel slab


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:   # no libtpu here, or another holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: "
                        f"{type(e).__name__}: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_tpu(monkeypatch):
    """Code under trace that asks ``jax.default_backend()`` sees the CPU
    here and would take its CPU branch (ops/split.py's prefix sum):
    steer it in the test, never through an option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(lowered, temp_limit=8 * 2**30):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # one v5e chip has 16 GiB; a program's own temporaries past half of
    # it would leave no room for the data it works on
    assert mem.temp_size_in_bytes < temp_limit, mem
    return compiled.as_text()


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------
@pytest.mark.parametrize("int_mode", [False, True],
                         ids=["f32", "int8"])
@pytest.mark.parametrize("col_bins,B,K", [
    ((256,) * 28, 256, 32),    # Higgs: one block of full columns
    ((256,) * 136, 256, 32),   # MSLR width: feature-blocked grid
    ((64,) * 28, 64, 32),      # narrow bins
    (AIRLINE_BINS, 256, 32),   # ragged: 1,952 one-hot rows, one block
    (CRITEO_BINS, 256, 32),    # ragged: 7,520 rows in ONE block
    ((256, 1, 1, 1), 256, 32),  # narrow table with 1-bin padding columns
    (((256,) * 12 + (40,) * 4) * 2 + (256,) * 8, 256, 8),  # ragged grid
], ids=["higgs", "mslr", "narrow", "airline", "criteo", "padding",
        "ragged_grid"])
def test_multi_leaf_histogram_compiles(one_chip, col_bins, B, K, int_mode):
    from lightgbm_tpu.ops.pallas_histogram import (multi_leaf_histogram,
                                                   onehot_layout)
    s = functools.partial(_sds, one_chip)
    # learner/serial.py's caps
    R = 4096 if onehot_layout(col_bins, B).n_fb == 1 else 2048
    text = _compiled_text(multi_leaf_histogram.lower(
        s((len(col_bins), SLAB), jnp.int8), s((3, SLAB), jnp.float32),
        s((SLAB,), jnp.int32), s((K,), jnp.int32),
        num_bins=B, col_bins=col_bins, rows_per_block=R,
        int_mode=int_mode))
    assert "tpu_custom_call" in text


# Higgs and Bosch widths, then the benchmark cells' (airline, criteo:
# their four value channels are g, h and GOSS's two masks)
@pytest.mark.parametrize("F,C", [(28, 3), (200, 4), (13, 4), (39, 4)])
def test_compact_rows_compiles(one_chip, F, C):
    from lightgbm_tpu.ops.compact import (compact_rows,
                                          compaction_out_cols)
    s = functools.partial(_sds, one_chip)
    R = 1024
    out_cols = compaction_out_cols(int(SLAB * 0.3), R, 4096)
    per_block = s((SLAB // R,), jnp.int32)
    text = _compiled_text(compact_rows.lower(
        s((F, SLAB), jnp.int8), s((C, SLAB), jnp.float32),
        s((SLAB,), jnp.int32), per_block, per_block, per_block,
        out_cols=out_cols, rows_per_block=R))
    assert "tpu_custom_call" in text


def test_compact_rows_block_scalars_fit_smem(one_chip):
    """The kernel keeps two int32 words a block in SMEM (1 MB in all),
    so Airline's full 115M rows, 112,305 blocks of 1,024, still
    compile; a third word a block would not fit."""
    from lightgbm_tpu.ops.compact import (compact_rows,
                                          compaction_out_cols)
    s = functools.partial(_sds, one_chip)
    R, nb = 1024, 112_305
    n = nb * R
    per_block = s((nb,), jnp.int32)
    text = _compiled_text(compact_rows.lower(
        s((13, n), jnp.int8), s((4, n), jnp.float32), s((n,), jnp.int32),
        per_block, per_block, per_block,
        out_cols=compaction_out_cols(int(n * 0.3) + 8192, R, 4096),
        rows_per_block=R))
    assert "tpu_custom_call" in text


def _route_nodes(s, n_nodes, W):
    from lightgbm_tpu.ops.route import RouteNodes
    col = s((n_nodes,), jnp.int32)
    return RouteNodes(count=s((), jnp.int32), feature=col, threshold=col,
                      flip_bin=col, leaf=col, is_cat=col if W else None,
                      bitset=s((n_nodes, W), jnp.int32) if W else None)


@pytest.mark.parametrize("F,W,n", [
    (13, 0, 57_503_744),   # the airline cell's table: thresholds only
    (39, 8, 22_921_216),   # the click-log cell's: 256-bin bitsets
], ids=["airline", "criteo"])
def test_route_rows_compiles(one_chip, F, W, n):
    """The table routed through a finished tree of 127 leaves, at the
    cells' own lengths (airline's is no multiple of the kernel's block:
    its last block is ragged) and widths."""
    from lightgbm_tpu.ops.route import route_rows
    s = functools.partial(_sds, one_chip)
    text = _compiled_text(route_rows.lower(
        s((F, n), jnp.int8), _route_nodes(s, 126, W)),
        temp_limit=2**28)       # ids out and nothing else
    assert "%route_rows." in text


@pytest.mark.parametrize("kernel", ["multi_leaf_histogram",
                                    "compact_rows", "partition_move"])
def test_pallas_call_lowers_with_its_pinned_name(one_chip, kernel):
    """The benchmark's kernel metrics match the device op by name
    (``^multi_leaf_histogram(\\.\\d+)?$``, ``^compact_rows(...)``,
    ``^partition_move(...)``). The name is the ``pallas_call``'s own
    ``name=``: called from a function of another name, the kernel still
    lowers and compiles under it. The leaf-ordered partition's mover is
    the compaction kernel under a name of its own, so that a trace tells
    its two passes from GOSS's compaction."""
    from lightgbm_tpu.ops import compact, pallas_histogram, partition
    s = functools.partial(_sds, one_chip)
    if kernel == "partition_move":
        def renamed(*a):
            return partition.move_cols_tpu(*a, rows_per_block=1024)
        args = (s((13, SLAB), jnp.int8), s((4, SLAB), jnp.float32),
                s((SLAB,), jnp.bool_), s((), jnp.int32))
    elif kernel == "multi_leaf_histogram":
        def renamed(*a):
            return pallas_histogram.multi_leaf_histogram.__wrapped__(
                *a, num_bins=256, rows_per_block=4096, int_mode=True)
        args = (s((13, SLAB), jnp.int8), s((3, SLAB), jnp.float32),
                s((SLAB,), jnp.int32), s((8,), jnp.int32))
    else:
        out_cols = compact.compaction_out_cols(int(SLAB * 0.3), 1024, 4096)

        def renamed(*a):
            return compact.compact_rows.__wrapped__(
                *a, out_cols=out_cols, rows_per_block=1024)
        args = (s((13, SLAB), jnp.int8), s((4, SLAB), jnp.float32),
                s((SLAB,), jnp.int32), s((SLAB // 1024,), jnp.int32),
                s((SLAB // 1024,), jnp.int32),
                s((SLAB // 1024,), jnp.int32))
    lowered = jax.jit(renamed).lower(*args)
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    text = _compiled_text(lowered)
    assert f"%{kernel}." in text
    if kernel == "partition_move":
        assert "%compact_rows." not in text


# ---------------------------------------------------------------------
# the tree grower at Higgs-1M: n=2^20, F=28, B=256, L=127, Kb=32
# ---------------------------------------------------------------------
def _grow_cfg(**kw):
    from lightgbm_tpu.learner.serial import GrowConfig
    return GrowConfig(num_leaves=127, num_bins=256, rows_per_block=4096,
                      leaf_batch=32, use_pallas=True, **kw)


GROW_VARIANTS = {
    "plain": {},
    "int_hist": {"int_hist": True},
    "partition": {"int_hist": True, "partition": True,
                  "part_rpb": 1024},
    "hist_compact": {"int_hist": True, "hist_compact": True},
    # the click-log cell's program: 13 count + 26 categorical columns
    # at their own bin counts (one block of 7,520 one-hot rows),
    # set-split search and bitset routing
    "hist_compact_cat": {"int_hist": True, "hist_compact": True,
                         "has_categorical": True,
                         "cat_positions": tuple(range(13, 39)),
                         "hist_col_bins": CRITEO_BINS},
}


@pytest.mark.parametrize("variant", sorted(GROW_VARIANTS))
def test_grow_tree_compiles(one_chip, as_tpu, variant):
    from lightgbm_tpu.learner.serial import grow_tree
    from lightgbm_tpu.ops.compact import compaction_out_cols
    s = functools.partial(_sds, one_chip)
    cfg = _grow_cfg(**GROW_VARIANTS[variant])
    F = 39 if cfg.has_categorical else 28
    vals = s((N, 3), jnp.float32)
    kw = {"bins_t": s((F, N), jnp.int8)}
    if cfg.has_categorical:
        kw["is_cat"] = s((F,), jnp.bool_)
    if cfg.int_hist:
        kw["chan_scale"] = s((3,), jnp.float32)
    if cfg.hist_compact:
        # GOSS at top_rate + other_rate = 0.3 (boosting/gbdt.py)
        n_c = compaction_out_cols(int(np.ceil(N * 0.3)) + 8192, 1024,
                                  cfg.rows_per_block)
        vals = s((n_c, 3), jnp.float32)
        kw["compact"] = (s((n_c, F), jnp.uint8), s((F, n_c), jnp.int8),
                         vals)
    text = _compiled_text(grow_tree.lower(
        s((N, F), jnp.uint8), vals, s((F,), jnp.int32),
        s((F,), jnp.bool_), s((F,), jnp.bool_), cfg, **kw))
    assert "tpu_custom_call" in text
    # the chip's compiler keeps the grower's scopes as op metadata,
    # the kernel's included
    for scope in ("histogram", "split_search", "partition",
                  "leaf_values") + (("cat_search",)
                                    if cfg.has_categorical else ()):
        assert f"lgbm/grower/{scope}" in text, scope
    kernel_line = next(ln for ln in text.splitlines()
                       if "%multi_leaf_histogram." in ln
                       and "custom-call(" in ln)
    assert "lgbm/grower/histogram" in kernel_line
    # the in-loop row -> leaf pass gives every row its split by a
    # float32 attribute matrix, [rows, 6] (thresholds) or [rows, 6 + 1 +
    # 2 x 8] (bitsets). Handed a compact buffer, the loop builds the
    # buffer's alone, and the table is routed once, after it, by the
    # route_rows kernel under the same scope; under the leaf-ordered
    # partition (since PR 36) it builds the partition's own alone, as
    # long as the table's, and routes the table after it too
    attr = f"f32[{{}},{23 if cfg.has_categorical else 6}]"
    assert (attr.format(N) in text) == (not cfg.hist_compact)
    assert ("%route_rows." in text) == (cfg.hist_compact or cfg.partition)
    if cfg.hist_compact:
        assert attr.format(n_c) in text
        route_line = next(ln for ln in text.splitlines()
                          if "%route_rows." in ln and "custom-call(" in ln)
        assert "lgbm/grower/partition" in route_line


def test_goss_compact_chunk_program_compiles(one_chip, as_tpu):
    """The program both benchmark cells run in their window: a fused
    chunk of GOSS iterations on the compact buffer (airline's 13
    columns at 131,072 rows; the engine's sizes are constants of its
    programs). ``route_rows`` sits inside it, and no float32 attribute
    matrix of the FULL table is left (the ``[n_pad, 23]`` one is what
    kept the click-log table's 45.8M rows from compiling, PERF.md §4)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    rng = np.random.default_rng(0)
    n = 1 << 17
    X = np.stack([rng.integers(0, b, n) for b in AIRLINE_BINS],
                 axis=1).astype(np.float32)
    eng = GBDT(Config({"objective": "binary", "verbosity": -1,
                       "num_leaves": 127, "use_quantized_grad": True,
                       "data_sample_strategy": "goss", "top_rate": 0.2,
                       "other_rate": 0.1}),
               lgb.Dataset(X, label=(X[:, 0] > 3).astype(np.float32)))
    assert eng._use_goss_compact and eng.use_pallas
    chunk = eng._make_chunk(True)
    program = next(c.cell_contents for c in chunk.__closure__
                   if hasattr(c.cell_contents, "lower"))
    s = functools.partial(_sds, one_chip)
    d = eng.data
    n_pad, F = d.bins.shape
    text = _compiled_text(program.lower(
        s((n_pad, F), d.bins.dtype), s((F, n_pad), jnp.int8),
        s((n_pad,), jnp.float32), None, s((n_pad, 1), jnp.float32),
        s((n_pad,), jnp.float32), s((5, 2), jnp.uint32)))
    for kernel in ("compact_rows", "multi_leaf_histogram", "route_rows"):
        assert f"%{kernel}." in text, kernel
    assert not re.search(rf"f32\[{n_pad},(6|23)\]", text)


def test_plain_chunk_program_compiles_at_the_click_rate_tables_shape(
        one_chip, as_tpu):
    """The program `criteo-tb-1700m.train-plain` runs in its window: a
    fused chunk of UNSAMPLED iterations at 255 leaves over 67 all-full
    columns under the leaf-ordered partition, at the row count the
    configuration ships (the engine is built on a small table of the same
    columns and asked for the partition by name; its programs take their
    row counts from the shapes handed). The chip's compiler has to fit it
    and leave 15% of the 15.75 GiB a v5e's programs may use: the
    configuration's rows were cut by that rule (PERF.md section 4). The
    mover's two passes are there under their own name, the table is
    routed once after the loop (`route_rows`), and GOSS's compaction is
    not there."""
    import json
    import os
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "criteo-tb-1700m.json")) as f:
        config = json.load(f)
    rng = np.random.default_rng(0)
    n, F = 1 << 17, 67
    X = rng.integers(0, 255, (n, F)).astype(np.float32)
    params = dict(config["params"], verbosity=-1, use_quantized_grad=True,
                  tpu_hist_partition="true")
    eng = GBDT(Config(params),
               lgb.Dataset(X, label=(X[:, 0] > 100).astype(np.float32)))
    assert eng.use_pallas and eng.hist_partition and eng.grow_cfg.int_hist
    assert not eng._use_goss_compact and eng.grow_cfg.num_leaves == 255
    chunk = eng._make_chunk(False)
    program = next(c.cell_contents for c in chunk.__closure__
                   if hasattr(c.cell_contents, "lower"))
    s = functools.partial(_sds, one_chip)
    rpb = eng.rows_per_block
    n_pad = -(-int(config["rows"]) // rpb) * rpb
    compiled = program.lower(
        s((n_pad, F), eng.data.bins.dtype), s((F, n_pad), jnp.int8),
        s((n_pad,), jnp.float32), None, s((n_pad, 1), jnp.float32),
        s((n_pad,), jnp.float32),
        s((int(params["tpu_fuse_iters"]), 2), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    held = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 0.85 * 15.75 * 2**30, mem
    text = compiled.as_text()
    for kernel in ("partition_move", "multi_leaf_histogram", "route_rows"):
        assert f"%{kernel}." in text, kernel
    assert "%compact_rows." not in text
    # the one float32 attribute matrix left is the partition's own pass
    assert f"f32[{n_pad},6]" in text


def test_grow_tree_compiles_under_shard_map_on_four_chips(topo, as_tpu):
    """tree_learner=data: the Pallas kernel inside a manual-axes region,
    rows sharded over a 4-device mesh of described devices, the
    histogram all-reduce put in by the grower."""
    from lightgbm_tpu.learner.serial import grow_tree
    from lightgbm_tpu.parallel.mesh import DATA_AXIS, shard_map
    mesh = Mesh(np.array(topo.devices).reshape(4), (DATA_AXIS,))
    cfg = _grow_cfg(int_hist=True, axis_name=DATA_AXIS, num_shards=4)
    rows, rep = P(DATA_AXIS), P()

    def grow(bins, vals, nb, nan, allowed, bins_t, chan_scale):
        return grow_tree(bins, vals, nb, nan, allowed, cfg,
                         bins_t=bins_t, chan_scale=chan_scale)

    def s(spec, shape, dtype):
        return _sds(NamedSharding(mesh, spec), shape, dtype)

    F = 28
    fn = jax.jit(shard_map(
        grow, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None), rep, rep, rep,
                  P(None, DATA_AXIS), rep),
        out_specs=(rep, rows), check_vma=False))
    text = _compiled_text(fn.lower(
        s(P(DATA_AXIS, None), (N, F), jnp.uint8),
        s(P(DATA_AXIS, None), (N, 3), jnp.float32),
        s(rep, (F,), jnp.int32), s(rep, (F,), jnp.bool_),
        s(rep, (F,), jnp.bool_), s(P(None, DATA_AXIS), (F, N), jnp.int8),
        s(rep, (3,), jnp.float32)))
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


# ---------------------------------------------------------------------
# the engine's GOSS sample
# ---------------------------------------------------------------------
def test_goss_sample_program_orders_no_row(one_chip):
    """``goss_masks`` at the benchmark cell's length (57,503,744 rows,
    shapes only): its two thresholds come from the counting select, so
    the optimised program holds no sort and no top-k, and every pass of
    a select is ONE fusion that reads the rows once and leaves only
    counts (``goss.select_passes`` counts those reads)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.select import PASSES
    rng = np.random.default_rng(0)
    X = rng.normal(size=(4096, 4))
    # the closure's k's come from this small table; they are data of
    # the program (a table lookup), its shapes are the arguments'
    eng = GBDT(Config({"objective": "binary", "verbosity": -1,
                       "data_sample_strategy": "goss"}),
               lgb.Dataset(X, label=(X[:, 0] > 0).astype(float)))
    s = functools.partial(_sds, one_chip)
    n = 57_503_744
    text = _compiled_text(jax.jit(eng._goss_masks).lower(
        s((n,), jnp.float32), s((n,), jnp.float32), s((n,), jnp.float32),
        s((2,), jnp.uint32)))
    assert not re.search(r"\bsort\(", text)
    assert not re.search(r"top-?k", text, re.I)
    # fusions whose every output is an int32 scalar: the select's
    # passes. XLA folds each select's first pass into the fusion that
    # writes its input, hence PASSES - 1 a select.
    counts_only = re.findall(
        r"= \((?:s32\[\]\S*,? ?(?:/\*index=\d+\*/)?)+\) fusion\(", text)
    assert len(counts_only) == 2 * (PASSES - 1), len(counts_only)


# ---------------------------------------------------------------------
# ingest and serving
# ---------------------------------------------------------------------
@pytest.mark.parametrize("F,C", [(28, 0), (39, 254)],
                         ids=["numeric", "categorical"])
def test_ingest_chunk_program_compiles(one_chip, F, C):
    """One 262,144-row chunk of device bin assignment, both layouts: 28
    numeric columns, and the click-log cell's 39 with category tables of
    254 ids (the lookup keeps its scope, the "other" count comes out)."""
    from lightgbm_tpu import obs
    from lightgbm_tpu.ops.ingest import _assign_chunk_impl
    s = functools.partial(_sds, one_chip)
    R, B = 262_144, 255
    fn = jax.jit(obs.scope("ingest/assign")(_assign_chunk_impl),
                 static_argnames=("out_dtype", "emit_transposed",
                                  "cat_cols"))
    text = _compiled_text(fn.lower(
        s((R, F), jnp.float32), s((F, B), jnp.float32),
        s((F,), jnp.int32), s((F,), jnp.int32), s((F,), jnp.int32),
        s((F,), jnp.int32), s((F,), jnp.bool_),
        s((F, max(C, 1)), jnp.int32), s((F, max(C, 1)), jnp.int32),
        out_dtype=jnp.uint8, emit_transposed=True,
        cat_cols=tuple(range(13, F)) if C else ()),
        # the lookup's [R, 26, 254] compare lives inside one fused
        # reduction: written out it would be 6.9 GB a chunk
        temp_limit=2**30)
    assert f"s8[{F},262144]" in text.replace(" ", "")   # the bins_t tile
    assert ("lgbm/ingest/cat_lookup" in text) == bool(C)


def test_onehot_forest_traversal_compiles(one_chip):
    """100 trees x 127 leaves x 16,384 rows, the TPU formulation
    (``default_formulation`` picks "gather" on this CPU)."""
    from lightgbm_tpu.ops.predict import _forest_predict_impl
    s = functools.partial(_sds, one_chip)
    T, L, n, F = 100, 127, 16_384, 28
    Ln = L - 1
    stacked = {
        "split_feature": s((T, Ln), jnp.int32),
        "threshold_bin": s((T, Ln), jnp.int32),
        "default_left": s((T, Ln), jnp.bool_),
        "left_child": s((T, Ln), jnp.int32),
        "right_child": s((T, Ln), jnp.int32),
        "leaf_value": s((T, L), jnp.float32),
        "num_leaves": s((T,), jnp.int32),
    }
    text = _compiled_text(_forest_predict_impl.lower(
        stacked, s((n, F), jnp.uint8), s((F,), jnp.int32),
        s((F,), jnp.bool_), s((T,), jnp.int32), num_class=1,
        mode="level", formulation="onehot"))
    assert "while" in text
