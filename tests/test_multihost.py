"""Multi-process distributed training via the public launcher API.

Round 4 (VERDICT r3 item 2): the hand-wired worker recipe became
``lightgbm_tpu.train_distributed`` — fork/join localhost processes,
automatic cross-process bin-boundary sync, rank-0 model collection
(the dask.py analog; SURVEY.md §2.2). These tests are the reference's
own localhost-distributed strategy (N processes against 127.0.0.1,
tests/distributed/_test_distributed.py per SURVEY.md §4):

- a REAL 4-process ``jax.distributed`` job through the public API,
  checked against a single-process 4-fake-device run of the same SPMD
  program (prediction equivalence);
- the bin-sync helper alone (union-sample determinism).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb

WORKER = os.path.join(os.path.dirname(__file__), "_multihost_worker.py")

# data + params shared with the subprocess baseline (single source of
# truth — a drifted copy would compare models from different setups)
from _multihost_worker import GOSS_PARAMS, PARAMS, make_data  # noqa: E402


def shard_fn(rank, nproc):
    """Module-level so the spawned workers can unpickle it — the
    partition->worker alignment step (dask.py _train's partition
    mapping)."""
    X, y = make_data()
    blk = len(X) // nproc
    lo, hi = rank * blk, (rank + 1) * blk
    return {"data": X[lo:hi], "label": y[lo:hi]}


def test_train_distributed_four_processes(tmp_path,
                                          multiprocess_collectives):
    bst = lgb.train_distributed(PARAMS, shard_fn, n_processes=4,
                                num_boost_round=5)
    X, y = make_data()
    p_mh = bst.predict(X)
    assert np.mean((p_mh > 0.5) == y) > 0.8

    # single-process baseline: the same SPMD program on 4 FAKE devices
    # (multi-node-without-a-cluster, SURVEY.md §4) — predictions match
    base_model = str(tmp_path / "base.txt")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST", "XLA_", "JAX_"))}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    base = subprocess.run(
        [sys.executable, WORKER, "-1", "4", "0", base_model],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        timeout=600)
    assert base.returncode == 0, base.stdout.decode(errors="replace")
    p_base = lgb.Booster(model_file=base_model).predict(X)
    np.testing.assert_allclose(p_mh, p_base, rtol=1e-5, atol=1e-6)


def test_train_distributed_goss_matches_single_process(
        tmp_path, multiprocess_collectives):
    """VERDICT r4 item 7: exact GOSS subset counts at ANY process
    count — the 4-process GOSS run must produce the same model as the
    single-process 4-fake-device run of the same SPMD program (which
    only holds when both derive identical per-shard k_top/k_rand).
    Needs REAL multi-process collectives (the conftest probe skips
    where jaxlib's CPU backend lacks them, known-red since seed)."""
    bst = lgb.train_distributed(GOSS_PARAMS, shard_fn, n_processes=4,
                                num_boost_round=5)
    X, y = make_data()
    p_mh = bst.predict(X)
    base_model = str(tmp_path / "base_goss.txt")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST", "XLA_", "JAX_"))}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    base = subprocess.run(
        [sys.executable, WORKER, "-1", "4", "0", base_model, "goss"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        timeout=600)
    assert base.returncode == 0, base.stdout.decode(errors="replace")
    p_base = lgb.Booster(model_file=base_model).predict(X)
    np.testing.assert_allclose(p_mh, p_base, rtol=1e-5, atol=1e-6)


def test_goss_shard_valid_counts_multiprocess_table():
    """The multi-host exact-count table: inject a fake allgather and
    check per-global-shard counts equal the single-process layout of
    the concatenated rows."""
    from lightgbm_tpu.boosting.gbdt import goss_shard_valid_counts

    # 2 processes x 2 local devices, uneven local valid rows
    # (n_pad_local identical across processes, as the placement
    # contract requires)
    n_pad_local, blk = 1024, 512
    locals_ = {0: 900, 1: 700}   # valid rows per process

    def fake_allgather(x):
        out = []
        for p in range(2):
            n = locals_[p]
            out.append([max(0, min(n - s * blk, blk)) for s in range(2)])
        return np.asarray(out, np.int64)

    got = goss_shard_valid_counts(900, n_pad_local, 4, 2,
                                  allgather=fake_allgather)
    assert got == [512, 388, 512, 188]
    # single-process path: same layout semantics per shard
    assert goss_shard_valid_counts(900, 1024, 2, 1) == [512, 388]


def test_sync_bin_mappers_single_process_matches_local():
    """With one process the union sample IS the local sample, so the
    synced mappers equal plain find_bin_mappers on the same rows."""
    from lightgbm_tpu.io.binning import find_bin_mappers
    from lightgbm_tpu.parallel.launch import sync_bin_mappers
    X, _ = make_data()
    synced = sync_bin_mappers(X, {"max_bin": 63})
    local = find_bin_mappers(X, max_bin=63, sample_cnt=len(X))
    assert len(synced) == len(local)
    for ms, ml in zip(synced, local):
        np.testing.assert_array_equal(ms.bin_upper_bound,
                                      ml.bin_upper_bound)
        assert ms.num_bin == ml.num_bin
        assert ms.missing_type == ml.missing_type


def _run_sync_uneven(shards, params, monkeypatch):
    """Simulate an ``len(shards)``-process sync_bin_mappers in ONE
    process: fake ``jax.process_count/index`` and
    ``process_allgather``, record every rank's sample contribution in
    a first pass, then combine them for rank 0's final run. Exercises
    the real function body (both allgathers) without a cluster."""
    import jax
    from jax.experimental import multihost_utils

    from lightgbm_tpu.parallel.launch import sync_bin_mappers

    nproc = len(shards)
    n_all = np.array([len(s) for s in shards], np.int64)
    recorded = {}          # rank -> its padded sample contribution

    class _Abort(Exception):
        pass

    state = {"rank": 0, "mode": "record"}

    def fake_allgather(x):
        x = np.asarray(x)
        if x.dtype == np.int64 and x.size == 1:      # counts gather
            return n_all.reshape(nproc, 1)
        if state["mode"] == "record":                # sample gather
            recorded[state["rank"]] = x.copy()
            raise _Abort()
        stacked = [x if r == 0 else recorded[r] for r in range(nproc)]
        return np.stack(stacked)

    monkeypatch.setattr(jax, "process_count", lambda: nproc)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        fake_allgather)
    for r in range(1, nproc):
        state.update(rank=r, mode="record")
        monkeypatch.setattr(jax, "process_index", lambda r=r: r)
        with pytest.raises(_Abort):
            sync_bin_mappers(shards[r], params)
    state.update(rank=0, mode="combine")
    monkeypatch.setattr(jax, "process_index", lambda: 0)
    mappers = sync_bin_mappers(shards[0], params)
    # the sample rides the gather as the raw 32-bit words of its
    # float64 values (parallel.multihost.allgather_float64)
    sizes = {r: int(np.sum(~np.isnan(
                 recorded[r].view(np.float64)[:, 0])))
             for r in recorded}
    return mappers, sizes


def test_sync_bin_mappers_uneven_shards_weighted(monkeypatch):
    """VERDICT r4 item 4: with a 10:1 row skew across shards drawn
    from DIFFERENT distributions, sample quotas must be proportional
    to shard size and the synced boundaries must match a
    single-process build of the concatenated data."""
    from lightgbm_tpu.io.binning import find_bin_mappers
    rng = np.random.default_rng(11)
    big = rng.normal(0.0, 1.0, size=(50_000, 3))
    small = rng.normal(5.0, 0.3, size=(5_000, 3))     # shifted dist
    params = {"max_bin": 63, "bin_construct_sample_cnt": 5_000}
    _, sizes = _run_sync_uneven([big, small], params, monkeypatch)
    # proportional allocation: the small shard (1/11 of rows) must get
    # ~1/11 of the budget, NOT the old equal half
    assert sizes[1] <= 600, sizes     # equal split would give 2500
    # exact path: budget >= total rows -> union IS the concatenation,
    # so boundaries equal a single-process build bit-for-bit
    params_full = {"max_bin": 63,
                   "bin_construct_sample_cnt": 100_000}
    mappers, _ = _run_sync_uneven([big, small], params_full,
                                  monkeypatch)
    concat = np.concatenate([big, small])
    local = find_bin_mappers(concat, max_bin=63, sample_cnt=len(concat))
    for ms, ml in zip(mappers, local):
        np.testing.assert_array_equal(ms.bin_upper_bound,
                                      ml.bin_upper_bound)


def test_preset_mappers_dataset_roundtrip():
    """Dataset honors pre-injected bin mappers (the launcher's sync
    hook) instead of re-deriving its own."""
    from lightgbm_tpu.io.binning import find_bin_mappers
    X, y = make_data()
    mappers = find_bin_mappers(X, max_bin=31, sample_cnt=len(X))
    ds = lgb.Dataset(X, label=y, params={"verbosity": -1})
    ds.bin_mappers = mappers
    ds.construct()
    assert max(m.num_bin for m in ds.bin_mappers) <= 32
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, ds, num_boost_round=3)
    assert np.mean((bst.predict(X) > 0.5) == y) > 0.7


def test_train_distributed_rank_traces_merge(tmp_path,
                                             multiprocess_collectives):
    """Request-lifecycle tracing across a gang (ISSUE 13 acceptance):
    a 2-rank ``train_distributed`` run with ``tpu_trace_dir`` leaves
    one rank-tagged trace file per worker, and scripts/trace_merge.py
    merges them into ONE Perfetto-loadable timeline with rebased
    clocks and rank-named process rows (the straggler-visibility
    contract; the 1-rank in-container path is pinned in
    test_trace_merge.py)."""
    import json
    import subprocess

    tdir = str(tmp_path / "trace")
    lgb.train_distributed(dict(PARAMS, tpu_trace_dir=tdir), shard_fn,
                          n_processes=2, num_boost_round=3)
    names = sorted(os.listdir(tdir))
    assert "rank_0.trace.json" in names and "rank_1.trace.json" in names
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_merge.py")
    proc = subprocess.run(
        [sys.executable, script, tdir],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    rec = json.loads(proc.stdout)
    assert rec["ranks"] == [0, 1]
    assert rec["unrebased_ranks"] == []
    doc = json.load(open(os.path.join(tdir, "merged.trace.json")))
    rows = [e["args"]["name"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"]
    assert any(n.startswith("rank 0") for n in rows)
    assert any(n.startswith("rank 1") for n in rows)
    # both ranks' spans share the one rebased timeline, keyed by rank
    pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert pids == {0, 1}
