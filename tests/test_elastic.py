"""Elastic topology (ISSUE 15): topology-free streamed checkpoints,
score re-cut on resume, and degrade-and-continue gangs.

The contract pinned here: a streamed×sharded run killed mid-training
resumes at a DIFFERENT shard count (4 → 2 and 4 → 8) with trees
BIT-IDENTICAL (quantized path — integer level histograms are
shard/block-cut-invariant) to the uninterrupted 4-shard run, including
a mid-bagging-window cut and the GOSS pending-statistics re-reduction;
rows whose saved slots are unreachable replay bit-exactly from the
pickled trees; re-cut eligibility is a capability-table verdict
(`capabilities.stream_recut_verdict`) whose refusal names the blocking
feature, the table cell, and the override knob; and the launcher
degrades-and-continues past a permanently-lost host (the `resize`
chaos fault's `.host_gone.rank<r>` markers) at reduced width without
consuming `max_restarts`, counting `watchdog.degrades`.
"""
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import capabilities, obs
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel import launch
from lightgbm_tpu.recovery.checkpoint import (CheckpointManager,
                                              latest_complete_iteration)
from lightgbm_tpu.recovery.faults import (clear_host_gone_markers,
                                          host_gone_ranks,
                                          parse_fault_spec)


def _data(n=8_000, f=10, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + rng.normal(scale=0.3, size=n) > 0).astype(np.float64)
    return X, y


# same shape family as tests/test_streaming_resume.py BASE so the
# modules share jit compiles (block 2048, leaves 16, depth 4); the
# QUANTIZED path is what makes cross-topology resume bit-exact
BASE = {"objective": "binary", "num_leaves": 16, "max_depth": 4,
        "verbosity": -1, "min_data_in_leaf": 20,
        "tpu_streaming": "true", "tpu_stream_block_rows": 2_048,
        "use_quantized_grad": True}

ROUNDS = 5
KILL_AT = 3          # checkpoints at 2 and 4; the fault fires before 3


def _params(shards, ckpt_dir, **extra):
    p = dict(BASE, checkpoint_dir=str(ckpt_dir),
             checkpoint_interval=2, **extra)
    if shards > 1:
        p["tree_learner"] = "data"
        p["tpu_mesh_shape"] = shards
    else:
        p.pop("tpu_mesh_shape", None)
    return p


def _kill_mid_run(X, y, shards, ckpt_dir, rounds=ROUNDS,
                  kill_at=KILL_AT, **extra):
    p = _params(shards, ckpt_dir, tpu_fault_inject=f"exn:iter={kill_at}",
                **extra)
    with pytest.raises(lgb.LightGBMError, match="injected failure"):
        lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=rounds)


# ---------------------------------------------------------------------------
# the acceptance matrix: 4-shard training killed mid-run resumes at 2
# AND at 8 shards bit-equal to the uninterrupted 4-shard run
# ---------------------------------------------------------------------------
def test_elastic_resume_4_to_2_and_8_bit_equal(tmp_path):
    X, y = _data()
    straight = lgb.train(_params(4, tmp_path / "s"),
                         lgb.Dataset(X, label=y),
                         num_boost_round=ROUNDS)
    _kill_mid_run(X, y, 4, tmp_path / "c")
    before = getattr(obs.registry().get("train.topology_changes"),
                     "value", 0.0)
    for new_shards in (2, 8):
        resumed = lgb.train(_params(new_shards, tmp_path / "c"),
                            lgb.Dataset(X, label=y),
                            num_boost_round=ROUNDS,
                            resume_from=str(tmp_path / "c"))
        assert resumed.num_trees() == ROUNDS
        assert resumed.model_to_string() == straight.model_to_string(), \
            f"4 -> {new_shards} elastic resume lost bit-equality"
    after = obs.registry().get("train.topology_changes").value
    assert after >= before + 2        # each re-cut resume counted


def test_narrower_resume_publish_hot_swaps_into_a_warm_server(tmp_path):
    """The resize cycle seen from serving: a warm resident server
    watching the trainer's checkpoint directory adopts the dying 4-shard
    trainer's publish, then the 2-shard resume's, drops no predict on
    the way, and ends up serving the resized model."""
    X, y = _data()
    pub = tmp_path / "pub"
    _kill_mid_run(X, y, 4, pub)
    server = lgb.train({"objective": "binary", "num_leaves": 16,
                        "max_depth": 4, "verbosity": -1},
                       lgb.Dataset(X, label=y), num_boost_round=2)
    server.watch_checkpoints(str(pub), interval=0.0)
    Xq = X[:512]
    server.predict(Xq)              # adopts the dying trainer's publish
    assert server._model_watch.swaps == 1
    resized = lgb.train(_params(2, pub), lgb.Dataset(X, label=y),
                        num_boost_round=ROUNDS, resume_from=str(pub))
    served = [server.predict(Xq) for _ in range(3)]   # none may raise
    assert server._model_watch.swaps >= 2
    assert not server._model_watch.stale
    # checkpoints land every 2 rounds: the newest publish of the
    # 5-round run is the resized model's first 4 trees
    assert server.num_trees() == 4
    np.testing.assert_allclose(
        served[-1], resized.predict(Xq, num_iteration=4),
        rtol=1e-5, atol=1e-6)


def test_elastic_resume_mid_bagging_window(tmp_path):
    """Kill INSIDE a bagging_freq window, resume NARROWER: the bagging
    salt is a counter-hash of (bagging_seed, iter//freq, GLOBAL row
    index), so the re-cut shards redraw the identical mid-window mask
    at the new width."""
    X, y = _data(seed=3)
    extra = {"bagging_fraction": 0.6, "bagging_freq": 3}
    straight = lgb.train(_params(4, tmp_path / "s", **extra),
                         lgb.Dataset(X, label=y), num_boost_round=7)
    _kill_mid_run(X, y, 4, tmp_path / "c", rounds=7, kill_at=5, **extra)
    resumed = lgb.train(_params(2, tmp_path / "c", **extra),
                        lgb.Dataset(X, label=y), num_boost_round=7,
                        resume_from=str(tmp_path / "c"))
    assert resumed.model_to_string() == straight.model_to_string()


def test_elastic_resume_goss_pending_stats_re_reduce(tmp_path):
    """GOSS + quantized tracks pending round statistics; on a re-cut
    they re-reduce (element-wise max / integer sum — grouping-
    invariant) instead of travelling per-rank, and the continued
    trees stay bit-equal."""
    X, y = _data(seed=5)
    extra = {"data_sample_strategy": "goss"}
    straight = lgb.train(_params(4, tmp_path / "s", **extra),
                         lgb.Dataset(X, label=y),
                         num_boost_round=ROUNDS)
    _kill_mid_run(X, y, 4, tmp_path / "c", **extra)
    resumed = lgb.train(_params(2, tmp_path / "c", **extra),
                        lgb.Dataset(X, label=y),
                        num_boost_round=ROUNDS,
                        resume_from=str(tmp_path / "c"))
    assert resumed.model_to_string() == straight.model_to_string()


def test_replay_from_trees_is_bit_exact(tmp_path):
    """Rows with no reachable saved slot recompute from the pickled
    trees — the replay runs the final sweep's exact f32 arithmetic, so
    continuing from replayed scores is bit-equal to continuing from
    the saved ones."""
    X, y = _data(seed=7)
    straight = lgb.train(_params(1, tmp_path / "s"),
                         lgb.Dataset(X, label=y),
                         num_boost_round=ROUNDS)
    _kill_mid_run(X, y, 1, tmp_path / "c")
    mgr = CheckpointManager(str(tmp_path / "c"), rank=0)
    st = mgr.load()
    st["engine"]["scores"] = None          # lose every saved slot
    st.pop("_checkpoint_path", None)
    mgr.save(st, int(st["iteration"]))
    resumed = lgb.train(_params(1, tmp_path / "c"),
                        lgb.Dataset(X, label=y),
                        num_boost_round=ROUNDS,
                        resume_from=str(tmp_path / "c"))
    assert resumed.model_to_string() == straight.model_to_string()


# ---------------------------------------------------------------------------
# eligibility: a capability-table verdict, not an inline engine gate
# ---------------------------------------------------------------------------
def test_recut_verdict_table():
    quant = Config({"objective": "binary", "use_quantized_grad": True,
                    "verbosity": -1})
    assert capabilities.stream_recut_verdict(quant)[0] \
        == capabilities.SUPPORTED
    f32 = Config({"objective": "binary", "verbosity": -1})
    v, why = capabilities.stream_recut_verdict(f32)
    assert v == capabilities.FATAL
    assert "tpu_elastic_recut" in why and "STREAM_RECUT" in why
    forced = Config({"objective": "binary", "verbosity": -1,
                     "tpu_elastic_recut": "true"})
    assert capabilities.stream_recut_verdict(forced)[0] \
        == capabilities.DEMOTE
    pinned = Config({"objective": "binary", "use_quantized_grad": True,
                     "verbosity": -1, "tpu_elastic_recut": "false"})
    assert capabilities.stream_recut_verdict(pinned)[0] \
        == capabilities.FATAL


def test_recut_refused_f32_names_feature_cell_and_knob(tmp_path):
    """The exact-f32 refusal must tell the operator WHAT blocks (f32
    accumulation), WHERE the judgment lives (the table cell) and HOW
    to override (the knob) — not just that a layout moved."""
    X, y = _data(n=4_000, seed=9)
    f32 = {k: v for k, v in BASE.items() if k != "use_quantized_grad"}
    p = dict(f32, checkpoint_dir=str(tmp_path),
             checkpoint_interval=2)
    lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    changed = dict(p, tpu_stream_block_rows=1_024)
    with pytest.raises(lgb.LightGBMError) as ei:
        lgb.train(changed, lgb.Dataset(X, label=y), num_boost_round=6,
                  resume_from=str(tmp_path))
    msg = str(ei.value)
    assert "layout" in msg
    assert "tpu_elastic_recut" in msg
    assert "STREAM_RECUT" in msg


def test_recut_forced_f32_trains_with_divergence_warning(tmp_path):
    X, y = _data(n=4_000, seed=9)
    f32 = {k: v for k, v in BASE.items() if k != "use_quantized_grad"}
    p = dict(f32, checkpoint_dir=str(tmp_path), checkpoint_interval=2)
    lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    forced = dict(p, tpu_stream_block_rows=1_024,
                  tpu_elastic_recut="true")
    bst = lgb.train(forced, lgb.Dataset(X, label=y), num_boost_round=6,
                    resume_from=str(tmp_path))
    assert bst.num_trees() == 6            # documented-close, completes


def test_recut_false_pins_strict_contract(tmp_path):
    """tpu_elastic_recut=false restores the PR-13 any-change-fatals
    behavior even on the otherwise-eligible quantized path."""
    X, y = _data(n=4_000, seed=11)
    p = _params(1, tmp_path)
    lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    pinned = dict(p, tpu_stream_block_rows=1_024,
                  tpu_elastic_recut="false")
    with pytest.raises(lgb.LightGBMError, match="layout"):
        lgb.train(pinned, lgb.Dataset(X, label=y), num_boost_round=6,
                  resume_from=str(tmp_path))


def test_changed_data_is_genuinely_incompatible(tmp_path):
    """Elastic resume re-cuts the SAME rows across topologies; a
    different global row count is a different dataset and must stay a
    hard error naming what moved."""
    X, y = _data(n=4_000, seed=13)
    p = _params(1, tmp_path)
    lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=4)
    X2, y2 = _data(n=6_000, seed=13)
    with pytest.raises(lgb.LightGBMError, match="row count"):
        lgb.train(p, lgb.Dataset(X2, label=y2), num_boost_round=6,
                  resume_from=str(tmp_path))


# ---------------------------------------------------------------------------
# the resize fault kind
# ---------------------------------------------------------------------------
def test_resize_fault_spec_parsing():
    plan = parse_fault_spec("resize:iter=4,ranks=1+3")
    assert plan.kind == "resize"
    assert plan.iteration == 4
    assert plan.ranks == (1, 3)
    with pytest.raises(lgb.LightGBMError, match="ranks="):
        parse_fault_spec("resize:iter=4")          # ranks required
    with pytest.raises(lgb.LightGBMError, match="cannot parse"):
        parse_fault_spec("resize:iter=4,ranks=a+b")
    with pytest.raises(lgb.LightGBMError, match="takes"):
        parse_fault_spec("resize:iter=4,ranks=1,ms=5")  # wrong key


def test_resize_fault_writes_host_gone_markers(tmp_path):
    """A firing resize fault leaves one .host_gone.rank<r> marker per
    named rank (the launcher's degrade signal) and a fire-once marker
    so a relaunch replaying the iteration skips it. This process is
    rank 0 and NOT in ranks, so it survives to assert."""
    d = str(tmp_path)
    plan = parse_fault_spec("resize:iter=2,ranks=1+2", marker_dir=d)
    plan.maybe_fire(1)                     # not the target iteration
    assert host_gone_ranks(d) == []
    plan.maybe_fire(2)
    assert host_gone_ranks(d) == [1, 2]
    assert os.path.exists(plan.marker_path(0))      # fire-once
    plan.maybe_fire(2)                     # marker-gated: no refire
    assert clear_host_gone_markers(d, ranks=[1]) == 1
    assert host_gone_ranks(d) == [2]
    assert clear_host_gone_markers(d) == 1
    assert host_gone_ranks(d) == []


# ---------------------------------------------------------------------------
# degrade-and-continue: the launcher loop (gang simulated — real
# multi-process gangs are capability-gated below)
# ---------------------------------------------------------------------------
def _model_str():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1_000, 6))
    y = (X[:, 0] > 0).astype(np.float64)
    return lgb.train({"objective": "binary", "num_leaves": 7,
                      "verbosity": -1}, lgb.Dataset(X, label=y),
                     num_boost_round=3).model_to_string()


def test_degrade_and_continue_without_consuming_restarts(
        tmp_path, monkeypatch):
    """A rank's host goes away mid-gang (resize marker): the launcher
    relaunches at width-1 through the SAME loop — with max_restarts=0,
    so the narrower relaunch provably consumed no restart attempt —
    counts watchdog.degrades, and consumes the marker."""
    model = _model_str()
    d = str(tmp_path / "ck")
    os.makedirs(d)
    calls = []

    def fake_gang_once(params, data_fn, n, *a, **kw):
        calls.append(n)
        if len(calls) == 1:
            with open(os.path.join(d, ".host_gone.rank1"), "w") as f:
                f.write("resize\n")
            return ("err", "rank 1: connection lost"), [(1, -9)], \
                [(1, -9)]
        return ("ok", model), [], []

    monkeypatch.setattr(launch, "_gang_once", fake_gang_once)
    before = getattr(obs.registry().get("watchdog.degrades"),
                     "value", 0.0)
    bst = lgb.train_distributed(
        {"objective": "binary", "verbosity": -1, "checkpoint_dir": d},
        _model_str, n_processes=2, num_boost_round=3, max_restarts=0)
    assert calls == [2, 1]                 # full width, then degraded
    assert bst.num_trees() == 3
    assert obs.registry().get("watchdog.degrades").value >= before + 1
    assert host_gone_ranks(d) == []        # marker consumed


def test_degrade_predicts_refused_recut_and_restarts_fresh(
        tmp_path, monkeypatch):
    """A forced-streaming f32 job (re-cut verdict FATAL) that loses a
    host must NOT resume the narrower gang into a checkpoint the
    engine is guaranteed to refuse — the degrade path predicts the
    verdict and restarts from scratch at the reduced width instead of
    burning restarts on a refused resume."""
    model = _model_str()
    d = str(tmp_path / "ck")
    os.makedirs(d)
    CheckpointManager(d, rank=0).save({"engine": {}, "iteration": 2}, 2)
    seen = []

    def fake_gang_once(params, data_fn, n, rounds, platform, cat,
                       timeout, resume_from, **kw):
        seen.append((n, resume_from))
        if len(seen) == 1:
            with open(os.path.join(d, ".host_gone.rank1"), "w") as f:
                f.write("resize\n")
            return ("err", "rank 1: host lost"), [(1, -9)], [(1, -9)]
        return ("ok", model), [], []

    monkeypatch.setattr(launch, "_gang_once", fake_gang_once)
    lgb.train_distributed(
        {"objective": "binary", "verbosity": -1, "checkpoint_dir": d,
         "tpu_streaming": "true"},
        _model_str, n_processes=2, num_boost_round=3, max_restarts=0,
        resume="auto")
    # the wide launch resumed (valid checkpoint on disk); the narrow
    # relaunch did NOT — the f32 re-cut would have been refused
    assert seen[0] == (2, d)
    assert seen[1] == (1, None)


def test_degrade_refuses_to_drop_every_rank(tmp_path, monkeypatch):
    d = str(tmp_path / "ck")
    os.makedirs(d)

    def fake_gang_once(params, data_fn, n, *a, **kw):
        for r in range(n):
            with open(os.path.join(d, f".host_gone.rank{r}"),
                      "w") as f:
                f.write("resize\n")
        return ("err", "all hosts lost"), [(0, -9), (1, -9)], \
            [(0, -9), (1, -9)]

    monkeypatch.setattr(launch, "_gang_once", fake_gang_once)
    with pytest.raises(lgb.LightGBMError, match="gone"):
        lgb.train_distributed(
            {"objective": "binary", "verbosity": -1,
             "checkpoint_dir": d},
            _model_str, n_processes=2, num_boost_round=3,
            max_restarts=3)


def test_stale_rank_snapshots_cleared_beyond_live_width(
        tmp_path, monkeypatch):
    """The PR-11 aggregation leak, pinned: a gang relaunched NARROWER
    (here resumed at width 1 after a width-2 run) must not merge the
    old topology's rank_1 snapshot into merged.jsonl — rank files
    beyond the live width are cleared on any (re)launch, resume
    included."""
    from lightgbm_tpu.obs.aggregate import dump_rank_snapshot
    model = _model_str()
    d = str(tmp_path / "ck")
    rank_dir = str(tmp_path / "ranks")
    os.makedirs(rank_dir)
    # a resumable checkpoint so the relaunch takes the RESUME path
    # (the fresh-run full clear would mask the beyond-width clear)
    CheckpointManager(d, rank=0).save(
        {"engine": {}, "iteration": 2}, 2)
    # yesterday's 2-rank gang left both snapshots behind
    dump_rank_snapshot(rank_dir, 0)
    dump_rank_snapshot(rank_dir, 1)

    def fake_gang_once(params, data_fn, n, *a, **kw):
        dump_rank_snapshot(rank_dir, 0)    # the live rank reports
        return ("ok", model), [], []

    monkeypatch.setattr(launch, "_gang_once", fake_gang_once)
    lgb.train_distributed(
        {"objective": "binary", "verbosity": -1, "checkpoint_dir": d,
         "tpu_metrics_rank_dir": rank_dir},
        _model_str, n_processes=1, num_boost_round=3, resume="auto")
    assert not os.path.exists(os.path.join(rank_dir, "rank_1.jsonl"))
    with open(os.path.join(rank_dir, "merged.jsonl")) as f:
        merged = json.loads(f.read().splitlines()[-1])
    assert merged["merged_from_ranks"] == [0]


# ---------------------------------------------------------------------------
# topology-aware rank agreement
# ---------------------------------------------------------------------------
def test_latest_complete_iteration(tmp_path):
    d = str(tmp_path)
    for rank in (0, 1):
        mgr = CheckpointManager(d, rank=rank)
        for it in (2, 4):
            mgr.save({"engine": {}, "iteration": it}, it)
    assert latest_complete_iteration(d) == 4
    # corrupt rank 1's newest -> the agreement walks back to 2
    p = CheckpointManager(d, rank=1).path(4)
    with open(p, "r+b") as f:
        f.seek(-32, os.SEEK_END)
        f.write(b"\0" * 32)
    assert latest_complete_iteration(d) == 2
    # a rank-gapped iteration (rank 0 only of {0, 2}) never qualifies
    CheckpointManager(d, rank=2).save({"engine": {}, "iteration": 6}, 6)
    assert latest_complete_iteration(d) == 2
    assert latest_complete_iteration(str(tmp_path / "void")) is None


# ---------------------------------------------------------------------------
# real multi-process degrade gang (capability-gated: this container's
# jaxlib cannot run cross-process collectives)
# ---------------------------------------------------------------------------
def elastic_shard_fn(rank, nproc):
    """Module-level so spawned workers can unpickle it."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2_000, 6))
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    blk = len(X) // nproc
    lo = rank * blk
    hi = len(X) if rank == nproc - 1 else lo + blk
    return {"data": X[lo:hi], "label": y[lo:hi]}


def test_gang_degrades_past_permanently_dead_host(
        tmp_path, multiprocess_collectives):
    """Acceptance: a 2-process gang whose rank-1 host vanishes
    (resize fault) completes at width 1 without exhausting
    max_restarts, with watchdog.degrades counted."""
    d = str(tmp_path / "ck")
    before = getattr(obs.registry().get("watchdog.degrades"),
                     "value", 0.0)
    bst = lgb.train_distributed(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1,
         "checkpoint_dir": d, "checkpoint_interval": 2,
         "use_quantized_grad": True,
         "tpu_fault_inject": "resize:iter=3,ranks=1"},
        elastic_shard_fn, n_processes=2, num_boost_round=6,
        timeout=120.0, max_restarts=0, restart_backoff=0.2)
    assert bst.num_trees() == 6
    assert obs.registry().get("watchdog.degrades").value >= before + 1
