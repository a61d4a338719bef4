"""Chaos-hardened continuous training (ISSUE 9): the fault matrix,
the heartbeat watchdog, and zero-downtime serve-side hot-swap — the
pieces that turn train → checkpoint → hot-swap → serve into one loop
that survives injected kills, hangs, corruption and port races.

Tiers:

* in-process units: fault-spec grammar, deterministic corrupt seeds,
  marker hygiene, slow/hang/corrupt semantics, hot-swap + degradation
  (CompileWatch-pinned), the e2e train/publish/swap cycle under
  injected corruption;
* 1-process gangs (always runnable, SIGALRM-guarded like
  test_fault_tolerance.py): a hung rank detected by the heartbeat
  watchdog and relaunched to completion; a kill mid-STREAMED-run
  self-healing bit-exactly; an injected port conflict absorbed by the
  bind-retry path without consuming a restart attempt.
"""
import json
import os
import signal
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.recovery.checkpoint import CheckpointManager
from lightgbm_tpu.recovery.faults import (FaultPlan, clear_fault_markers,
                                          parse_fault_spec,
                                          parse_fault_specs, spec_seed)
from lightgbm_tpu.recovery.restart import backoff_seconds, is_bind_failure
from lightgbm_tpu.utils.debug import CompileWatch


def _data(n=3_000, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.4 * X[:, 1] + rng.normal(scale=0.3, size=n)
         > 0).astype(np.float64)
    return X, y


PARAMS = {"objective": "binary", "num_leaves": 8, "max_depth": 3,
          "verbosity": -1}


class _Watchdog:
    """SIGALRM in-test guard (same shape as test_fault_tolerance.py):
    a hung gang loop fails fast instead of eating the suite budget."""

    def __init__(self, seconds):
        self.seconds = seconds

    def __enter__(self):
        def _on_alarm(signum, frame):
            raise TimeoutError(f"chaos test exceeded its "
                               f"{self.seconds}s in-test watchdog")
        self._old = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(self.seconds)
        return self

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self._old)
        return False


# ---------------------------------------------------------------------------
# fault-spec grammar: the matrix, per-kind keys, multi-spec lists
# ---------------------------------------------------------------------------
def test_fault_matrix_grammar():
    plan = parse_fault_spec("hang:rank=1,iter=10")
    assert (plan.kind, plan.rank, plan.iteration) == ("hang", 1, 10)
    plan = parse_fault_spec("slow:iter=3,ms=250")
    assert (plan.kind, plan.ms) == ("slow", 250)
    plan = parse_fault_spec("corrupt:iter=5,target=both,nbytes=16")
    assert (plan.kind, plan.target, plan.nbytes) == ("corrupt", "both",
                                                     16)
    plan = parse_fault_spec("port:iter=2")
    assert plan.kind == "port"
    # multi-spec lists parse in order
    plans = parse_fault_specs("slow:iter=1,ms=50;exn:iter=4")
    assert [p.kind for p in plans] == ["slow", "exn"]
    # per-kind key validation: keys a kind does not take are typos
    for bad in ("exn:iter=1,ms=5", "kill:iter=1,target=ckpt",
                "corrupt:iter=1,target=everything", "slow:iter=1,x=2",
                "wedge:iter=1"):
        with pytest.raises(lgb.LightGBMError):
            parse_fault_spec(bad)


def test_spec_seed_is_deterministic_and_spec_keyed():
    assert spec_seed("corrupt:iter=5") == spec_seed("corrupt:iter=5")
    assert spec_seed("corrupt:iter=5") != spec_seed("corrupt:iter=6")


def test_clear_fault_markers_is_rank_scoped(tmp_path):
    for name in (".fault_fired.aaaa.rank0", ".fault_fired.aaaa.rank1",
                 ".fault_fired.bbbb.rank0", "keepme.txt"):
        (tmp_path / name).write_text("x")
    assert clear_fault_markers(tmp_path, rank=0) == 2
    left = sorted(os.listdir(tmp_path))
    assert left == [".fault_fired.aaaa.rank1", "keepme.txt"]
    assert clear_fault_markers(tmp_path) == 1        # rank=None: all


def test_fresh_run_clears_stale_markers_but_relaunch_keeps_them(
        tmp_path, monkeypatch):
    """Satellite: yesterday's fire-once marker must not suppress
    today's injected fault — a FRESH run clears its rank's markers at
    setup. A gang RELAUNCH (LGBM_TPU_GANG_RELAUNCH set by the
    launcher) keeps them, so a from-scratch relaunch replaying the
    fault iteration does not re-die on it."""
    X, y = _data(n=1_000)
    spec = "exn:iter=2"
    params = dict(PARAMS, checkpoint_dir=str(tmp_path),
                  checkpoint_interval=10, tpu_fault_inject=spec)
    # plant the marker a previous run would have left
    plan = parse_fault_spec(spec, marker_dir=str(tmp_path))
    mp = plan.marker_path(0)
    open(mp, "w").write(spec)
    # fresh run: marker cleared -> the fault FIRES
    with pytest.raises(lgb.LightGBMError, match="injected failure"):
        lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    assert os.path.exists(mp)              # re-written by the firing
    # relaunch: marker kept -> the fault is skipped, training finishes
    monkeypatch.setenv("LGBM_TPU_GANG_RELAUNCH", "1")
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    assert bst.num_trees() == 4


# ---------------------------------------------------------------------------
# slow / hang / corrupt / port semantics
# ---------------------------------------------------------------------------
def test_slow_fault_delays_without_changing_the_model():
    """A straggler rank is SLOW, not wrong: the injected delay must
    cost wall clock and change nothing else."""
    X, y = _data(n=1_000)
    # an untimed run first: the timed pair then both find their programs
    # compiled (a cold first run of 1.4 s once read slower than the
    # delayed second, under six busy workers)
    lgb.train(dict(PARAMS), lgb.Dataset(X, label=y), num_boost_round=4)
    # the lesser of two clean runs: under six busy workers one clean run
    # read 0.13 s over the other and hid a fifth of the delay (PR 36)
    t_clean = float("inf")
    for _ in range(2):
        t0 = time.monotonic()
        clean = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                          num_boost_round=4)
        t_clean = min(t_clean, time.monotonic() - t0)
    t0 = time.monotonic()
    slowed = lgb.train(dict(PARAMS, tpu_fault_inject="slow:iter=1,ms=200"),
                       lgb.Dataset(X, label=y), num_boost_round=4)
    t_slow = time.monotonic() - t0
    assert slowed.model_to_string() == clean.model_to_string()
    # fires before iterations 1, 2, 3 -> >= 0.6s of injected delay
    assert t_slow >= t_clean + 0.5


def test_hang_fault_wedges_until_cap():
    """Without a watchdog the ms cap (tests only) releases the wedge;
    the marker makes it fire-once like every terminal fault."""
    plan = parse_fault_spec("hang:iter=3,ms=300")
    t0 = time.monotonic()
    with pytest.raises(lgb.LightGBMError, match="hang released"):
        plan.maybe_fire(3)
    assert time.monotonic() - t0 >= 0.3


def test_corrupt_fault_damages_newest_checkpoint_deterministically(
        tmp_path):
    """corrupt:target=both flips payload bytes in the newest rank-0
    checkpoint AND clobbers the latest pointer mid-training; training
    itself survives (corrupt is damage, not death), the damaged file
    fails verification, and the loader walks back to the previous
    valid checkpoint."""
    X, y = _data(n=1_500)
    params = dict(PARAMS, checkpoint_dir=str(tmp_path),
                  checkpoint_interval=2,
                  tpu_fault_inject="corrupt:iter=5,target=both")
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=6)
    assert bst.num_trees() == 6            # the run itself completed
    mgr = CheckpointManager(str(tmp_path), rank=0)
    # fired before iteration 5: the then-newest checkpoint (iter 4) is
    # damaged, the pointer is garbage; iter 6 landed valid afterwards
    from lightgbm_tpu.recovery.checkpoint import CheckpointError
    with pytest.raises(CheckpointError):
        mgr.load_file(mgr.path(4))
    assert mgr.latest_valid_iteration() == 6
    st = mgr.load()                        # pointer garbage -> scan
    assert st["iteration"] == 6


def test_port_fault_matches_bind_failure_classifier():
    plan = parse_fault_spec("port:iter=1")
    with pytest.raises(lgb.LightGBMError) as ei:
        plan.maybe_fire(1)
    assert is_bind_failure(str(ei.value))


# ---------------------------------------------------------------------------
# restart backoff: decorrelated jitter (satellite)
# ---------------------------------------------------------------------------
def test_backoff_jitter_bounds_and_determinism():
    import random
    # no rng: the original deterministic exponential
    assert backoff_seconds(2, base=0.5) == 1.0
    # seeded rng: deterministic replay, bounded by [base, cap], and
    # decorrelated (depends on prev, not on attempt alone)
    a = backoff_seconds(1, base=0.5, cap=30.0,
                        rng=random.Random(7), prev=0.0)
    b = backoff_seconds(1, base=0.5, cap=30.0,
                        rng=random.Random(7), prev=0.0)
    assert a == b
    assert 0.5 <= a <= 1.5                 # uniform(base, 3*base)
    c = backoff_seconds(2, base=0.5, cap=30.0,
                        rng=random.Random(7), prev=10.0)
    assert 0.5 <= c <= 30.0
    # two seeds diverge — the whole point is ranks NOT sleeping in
    # lockstep
    vals = {backoff_seconds(1, base=0.5, rng=random.Random(s))
            for s in range(20)}
    assert len(vals) > 10
    # cap always wins
    assert backoff_seconds(9, base=1.0, cap=3.0,
                           rng=random.Random(1), prev=100.0) <= 3.0


# ---------------------------------------------------------------------------
# heartbeat files (obs <-> launcher watchdog contract)
# ---------------------------------------------------------------------------
def test_heartbeat_file_stamps_and_retires(tmp_path):
    from lightgbm_tpu import obs
    path = str(tmp_path / "heartbeat.train.rank0")
    obs.set_heartbeat_file("train", path, min_interval=0.0)
    try:
        assert not os.path.exists(path)    # lazily created: no stamp,
        obs.heartbeat("train")             # no file (startup != stale)
        assert os.path.exists(path)
        m0 = os.stat(path).st_mtime
        time.sleep(0.05)
        obs.heartbeat("train")
        assert os.stat(path).st_mtime >= m0
    finally:
        obs.retire_heartbeat("train")
    assert not os.path.exists(path)        # clean finish = absent


def test_heartbeat_dir_param_stamps_a_rank_file_while_training(tmp_path):
    """``tpu_heartbeat_dir`` through the train params: the round loop
    stamps ``heartbeat.train.rank<r>`` there (what the launcher's
    watchdog reads), and a clean finish retires it."""
    X, y = _data(n=1_500)
    seen = []
    lgb.train(dict(PARAMS, tpu_heartbeat_dir=str(tmp_path)),
              lgb.Dataset(X, label=y), num_boost_round=3,
              callbacks=[lambda env: seen.append(os.listdir(tmp_path))])
    assert seen and all(s == ["heartbeat.train.rank0"] for s in seen)
    assert os.listdir(tmp_path) == []      # clean finish = absent


def test_stale_heartbeat_detection(tmp_path):
    from lightgbm_tpu.parallel.launch import _stale_heartbeats
    p = tmp_path / "heartbeat.train.rank2"
    p.write_text("")
    old = time.time() - 60
    os.utime(p, (old, old))
    stale = _stale_heartbeats(str(tmp_path), 5.0)
    assert stale and stale[0][0] == 2 and stale[0][1] > 50
    # a fresh stamp is not stale; a missing dir is never stale
    os.utime(p)
    assert _stale_heartbeats(str(tmp_path), 5.0) == []
    assert _stale_heartbeats(str(tmp_path / "nope"), 5.0) == []


# ---------------------------------------------------------------------------
# serve-side hot-swap: warm adoption, zero recompiles, degradation
# ---------------------------------------------------------------------------
def _publish(pub_dir, rounds=8, seed=7, **extra):
    """One trainer cycle: train a fresh model publishing checkpoints
    into pub_dir (cleared fresh each time by train()'s hygiene is NOT
    wanted here — successive cycles resume_from=None would clear, so
    each cycle uses the callback directly via params on a fresh
    Booster; the checkpoint files accumulate/prune per keep_n)."""
    X, y = _data(n=2_000, seed=seed)
    p = dict(PARAMS, checkpoint_dir=str(pub_dir), checkpoint_interval=rounds,
             seed=seed, feature_fraction=0.9)
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=rounds)


def test_hot_swap_zero_recompiles_and_degradation(tmp_path):
    """The acceptance pin: N publish/swap cycles with ZERO warm-path
    recompiles (CompileWatch), zero dropped requests, atomic swaps;
    an injected corrupt publish keeps the previous model serving with
    serve.model_stale flipped, and the next good publish recovers."""
    from lightgbm_tpu import obs
    X, y = _data(n=2_000)
    pub = tmp_path / "pub"
    server = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                       num_boost_round=8)
    server.watch_checkpoints(str(pub), interval=0.0)
    Xq = X[:400]
    p_prev = server.predict(Xq)            # warm-up: compiles the
    server.predict(Xq)                     # bucketed padded shapes
    preds = {0: p_prev}
    for cycle in range(1, 4):
        _publish(pub, seed=100 + cycle)
        with CompileWatch() as w:
            preds[cycle] = server.predict(Xq)
        w.assert_compiles(0)               # warm path across the swap
        assert not np.allclose(preds[cycle], preds[cycle - 1])
    watch = server._model_watch
    assert watch.swaps == 3 and not watch.stale
    it_gauge = obs.registry().get("serve.model_iteration")
    assert it_gauge is not None and it_gauge.value == 8
    # corrupt publish: flip payload bytes in the newest checkpoint and
    # clobber the pointer (the chaos harness's own corrupt fault does
    # exactly this mid-training)
    mgr = CheckpointManager(str(pub), rank=0)
    newest = mgr.path(mgr.iterations()[-1])
    blob = open(newest, "rb").read()
    open(newest, "wb").write(blob[:-64] + bytes(64))
    open(mgr.latest_pointer, "w").write("ckpt_garbage")
    watch._last_sig = None                 # force the next poll to look
    with CompileWatch() as w:
        p_stale = server.predict(Xq)
    w.assert_compiles(0)
    assert np.allclose(p_stale, preds[3])  # previous model kept serving
    assert watch.stale
    assert obs.registry().get("serve.model_stale").value == 1.0
    assert obs.registry().get("serve.swap_failures").value >= 1.0
    # freshness lag is visible while pinned on the old model
    lag = obs.registry().get("train.freshness_lag_s")
    assert lag is not None and lag.value >= 0.0
    # the next GOOD publish recovers
    _publish(pub, seed=999)
    p_new = server.predict(Xq)
    assert not np.allclose(p_new, p_stale)
    assert not watch.stale and watch.swaps == 4
    assert obs.registry().get("serve.model_stale").value == 0.0


def test_model_watch_params_start_the_watcher(tmp_path):
    """``tpu_model_watch`` / ``tpu_model_watch_interval`` through the
    params: the Booster is built watching that directory at that
    interval, pinned to bucketed predict shapes, and adopts what is
    published there; without the params there is no watcher."""
    X, y = _data(n=1_500)
    pub = tmp_path / "pub"
    assert lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=2)._model_watch is None
    server = lgb.train(dict(PARAMS, tpu_model_watch=str(pub),
                            tpu_model_watch_interval=0.0),
                       lgb.Dataset(X, label=y), num_boost_round=6)
    watch = server._model_watch
    assert watch is not None and watch.dir == str(pub)
    assert watch.interval == 0.0
    assert server.engine._stable_predict_shapes
    p0 = server.predict(X[:200])
    _publish(pub, seed=42, rounds=6)
    assert not np.allclose(p0, server.predict(X[:200]))
    assert watch.swaps == 1
    timed = lgb.Booster(model_str=server.model_to_string(),
                        params={"tpu_model_watch": str(pub),
                                "tpu_model_watch_interval": 7.5})
    assert timed._model_watch.interval == 7.5


def test_watch_never_downgrades_a_newer_in_memory_model(tmp_path):
    """A trainer serving its OWN model finds its last round-boundary
    checkpoint in the watched dir — a PREFIX of the model in memory
    (the final iterations are rarely on a checkpoint boundary).
    Adopting it would silently drop trees; the first-adoption baseline
    refuses the downgrade and flags staleness instead, while anything
    published AFTER the watch started still swaps."""
    X, y = _data(n=1_500)
    p = dict(PARAMS, checkpoint_dir=str(tmp_path), checkpoint_interval=4)
    bst = lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=6)
    assert bst.num_trees() == 6            # newest checkpoint is iter 4
    pred = bst.predict(X[:200])
    bst.watch_checkpoints(str(tmp_path), interval=0.0)
    p2 = bst.predict(X[:200])
    assert bst._model_watch.swaps == 0     # refused the iter-4 prefix
    assert bst._model_watch.stale          # ...and said so
    assert bst.num_trees() == 6
    np.testing.assert_array_equal(pred, p2)
    # a publish AFTER the watch started adopts normally
    _publish(tmp_path, seed=77, rounds=4)
    p3 = bst.predict(X[:200])
    assert bst._model_watch.swaps == 1
    assert not np.allclose(p2, p3)


def test_hot_swap_host_model_booster(tmp_path):
    """A model-file-loaded Booster (no engine) swaps via model_str —
    the load-model-and-serve pod shape."""
    X, y = _data(n=1_500)
    pub = tmp_path / "pub"
    base = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                     num_boost_round=6)
    server = lgb.Booster(model_str=base.model_to_string())
    server.watch_checkpoints(str(pub), interval=0.0)
    p0 = server.predict(X[:200])
    _publish(pub, seed=42, rounds=6)
    p1 = server.predict(X[:200])
    assert not np.allclose(p0, p1)
    assert server._model_watch.swaps == 1


def test_hot_swap_streamed_trainer_to_resident_server(tmp_path):
    """The continuous-training composition: the STREAMED engine
    publishes, a resident server adopts (same binning pipeline — same
    data/params). The checkpointed streamed trees carry real-valued
    thresholds in model_str AND exact pickled trees, so either path
    serves them."""
    X, y = _data(n=4_000)
    pub = tmp_path / "pub"
    server = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                       num_boost_round=6)
    server.watch_checkpoints(str(pub), interval=0.0)
    server.predict(X[:200])
    streamed = lgb.train(
        dict(PARAMS, tpu_streaming="true", tpu_stream_block_rows=1_024,
             checkpoint_dir=str(pub), checkpoint_interval=6),
        lgb.Dataset(X, label=y), num_boost_round=6)
    p = server.predict(X[:200])
    assert server._model_watch.swaps == 1
    # the swapped-in forest serves the streamed model's predictions
    np.testing.assert_allclose(p, streamed.predict(X[:200]), rtol=1e-6)


def test_e2e_chaos_cycle_freshness_and_zero_drops(tmp_path):
    """Capstone (in-process): N train -> publish -> swap -> serve
    cycles with a corrupt publish injected mid-sequence via the chaos
    harness's own corrupt fault. Zero dropped requests (every predict
    returns), swaps land, staleness is visible then clears, and the
    freshness-lag gauge tracks the served checkpoint's age."""
    from lightgbm_tpu import obs
    X, y = _data(n=2_000)
    pub = tmp_path / "pub"
    server = lgb.train(dict(PARAMS), lgb.Dataset(X, label=y),
                       num_boost_round=8)
    server.watch_checkpoints(str(pub), interval=0.0)
    Xq = X[:256]
    server.predict(Xq)
    dropped = 0
    stale_seen = False
    latencies = []
    for cycle in range(4):
        if cycle == 2:
            # chaos: the trainer's OWN publish gets corrupted by the
            # injected fault right after the checkpoint lands
            Xc, yc = _data(n=2_000, seed=50 + cycle)
            p = dict(PARAMS, checkpoint_dir=str(pub),
                     checkpoint_interval=4, seed=50 + cycle,
                     tpu_fault_inject="corrupt:iter=4,target=both")
            lgb.train(p, lgb.Dataset(Xc, label=yc), num_boost_round=5)
            # only the corrupted iter-4 publish exists this cycle: the
            # server must keep serving and flag staleness
        else:
            _publish(pub, seed=50 + cycle, rounds=8)
        for _ in range(5):                 # serve traffic through it
            t0 = time.perf_counter()
            try:
                out = server.predict(Xq)
                assert out.shape == (len(Xq),)
            except Exception:
                dropped += 1
            latencies.append(time.perf_counter() - t0)
        stale_seen = stale_seen or server._model_watch.stale
    assert dropped == 0
    assert stale_seen                      # the corrupt cycle showed up
    assert not server._model_watch.stale   # ...and the next one healed
    assert server._model_watch.swaps >= 3
    lag = obs.registry().get("train.freshness_lag_s")
    assert lag is not None and 0.0 <= lag.value < 300.0
    p99 = float(np.quantile(latencies, 0.99))
    assert p99 < 30.0                      # sane, not a perf pin


# ---------------------------------------------------------------------------
# 1-process gangs: watchdog hang relaunch, streamed kill self-heal,
# port-fault bind retry (SIGALRM-guarded)
# ---------------------------------------------------------------------------
def chaos_shard_fn(rank, nproc):
    """Module-level so spawned workers can unpickle it."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2_000, 6))
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(np.float64)
    blk = len(X) // nproc
    lo = rank * blk
    hi = len(X) if rank == nproc - 1 else lo + blk
    return {"data": X[lo:hi], "label": y[lo:hi]}


GANG_PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1}


def test_hung_rank_detected_and_gang_relaunched(tmp_path):
    """Acceptance: an injected hang (which previously wedged forever —
    no exit code, no queue result) is detected via its stale heartbeat
    file within tpu_heartbeat_timeout, the gang is killed and
    relaunched through the normal backoff path, and the job completes
    within max_restarts without human intervention."""
    from lightgbm_tpu import obs
    d = str(tmp_path / "ck")
    params = dict(GANG_PARAMS, checkpoint_dir=d, checkpoint_interval=4,
                  tpu_fault_inject="hang:rank=0,iter=9")
    before = getattr(obs.registry().get("watchdog.restarts"), "value",
                     0.0)
    with _Watchdog(115):
        bst = lgb.train_distributed(
            params, chaos_shard_fn, n_processes=1, num_boost_round=12,
            timeout=90.0, max_restarts=2, restart_backoff=0.2,
            heartbeat_timeout=4.0)
    assert bst.num_trees() == 12
    after = obs.registry().get("watchdog.restarts").value
    assert after >= before + 1             # the watchdog, not the
    #                                        blunt timeout, caught it
    assert CheckpointManager(d, rank=0).latest_valid_iteration() == 12


def test_streamed_gang_kill_self_heals_bit_exact(tmp_path):
    """Acceptance: a kill injected by the chaos harness mid-STREAMED-
    run; the relaunched gang resumes streamed training from the newest
    checkpoint and the healed model is bit-identical to the fault-free
    gang's."""
    d_ok = str(tmp_path / "ok")
    d_fault = str(tmp_path / "fault")
    stream = dict(GANG_PARAMS, tpu_streaming="true",
                  tpu_stream_block_rows=512, checkpoint_interval=4)
    with _Watchdog(115):
        baseline = lgb.train_distributed(
            dict(stream, checkpoint_dir=d_ok), chaos_shard_fn,
            n_processes=1, num_boost_round=10, timeout=90.0)
        healed = lgb.train_distributed(
            dict(stream, checkpoint_dir=d_fault,
                 tpu_fault_inject="kill:rank=0,iter=6"),
            chaos_shard_fn, n_processes=1, num_boost_round=10,
            timeout=90.0, max_restarts=2, restart_backoff=0.2)
    assert [n for n in os.listdir(d_fault)
            if n.startswith(".fault_fired.")], "kill was never injected"
    assert healed.num_trees() == 10
    assert healed.model_to_string() == baseline.model_to_string()


def test_injected_port_conflict_absorbed_by_bind_retry(tmp_path):
    """A port fault raises the bind-conflict shape mid-run; the
    launcher's bind-retry path relaunches on a fresh port WITHOUT
    consuming a restart attempt (max_restarts=0 still succeeds), and
    the fire-once marker keeps the retry from re-dying."""
    from lightgbm_tpu import obs
    d = str(tmp_path / "ck")
    params = dict(GANG_PARAMS, checkpoint_dir=d, checkpoint_interval=2,
                  tpu_fault_inject="port:iter=3")
    before = getattr(obs.registry().get("restart.bind_retries"),
                     "value", 0.0)
    with _Watchdog(115):
        bst = lgb.train_distributed(params, chaos_shard_fn,
                                    n_processes=1, num_boost_round=6,
                                    timeout=90.0, max_restarts=0)
    assert bst.num_trees() == 6
    assert obs.registry().get("restart.bind_retries").value \
        >= before + 1
