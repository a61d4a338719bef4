"""Distributed training launcher — the ``dask.py`` analog.

Reference: ``python-package/lightgbm/dask.py`` (UNVERIFIED — empty
mount, see SURVEY.md banner) automates the multi-worker story: align
data partitions to workers, wire up ``machines``/ports, launch
concurrent per-worker training, return the (identical) model from
worker 0. Its transport is the socket collective layer.

TPU-native redesign: ``jax.distributed`` is the cluster fabric and the
SPMD learners already speak mesh collectives, so the launcher's job
collapses to three things this module provides:

1. :func:`train_distributed` — fork/join N localhost processes (the
   in-box testing + single-host-multi-process story; a real pod runs
   one process per host with the same worker body via
   :func:`run_worker`);
2. **automatic bin-boundary sync** — every process samples its own
   row shard, the samples are all-gathered
   (``multihost_utils.process_allgather``) and every process builds
   IDENTICAL BinMappers from the union sample (the reference
   ``DatasetLoader``'s distributed sample sync, dataset_loader.cpp —
   UNVERIFIED). No rank-0 broadcast needed: same bytes in, same
   mappers out, deterministically;
3. model collection from rank 0.

Pod recipe (multi-host hardware): run YOUR script once per host;
in it call ``run_worker(rank=None, ...)`` (auto-discovery on TPU
pods) or pass coordinator/rank explicitly. ``train_distributed``
itself is the localhost many-process convenience wrapper around it.

Out-of-core composition: with ``tpu_streaming`` ("true", or "auto"
when even the per-rank binned shard exceeds HBM) each worker routes
onto the SHARDED streaming engine — its shard's bins stay in host RAM
and stream through the device block by block, with ONE packed
collective of the accumulated histograms per tree level
(docs/perf.md "Streamed x sharded"). Same ``data_fn`` row-shard
contract, same rank-0 model collection; datasets beyond one host's
RAM x beyond one device's HBM become a worker-count question.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import re
import socket
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from ..config import get_param
from ..utils import log
from ..utils.log import LightGBMError

# set in the driver's environment (inherited by spawned workers) for
# EVERY launcher-spawned gang: a worker seeing it skips its fresh-run
# fault-marker clearing — marker hygiene is driver-owned here (one
# clear before the first gang, no per-rank race, and a from-scratch
# relaunch replaying the fault iteration honors the already-fired
# marker instead of re-dying on it every attempt). Direct lgb.train /
# run_worker users keep the worker-side clearing.
_RELAUNCH_ENV = "LGBM_TPU_GANG_RELAUNCH"

_HB_FILE_RE = re.compile(r"^heartbeat\.train\.rank(\d+)$")


def strip_fake_device_flags() -> None:
    """Drop any ``--xla_force_host_platform_device_count`` flag from
    this process's ``XLA_FLAGS``. Spawned children inherit the
    parent's env; a fake-device-count flag (e.g. the test suite's
    8-device CPU mesh) would multiply a worker's world size — each
    localhost worker/replica gets ONE device. Call BEFORE the first
    jax import in any spawned-process main."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" in flags:
        os.environ["XLA_FLAGS"] = " ".join(
            f for f in flags.split()
            if "host_platform_device_count" not in f)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clear_heartbeat_files(hb_dir: Optional[str]) -> None:
    """Remove per-rank heartbeat stamp files before (re)launching a
    gang — a stale file from the previous gang would read as an
    instantly-hung rank and kill every relaunch on sight."""
    if not hb_dir:
        return
    try:
        names = os.listdir(hb_dir)
    except OSError:
        return
    for name in names:
        if _HB_FILE_RE.match(name):
            try:
                os.unlink(os.path.join(hb_dir, name))
            except OSError:
                pass


def _stale_heartbeats(hb_dir: Optional[str],
                      timeout: float) -> List[Tuple[int, float]]:
    """(rank, age_seconds) for every heartbeat file older than
    ``timeout``. A rank with NO file yet is starting up (compiling,
    binning) — that is the overall gang timeout's job, not a hang."""
    if not hb_dir or timeout <= 0:
        return []
    import time as _time
    try:
        names = os.listdir(hb_dir)
    except OSError:
        return []
    now = _time.time()
    stale = []
    for name in names:
        m = _HB_FILE_RE.match(name)
        if not m:
            continue
        try:
            age = now - os.stat(os.path.join(hb_dir, name)).st_mtime
        except OSError:
            continue
        if age > timeout:
            stale.append((int(m.group(1)), round(age, 1)))
    return sorted(stale)


def _clear_rank_snapshots_beyond(rank_dir: Optional[str],
                                 width: int) -> None:
    """Remove per-rank metrics snapshots for ranks >= the LIVE gang
    width before any (re)launch — a gang relaunched narrower (R'=2
    after R=4) must not merge the previous topology's rank_2/rank_3
    snapshots into merged.jsonl as if those ranks were still
    members."""
    if not rank_dir:
        return
    rank_re = re.compile(r"^rank_(\d+)\.jsonl$")
    try:
        names = os.listdir(rank_dir)
    except OSError:
        return
    stale = []
    for name in names:
        m = rank_re.match(name)
        if m and int(m.group(1)) >= width:
            stale.append(name)
    for name in stale:
        try:
            os.remove(os.path.join(rank_dir, name))
        except OSError:
            pass
    if stale:
        log.warning(f"tpu_metrics_rank_dir {rank_dir} held "
                    f"{len(stale)} snapshot file(s) for ranks beyond "
                    f"the live width {width}; cleared before launch")


def _gone_ranks(gone_dirs: List[str], hb_dir: Optional[str],
                width: int, early_dead, hb_strikes: Dict[int, int],
                strikes_needed: int = 2) -> List[int]:
    """Ranks whose HOST is gone, from two signals: explicit
    ``.host_gone.rank<r>`` markers (the ``resize`` chaos fault, or an
    operator touch-file), and the spawn-failure heuristic — a rank
    that died on its own without EVER stamping a heartbeat this
    attempt collects a strike; ``strikes_needed`` consecutive strikes
    read as "that machine cannot even start a worker". Mutates
    ``hb_strikes`` (stamped ranks reset)."""
    from ..recovery.faults import host_gone_ranks
    gone = set()
    for d in gone_dirs:
        gone.update(host_gone_ranks(d))
    if hb_dir:
        # "consecutive" means exactly that: ANY rank that stamped a
        # heartbeat this attempt proved its host can start a worker —
        # its strike count resets even when the gang failed for an
        # unrelated reason and the rank never re-entered early_dead
        for r in list(hb_strikes):
            if os.path.exists(
                    os.path.join(hb_dir, f"heartbeat.train.rank{r}")):
                hb_strikes.pop(r, None)
        for r, _code in early_dead:
            if not os.path.exists(
                    os.path.join(hb_dir, f"heartbeat.train.rank{r}")):
                hb_strikes[r] = hb_strikes.get(r, 0) + 1
        gone.update(r for r, s in hb_strikes.items()
                    if s >= strikes_needed)
    return sorted(r for r in gone if 0 <= r < width)


@dataclass
class ShardSpec:
    """What ``data_fn`` returns: this process's row shard."""

    data: np.ndarray                      # [n_local, F] raw features
    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    group: Optional[np.ndarray] = None
    init_score: Optional[np.ndarray] = None


def sync_bin_mappers(X_local: np.ndarray, params: Dict,
                     categorical_idx=None):
    """Distributed bin-boundary sync: identical BinMappers on every
    process, built from an all-gathered cross-process sample.

    Each process's sample quota is PROPORTIONAL to its shard's row
    count (``bin_construct_sample_cnt * n_local / n_total``) so uneven
    shards don't bias bin boundaries toward small shards'
    distributions — the reference samples proportionally at the loader
    level (``dataset_loader.cpp`` sample-indices contract, SURVEY §2.1,
    UNVERIFIED). The fixed-size padded samples ride one
    ``process_allgather``, and each process runs the same binning code
    on the same union sample — bit-identical mappers with no broadcast
    step.
    """
    import jax
    from jax.experimental import multihost_utils

    from ..io.binning import mappers_from_params
    from .multihost import allgather_float64

    p = params
    total_cnt = int(p.get("bin_construct_sample_cnt", 200000))
    nproc = jax.process_count()
    n_local, F = X_local.shape
    rng = np.random.default_rng(
        int(p.get("data_random_seed", 1)) + 7919 * jax.process_index())
    # shard row counts first: every process derives ALL ranks' sample
    # sizes from the same gathered counts, so quotas are proportional
    # to shard size and no second counts gather is needed
    n_cnt = np.zeros((1,), np.int64) + n_local
    g_n = np.asarray(multihost_utils.process_allgather(n_cnt)) \
        .reshape(nproc).astype(np.int64)
    n_total = max(1, int(g_n.sum()))
    k_all = np.minimum(
        np.maximum(1, (total_cnt * g_n) // n_total), g_n).astype(int)
    k = int(k_all[jax.process_index()])
    idx = (rng.choice(n_local, size=k, replace=False) if k < n_local
           else np.arange(n_local))
    g_cnt = k_all
    slot = max(1, int(g_cnt.max()))
    samp = np.full((slot, F), np.nan, np.float64)
    samp[:k] = np.asarray(X_local, np.float64)[idx]
    g_samp = allgather_float64(samp)           # [nproc, slot, F]
    union = np.concatenate([g_samp[r, :g_cnt[r]] for r in range(nproc)])
    # total_sample_cnt semantics: the union IS the sample; sparse
    # implicit-zero accounting applies within it only
    return mappers_from_params(union, p, categorical_idx=categorical_idx,
                               sample_cnt=len(union))


def run_worker(params: Dict, data_fn: Callable[[int, int], ShardSpec],
               num_boost_round: int = 100, *,
               rank: Optional[int] = None,
               num_processes: Optional[int] = None,
               coordinator: Optional[str] = None,
               platform: Optional[str] = None,
               categorical_feature="auto",
               resume_from: Optional[str] = None):
    """The per-process worker body (call once per host on a pod).

    Joins the ``jax.distributed`` job, fetches this process's shard
    from ``data_fn(rank, num_processes)``, syncs bin boundaries across
    all processes, trains the data-parallel learner, and returns the
    Booster (identical on every rank — the SPMD program IS the sync).

    ``resume_from``: checkpoint directory to resume from (every rank
    restores its OWN per-rank checkpoint; ranks agree on the resume
    iteration via an allgather — recovery/checkpoint.py).
    """
    import jax
    if platform:
        jax.config.update("jax_platforms", platform)
    from .multihost import init_multihost
    if rank is not None or coordinator is not None:
        init_multihost(coordinator, num_processes, rank)
    else:
        init_multihost()    # TPU pod auto-discovery

    import lightgbm_tpu as lgb

    rank = jax.process_index()
    nproc = jax.process_count()
    # rank-tag this process's trace stream BEFORE training records any
    # span: with tpu_trace_dir set, each worker exports
    # rank_<r>.trace.json (rank-keyed pid + process_name rows) that
    # scripts/trace_merge.py rebases into one gang-wide timeline
    from ..obs import set_trace_rank
    set_trace_rank(rank)
    shard = data_fn(rank, nproc)
    if not isinstance(shard, ShardSpec):
        shard = ShardSpec(**shard) if isinstance(shard, dict) \
            else ShardSpec(*shard)
    params = dict(params)
    params.setdefault("tree_learner", "data")
    ds = lgb.Dataset(shard.data, label=shard.label,
                     weight=shard.weight, group=shard.group,
                     init_score=shard.init_score,
                     params=dict(params),
                     categorical_feature=categorical_feature)
    # automatic bin-boundary sync (closes the manual mapper-sharing
    # contract multihost.py documented through round 3)
    cat_idx = ds._resolve_categorical(
        ds._resolve_feature_names(shard.data.shape[1]))
    ds.bin_mappers = sync_bin_mappers(shard.data, params, cat_idx)
    bst = lgb.train(params, ds, num_boost_round=num_boost_round,
                    resume_from=resume_from)
    # per-rank metrics for the gang-wide view (obs/aggregate.py): each
    # worker appends its rank-tagged snapshot; the train_distributed
    # driver merges them after the gang joins. Best-effort — a full
    # disk must not fail a training run that already succeeded
    rank_dir = str(get_param(params, "tpu_metrics_rank_dir")
                   or "").strip()
    if rank_dir:
        from ..obs.aggregate import dump_rank_snapshot
        try:
            dump_rank_snapshot(rank_dir, rank)
        except Exception as e:
            log.warning(f"tpu_metrics_rank_dir: cannot write rank "
                        f"{rank} snapshot under {rank_dir!r}: {e}")
    return bst


def _spawn_main(rank, nproc, port, params, data_fn, num_boost_round,
                platform, categorical_feature, queue, resume_from):
    try:
        strip_fake_device_flags()
        bst = run_worker(params, data_fn, num_boost_round, rank=rank,
                         num_processes=nproc,
                         coordinator=f"localhost:{port}",
                         platform=platform,
                         categorical_feature=categorical_feature,
                         resume_from=resume_from)
        if rank == 0:
            queue.put(("ok", bst.model_to_string()))
    except Exception as e:          # surface the real worker error
        import traceback
        queue.put(("err", f"rank {rank}: {e}\n"
                   f"{traceback.format_exc()}"))
        raise


def _gang_once(params: Dict, data_fn, n_processes: int,
               num_boost_round: int, platform, categorical_feature,
               timeout: float, resume_from: Optional[str],
               hb_dir: Optional[str] = None,
               hb_timeout: float = 0.0):
    """One fork/join pass over a fresh worker gang on a fresh port.
    Returns ``(result, dead, early_dead)``: the ("ok", model_str) /
    ("err", payload) queue result or None when the gang died or timed
    out without reporting, the post-teardown dead rank/exitcode list
    for the error message, and ``early_dead`` — the ranks that died ON
    THEIR OWN before teardown (a teardown-terminated survivor must not
    feed the degrade heuristic's spawn-failure strikes).

    ``hb_dir``/``hb_timeout``: the heartbeat watchdog — workers stamp
    per-rank heartbeat files each round (engine.train via
    ``tpu_heartbeat_dir``); a stamp stale past ``hb_timeout`` means a
    HUNG rank (wedged pre-collective, stuck DMA): the gang is torn
    down like a crashed one and the caller's restart loop relaunches
    it. Hangs otherwise wedge forever — no exit code, no queue
    result — and only the blunt overall ``timeout`` would catch them.
    """
    ctx = mp.get_context("spawn")     # fork would inherit JAX state
    port = _free_port()
    queue = ctx.Queue()
    _clear_heartbeat_files(hb_dir)
    procs = [ctx.Process(
        target=_spawn_main,
        args=(r, n_processes, port, params, data_fn, num_boost_round,
              platform, categorical_feature, queue, resume_from))
        for r in range(n_processes)]
    for p in procs:
        p.start()
    # poll: fail FAST when a worker dies before rank 0 reports (e.g. a
    # non-importable data_fn under spawn, or an injected worker kill)
    # instead of sitting out the full timeout — the dask.py analog of
    # surfacing worker loss
    import queue as _queue
    import time as _time
    result = None
    early_dead = []
    deadline = _time.monotonic() + timeout
    while result is None and _time.monotonic() < deadline:
        try:
            result = queue.get(timeout=2.0)
        except _queue.Empty:
            dead = [(i, p.exitcode) for i, p in enumerate(procs)
                    if not p.is_alive() and p.exitcode not in (0, None)]
            if dead:
                early_dead = dead
                break
            stale = _stale_heartbeats(hb_dir, hb_timeout)
            if stale:
                from .. import obs
                # forced: the watchdog fires before any Config can
                # flip metrics on, like the restart counters
                obs.inc("watchdog.restarts", force=True)
                log.warning(
                    f"heartbeat watchdog: rank(s) "
                    f"{[r for r, _ in stale]} stale for "
                    f"{[a for _, a in stale]}s "
                    f"(> {hb_timeout:.1f}s) — killing the gang as "
                    f"hung")
                result = ("err",
                          f"heartbeat watchdog: rank(s) {stale} went "
                          f"stale past {hb_timeout:.1f}s — presumed "
                          f"hung pre-collective; gang killed for "
                          f"relaunch")
                break
        except Exception as e:
            # a worker killed MID-put leaves a truncated pickle in the
            # queue pipe; that is a gang failure to recover from — it
            # must reach the teardown + restart loop below, not escape
            # as a raw unpickling traceback that leaks hung workers
            result = ("err", f"worker result was undeliverable "
                      f"({type(e).__name__}: {e}) — a worker likely "
                      f"died while reporting")
            break
    # tear the gang down. On a clean result the workers exit on their
    # own (grant a grace join); on a dead/failed gang the survivors are
    # stuck in collectives waiting for the lost rank and will NEVER
    # exit, so don't sit out per-process joins — escalate to terminate
    # -> kill immediately (restart latency is the backoff, not this)
    clean = result is not None and result[0] == "ok"
    grace = 10.0 if clean else 0.5
    deadline = _time.monotonic() + grace
    for p in procs:
        p.join(timeout=max(0.0, deadline - _time.monotonic()))
        if p.is_alive():
            p.terminate()
    for p in procs:
        p.join(timeout=5)
        if p.is_alive():
            p.kill()
            p.join(timeout=5)
    if result is None:
        # a dying worker may have flushed its ('err', traceback) into
        # the queue between our last poll and the liveness check —
        # prefer that real error over the generic message. Only an
        # EMPTY queue is expected here; a real unpickling error must
        # surface, not vanish into a generic timeout message.
        try:
            result = queue.get_nowait()
        except _queue.Empty:
            pass
        except Exception as e:
            result = ("err", f"worker result was undeliverable "
                      f"({type(e).__name__}: {e}) — a worker likely "
                      f"died while reporting")
    dead = [(i, p.exitcode) for i, p in enumerate(procs)
            if p.exitcode not in (0, None)]
    if not early_dead:
        # a worker can die between the last poll and teardown; ranks
        # the TEARDOWN terminated show SIGTERM/SIGKILL exit codes and
        # are excluded (they were alive — not a spawn failure)
        early_dead = [(i, c) for i, c in dead
                      if c not in (-15, -9)] if not clean else []
    return result, dead, early_dead


def train_distributed(params: Dict,
                      data_fn: Callable[[int, int], ShardSpec],
                      n_processes: int, num_boost_round: int = 100, *,
                      platform: Optional[str] = "cpu",
                      categorical_feature="auto",
                      timeout: float = 900.0,
                      max_restarts: int = 0,
                      restart_backoff: float = 1.0,
                      checkpoint_dir: Optional[str] = None,
                      checkpoint_interval: int = 0,
                      resume: Union[bool, str] = "auto",
                      heartbeat_timeout: Optional[float] = None):
    """Train over ``n_processes`` localhost processes and return the
    rank-0 Booster (the dask.py ``_train`` analog).

    Args:
      params: lightgbm params (``tree_learner`` defaults to ``data``).
      data_fn: module-level picklable callable ``(rank, n_processes) ->
        ShardSpec`` (or dict of its fields) producing each process's
        row shard — the partition→worker alignment step.
      n_processes: localhost world size (one CPU device each by
        default; on real multi-host hardware run one process per host
        yourself via :func:`run_worker` instead).
      platform: force a JAX platform in the workers ("cpu" default —
        a TPU chip belongs to one process and cannot be shared by N
        local workers; pass None on a real pod, one process per host,
        from a parent that has not touched jax).
      timeout: seconds to wait for the workers (per attempt).
      max_restarts: automatic gang restarts after a worker death or
        timeout. Each restart terminates the gang, waits an
        exponential backoff, and relaunches every rank on a FRESH
        coordinator port; with a checkpoint dir holding a valid rank-0
        checkpoint the gang resumes from it, otherwise it restarts the
        run from scratch. 0 preserves the old fail-fast behavior.
      restart_backoff: base seconds for the exponential restart
        backoff (doubles per attempt, capped at 30 s).
      checkpoint_dir / checkpoint_interval: convenience for setting the
        same-named params on every worker (periodic durable per-rank
        checkpoints; docs/robustness.md). ``checkpoint_dir`` in
        ``params`` works identically.
      resume: "auto" (default) resumes from the newest valid rank-0
        checkpoint in the checkpoint dir when one exists — so re-running
        the SAME call after a whole-driver crash/preemption continues
        the job instead of wiping its checkpoints. False forces a fresh
        run (stale checkpoints are cleared); True requires a resumable
        checkpoint and raises when the dir holds none.
      heartbeat_timeout: heartbeat watchdog (seconds; also readable
        from params' ``tpu_heartbeat_timeout``). Workers stamp
        per-rank heartbeat files every round; a stamp stale past this
        timeout marks the rank HUNG (wedged pre-collective) and the
        gang is killed and relaunched through the same restart/backoff
        path a crash takes — so give it restart budget via
        ``max_restarts``. Set it above the worst cold-compile +
        per-round time; 0/None disables (hangs then only hit the
        blunt overall ``timeout``).
    """
    from ..recovery.restart import (backoff_seconds,
                                    has_resumable_checkpoint,
                                    is_bind_failure)
    params = dict(params)
    if checkpoint_dir:
        params["checkpoint_dir"] = str(checkpoint_dir)
    if checkpoint_interval > 0:
        # independent of HOW checkpoint_dir was supplied (kwarg or
        # params) — the dir may come from params with the cadence here
        params["checkpoint_interval"] = int(checkpoint_interval)
    ckpt_dir = str(params.get("checkpoint_dir") or "") or None

    # heartbeat watchdog wiring: give every worker a stamp-file dir and
    # remember the staleness budget the poll loop enforces
    hb_timeout = (float(heartbeat_timeout)
                  if heartbeat_timeout is not None
                  else float(get_param(params, "tpu_heartbeat_timeout")
                             or 0))
    if 0 < hb_timeout < 3.0:
        # workers stamp at most ~1 Hz (obs.set_heartbeat_file's
        # throttle): a timeout at or below the stamp interval would
        # read every HEALTHY rank as hung and kill each gang right
        # after its first stamp
        log.warning(f"heartbeat_timeout={hb_timeout:g}s is below the "
                    f"~1 Hz stamp cadence; raising to 3s")
        hb_timeout = 3.0
    hb_dir = (str(get_param(params, "tpu_heartbeat_dir") or "").strip()
              or None)
    if hb_timeout > 0 and not hb_dir:
        if ckpt_dir:
            hb_dir = ckpt_dir
        else:
            import tempfile
            hb_dir = tempfile.mkdtemp(prefix="lgbm_tpu_hb_")
    if hb_timeout > 0:
        params["tpu_heartbeat_dir"] = hb_dir
        os.makedirs(hb_dir, exist_ok=True)

    # cross-driver resume: a preempted/killed DRIVER re-running the
    # same call must continue the job, not clear its checkpoints
    resume_from = None
    if resume not in (False, True, "auto"):
        raise LightGBMError(f"resume must be True, False or 'auto', "
                            f"got {resume!r}")
    if resume in (True, "auto") and ckpt_dir \
            and has_resumable_checkpoint(ckpt_dir):
        resume_from = ckpt_dir
        log.info(f"resuming distributed training from the newest "
                 f"checkpoint in {ckpt_dir}")
    if resume is True and resume_from is None:
        raise LightGBMError(
            f"resume=True but {ckpt_dir!r} holds no valid rank-0 "
            f"checkpoint to resume from")
    if resume is False and ckpt_dir:
        # clear driver-side BEFORE the first launch: if the gang died
        # before any worker reached its own fresh-run clearing, the
        # restart path's has_resumable_checkpoint would adopt the old
        # run the caller explicitly asked to discard
        from ..recovery.checkpoint import clear_checkpoint_dir
        cleared = clear_checkpoint_dir(ckpt_dir)
        if cleared:
            log.warning(f"resume=False: cleared {cleared} stale "
                        f"checkpoint(s) from {ckpt_dir}")

    # fresh run claiming a rank-metrics dir: stale rank_*.jsonl from a
    # previous (possibly larger) gang would otherwise merge as live
    # members — yesterday's rank_3 joining today's 2-rank gang view
    rank_dir = str(get_param(params, "tpu_metrics_rank_dir")
                   or "").strip()
    if rank_dir and resume_from is None:
        import glob as _glob
        import os as _os
        stale = [p for pat in ("rank_*.jsonl", "merged.jsonl")
                 for p in _glob.glob(_os.path.join(rank_dir, pat))]
        for p in stale:
            try:
                _os.remove(p)
            except OSError:
                pass
        if stale:
            log.warning(f"tpu_metrics_rank_dir {rank_dir} held "
                        f"{len(stale)} snapshot file(s) from a "
                        f"previous run; cleared for this fresh run")

    # fault-marker hygiene is DRIVER-owned under the launcher: clear
    # stale fire-once markers for the whole gang once, before any
    # worker exists (no per-rank race), and have every worker — first
    # launch, bind retry, or relaunch alike — keep markers via the
    # relaunch env var. Worker-side clearing would race a first gang
    # that never reaches engine.train (a genuine bind-race loss) into
    # skipping the clear entirely.
    fi_spec = str(get_param(params, "tpu_fault_inject") or "").strip()
    fault_marker_dir = (str(get_param(params, "tpu_fault_marker") or "")
                        or ckpt_dir)
    if fi_spec and fault_marker_dir and resume_from is None:
        from ..recovery.faults import clear_fault_markers
        cleared = clear_fault_markers(fault_marker_dir)
        if cleared:
            log.warning(f"tpu_fault_inject: cleared {cleared} stale "
                        f"fire-once marker(s) from {fault_marker_dir} "
                        f"for this fresh run")

    import random as _random

    # decorrelated-jitter state for the restart backoff: N drivers (or
    # gang re-runs) sleeping IDENTICAL exponential delays would
    # stampede the coordinator port in lockstep every attempt — the
    # bind-retry counter below measures exactly those collisions
    _backoff_rng = _random.Random()
    _backoff_prev = 0.0
    attempt = 0           # restart attempts consumed (not bind retries)

    # elastic topology (docs/robustness.md "Elastic topology"): the
    # gang's LIVE width. A rank whose HOST is permanently gone — a
    # `.host_gone.rank<r>` marker from the resize chaos fault or an
    # operator, or repeated deaths without ever stamping a heartbeat —
    # narrows the gang instead of burning max_restarts relaunching at
    # full strength; the relaunched workers re-shard the rows over the
    # new width and the streamed resume path re-cuts the checkpoint
    # onto the new topology.
    live_width = int(n_processes)
    if live_width < 1:
        raise LightGBMError(f"n_processes must be >= 1, got "
                            f"{n_processes}")
    gone_dirs = [d for d in dict.fromkeys(
        (fault_marker_dir, ckpt_dir, hb_dir)) if d]
    from ..recovery.faults import clear_host_gone_markers
    if resume_from is None:
        # fresh run: yesterday's host loss must not shrink today's gang
        for d in gone_dirs:
            clear_host_gone_markers(d)
    hb_strikes: Dict[int, int] = {}

    def _apply_degrade(early_dead) -> bool:
        """Consume host-gone evidence; True = the gang narrowed and
        the caller should relaunch WITHOUT burning a restart attempt."""
        nonlocal live_width, resume_from
        gone = _gone_ranks(gone_dirs,
                           hb_dir if hb_timeout > 0 else None,
                           live_width, early_dead, hb_strikes)
        if not gone:
            return False
        if len(gone) >= live_width:
            raise LightGBMError(
                f"every live rank's host is gone ({gone}); nothing "
                f"left to degrade the gang to")
        from .. import obs
        # forced: degrades fire in the driver, before any worker
        # Config can flip metrics on — like the restart counters
        obs.inc("watchdog.degrades", len(gone), force=True)
        for d in gone_dirs:
            clear_host_gone_markers(d, ranks=gone)
        live_width -= len(gone)
        hb_strikes.clear()
        resume_from = (ckpt_dir if ckpt_dir
                       and has_resumable_checkpoint(ckpt_dir)
                       else None)
        if resume_from:
            # a FORCED-streaming job whose re-cut the capability table
            # refuses (exact f32 without the tpu_elastic_recut opt-in)
            # would fatal on EVERY narrower relaunch and burn
            # max_restarts — exactly what degrade exists to avoid.
            # Predict the verdict and restart from scratch instead.
            from .. import capabilities
            if capabilities.forced_engine(params) == "streaming":
                v, why = capabilities.stream_recut_verdict_params(
                    params)
                if v == capabilities.FATAL:
                    log.warning(
                        f"degrade-and-continue: the streamed "
                        f"checkpoint cannot be re-cut onto the "
                        f"narrower topology ({why}); restarting from "
                        f"scratch at the reduced width instead of "
                        f"burning restarts on a refused resume")
                    resume_from = None
        log.warning(
            f"degrade-and-continue: host(s) of rank(s) {gone} are "
            f"permanently gone; relaunching the gang at width "
            f"{live_width} "
            + (f"resuming from the newest topology-complete "
               f"checkpoint in {resume_from}" if resume_from else
               "with no resumable checkpoint — restarting the run "
               "from scratch at the reduced width"))
        return True

    # a marker already on disk at entry (e.g. resume="auto" after the
    # driver itself died mid-incident) narrows the FIRST gang too —
    # "missing host at gang start" must not cost a full-width attempt
    _apply_degrade([])
    try:
        os.environ[_RELAUNCH_ENV] = "1"
        while True:
            # stale-rank snapshot hygiene on EVERY (re)launch: a
            # narrower relaunch must not merge the wider topology's
            # rank_<r>.jsonl as live gang members
            _clear_rank_snapshots_beyond(rank_dir, live_width)
            result = None
            # the coordinator port race (_free_port -> jax.distributed
            # bind) loses when another process grabs the probed port
            # first; a bind failure retries on a fresh port WITHOUT
            # consuming a restart attempt
            for bind_attempt in range(3):
                result, dead, early_dead = _gang_once(
                    params, data_fn, live_width, num_boost_round,
                    platform, categorical_feature, timeout, resume_from,
                    hb_dir=hb_dir if hb_timeout > 0 else None,
                    hb_timeout=hb_timeout)
                if (result is not None and result[0] == "err"
                        and is_bind_failure(result[1])
                        and bind_attempt < 2):
                    from .. import obs
                    obs.inc("restart.bind_retries", force=True)
                    log.warning(
                        "coordinator port was reclaimed before bind "
                        "(the _free_port race); relaunching the worker "
                        "gang on a fresh port")
                    continue
                break
            if result is not None and result[0] == "ok":
                bst_str = result[1]
                break
            if result is not None:
                failure = LightGBMError(
                    f"distributed worker failed: {result[1]}")
            else:
                failure = LightGBMError(
                    "distributed training produced no result "
                    + (f"(worker ranks/exitcodes {dead} died — is "
                       f"data_fn a module-level importable callable? "
                       f"spawn re-imports its module in each worker)"
                       if dead else
                       "(workers timed out before rank 0 reported; "
                       "re-run with verbosity>=1 for worker logs)"))
            if _apply_degrade(early_dead):
                continue      # narrower relaunch; no attempt consumed
            attempt += 1
            if attempt > max_restarts:
                raise failure
            resume_from = (ckpt_dir if ckpt_dir
                           and has_resumable_checkpoint(ckpt_dir)
                           else None)
            # forced: gang restarts are exactly the restart-loop signal
            # the obs subsystem exists to surface, and the launcher
            # runs before any Config can flip tpu_metrics on
            from .. import obs
            obs.inc("restart.attempts", force=True)
            if resume_from:
                obs.inc("restart.resumes", force=True)
            delay = backoff_seconds(attempt, restart_backoff,
                                    rng=_backoff_rng,
                                    prev=_backoff_prev)
            _backoff_prev = delay
            log.warning(
                f"distributed training attempt {attempt} of "
                f"{max_restarts + 1} failed ({failure}); "
                + (f"resuming every rank from the newest checkpoint in "
                   f"{resume_from} " if resume_from else
                   "no resumable checkpoint — restarting from scratch ")
                + f"on a fresh port after {delay:.1f}s backoff")
            import time as _time
            _time.sleep(delay)
    finally:
        os.environ.pop(_RELAUNCH_ENV, None)

    # gang-wide metrics view: merge the per-rank snapshots the workers
    # dumped (counters sum, gauges latest, histograms bucket-add) into
    # <dir>/merged.jsonl and surface the straggler gauge on the driver
    rank_dir = str(get_param(params, "tpu_metrics_rank_dir")
                   or "").strip()
    if rank_dir:
        from ..obs.aggregate import merge_rank_dir
        try:
            merged = merge_rank_dir(rank_dir)
            if merged is None:
                log.warning(f"tpu_metrics_rank_dir={rank_dir!r}: no "
                            f"rank snapshots to merge")
            else:
                spread = next(
                    (m.get("value") for m in merged["metrics"]
                     if m.get("name") == "dist.round_time_spread"),
                    None)
                log.info(
                    f"merged {len(merged.get('merged_from_ranks', []))}"
                    f" rank snapshot(s) into {rank_dir}/merged.jsonl"
                    + (f" (round_time_spread={spread:.2f})"
                       if spread else ""))
        except Exception as e:
            log.warning(f"tpu_metrics_rank_dir: merge under "
                        f"{rank_dir!r} failed: {e}")

    import lightgbm_tpu as lgb
    bst = lgb.Booster(model_str=bst_str)
    log.info(f"distributed training done: {live_width} processes"
             + (f" (degraded from {n_processes} — "
                f"{n_processes - live_width} host(s) lost)"
                if live_width != n_processes else "")
             + f", {bst.num_trees()} trees collected from rank 0"
             + (f" ({attempt} restart(s))" if attempt else ""))
    return bst
