"""Multi-host (multi-process) training entry.

Reference: the reference's distributed launch story — ``machine_list`` /
``machines`` + ``local_listen_port`` + rank discovery over sockets/MPI
(src/network/linkers_socket.cpp, dask.py's cluster orchestration,
UNVERIFIED — empty mount, see SURVEY.md banner).

TPU-native replacement: ``jax.distributed.initialize`` IS the machine
list. Each host process calls :func:`init_multihost` once — BEFORE any
other JAX use — after which ``jax.devices()`` spans the whole slice/pod
and ``create_data_mesh()`` builds the global mesh. The data placement
layer (``parallel.mesh.put``) then assembles global arrays from
per-process local chunks via ``jax.make_array_from_process_local_data``:
each process constructs its ``Dataset`` from its OWN row shard (the
reference's rank-aware ``pre_partition`` load, dataset_loader.cpp), and
the SPMD learners consume the resulting global arrays. Cross-process
bin-boundary consistency is AUTOMATIC through the launcher layer
(``parallel.launch``: union-sample ``sync_bin_mappers``); hand-wired
jobs can still share mappers manually (``Dataset.save_binary`` on rank
0, or a ``reference=`` dataset).

Validated by a REAL 4-process localhost run in CI
(tests/test_multihost.py): four processes join one ``jax.distributed``
job on the CPU backend via ``train_distributed``, each ingests its own
row shard with synced bin mappers, trains ``tree_learner=data``, and
the model matches a single-process run on the same global data. Mean-statistic
init scores (L2/binary/poisson family) sync across processes like the
reference's ``Network::GlobalSyncUpByMean`` (boosting/gbdt.py);
percentile-based init scores warn and use the local shard.
"""
from __future__ import annotations

from typing import Optional

from ..utils import log
from ..utils.log import LightGBMError


# substrings (lowercased) that identify a TRANSIENT coordinator error
# worth retrying: the coordinator process is still coming up, or the
# connection dropped. "Already initialized" / misuse errors are not
# transient and raise immediately.
_TRANSIENT_TOKENS = ("timeout", "timed out", "deadline", "unavailable",
                     "connection", "refused", "temporarily", "reset")


def init_multihost(coordinator_address: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None, *,
                   connect_retries: int = 2,
                   retry_backoff: float = 1.0) -> None:
    """Join the multi-host training job (call once per host process,
    before ANY other JAX use).

    Equivalent of the reference's ``machines=ip1:port,ip2:port`` +
    ``machine_list_file`` rank discovery: on TPU pods call with no
    arguments (auto-discovery); elsewhere pass the coordinator's
    ``ip:port``, the world size, and this process's rank.

    Transient coordinator-connect failures (the coordinator not up
    yet, dropped connections) retry up to ``connect_retries`` times
    with exponential backoff before raising; non-transient errors
    (double initialization, JAX already used) raise immediately. Every
    failure mode — including timeout/connection errors that are not
    ``RuntimeError`` — surfaces as the same actionable
    ``LightGBMError``.
    """
    import time

    import jax
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    from .. import obs
    for conn_attempt in range(connect_retries + 1):
        try:
            # forced span: gang-join latency is restart-loop telemetry
            # (like the forced connect-retry counter below) and fires
            # before any Config can flip tpu_metrics on
            with obs.span("multihost/init", force=True,
                          attempt=conn_attempt):
                jax.distributed.initialize(**kwargs)
            break
        except (RuntimeError, TimeoutError, ConnectionError, OSError) as e:
            transient = any(tok in str(e).lower()
                            for tok in _TRANSIENT_TOKENS)
            if transient and conn_attempt < connect_retries:
                from .. import obs
                obs.inc("multihost.connect_retries", force=True)
                # a failed initialize leaves jax's distributed global
                # state partially set (client assigned before connect),
                # and a second initialize() would fail with the
                # non-transient "called once" error — reset it first
                try:
                    jax.distributed.shutdown()
                except Exception:
                    pass
                from ..recovery.restart import backoff_seconds
                delay = backoff_seconds(conn_attempt + 1, retry_backoff)
                log.warning(
                    f"coordinator connect attempt {conn_attempt + 1} of "
                    f"{connect_retries + 1} failed ({e}); retrying in "
                    f"{delay:.1f}s")
                time.sleep(delay)
                continue
            raise LightGBMError(
                f"jax.distributed.initialize failed: {e}. Common causes: "
                f"JAX was already used in this process (init_multihost "
                f"must be the first JAX call), initialize() was called "
                f"twice, or the coordinator at {coordinator_address!r} "
                f"is unreachable.") from e
    from .. import obs
    obs.set_gauge("multihost.process_count", jax.process_count(),
                  force=True)
    obs.set_gauge("multihost.process_index", jax.process_index(),
                  force=True)
    log.info(f"multi-host initialized: process {jax.process_index()} of "
             f"{jax.process_count()}, {jax.device_count()} global / "
             f"{jax.local_device_count()} local devices")


def allgather_float64(arr):
    """``process_allgather`` of a float64 array, bit for bit:
    ``[process_count, *arr.shape]``. The gather goes through jax, which
    without x64 would round float64 to float32 — a gang would then
    differ from one process in the last digits of whatever it derives
    from the gathered values (bin boundaries, the boost-from-average
    score). The values travel as their raw 32-bit words instead."""
    import numpy as np
    from jax.experimental import multihost_utils
    a = np.atleast_1d(np.ascontiguousarray(arr, dtype=np.float64))
    words = multihost_utils.process_allgather(a.view(np.uint32))
    return np.ascontiguousarray(words).view(np.float64).reshape(
        (-1,) + a.shape)


def is_multihost() -> bool:
    """NB: initializes the local backend if nothing has yet — only call
    AFTER init_multihost (or in single-process jobs)."""
    import jax
    return jax.process_count() > 1
