"""Test config: run everything on a virtual 8-device CPU mesh.

This is the "multi-node without a cluster" mechanism (SURVEY.md §4): the
reference spawns N localhost CLI processes for its distributed tests; we
give XLA 8 fake host devices so sharded/distributed paths execute real
collectives in-process.

``LGBM_TPU_TESTS=1`` skips the CPU pin so the two on-chip test groups
(the Pallas-vs-XLA cases of test_multi_leaf_histogram.py and
test_compact.py) run on the hardware they target. A chip belongs to
one process, so that run is ONE process without xdist, on the machine
with the chip:
``LGBM_TPU_TESTS=1 python -m pytest tests/test_multi_leaf_histogram.py
tests/test_compact.py -q -p no:xdist``. ``chip_smoke.py``'s ``kernels``
phase checks the flagship shapes of both on every chip run; this is
the wider shape grid. Distributed tests self-skip there (one chip).

The pin goes through jax.config as well as the environment so that it
holds whatever JAX_PLATFORMS the caller exported.
"""
import os

TPU_MODE = os.environ.get("LGBM_TPU_TESTS", "") == "1"

if not TPU_MODE:
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

# persistent compilation cache: grow_tree's while_loop is expensive to
# compile; cache across test runs keeps the suite fast
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")

import jax  # noqa: E402
import pytest  # noqa: E402

if not TPU_MODE:
    jax.config.update("jax_platforms", "cpu")
    assert jax.device_count() == 8, (
        f"expected 8 fake CPU devices, got {jax.devices()}")


@pytest.fixture
def pallas_path(monkeypatch):
    """The engine's Pallas path: compiled on the chip (``TPU_MODE``); on
    the CPU the engine is told it stands on a TPU and its kernels run in
    interpret mode, so what follows the kernel (layouts, counters, model
    bytes) is held by tier-1 too. Steered here, in the test, never
    through an option of the program."""
    if TPU_MODE:
        yield
        return
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture(scope="session")
def multiprocess_collectives():
    """Capability probe for cross-process collectives on the CPU
    backend: two bare ``jax.distributed`` processes attempt one
    ``process_allgather``, once per session (session scope memoizes
    the probe). Tests that fork a REAL multi-process gang
    (``num_machines>1`` CLI runs, 4-process fault-tolerance/multihost
    runs) request this fixture. The installed jaxlib runs them (Gloo),
    so these tests run; a build whose CPU backend cannot would skip
    them here instead of failing each one. Only a probe ERROR skips —
    an allgather that runs but returns wrong data is a real failure
    and fails every dependent test."""
    import multiprocessing as mp

    from _multihost_worker import collectives_probe_child
    from lightgbm_tpu.parallel.launch import _free_port
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    port = _free_port()
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in flags.split()
        if "host_platform_device_count" not in f)
    procs = []
    try:
        for rank in range(2):
            os.environ["_LGBM_PROBE_RANK"] = str(rank)
            p = ctx.Process(target=collectives_probe_child,
                            args=(port, q))
            p.start()
            procs.append(p)
        results = [q.get(timeout=60) for _ in range(2)]
    except Exception as e:
        results = [("err", f"{type(e).__name__}: {e}")]
    finally:
        os.environ["XLA_FLAGS"] = flags
        os.environ.pop("_LGBM_PROBE_RANK", None)
        for p in procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
    bad = [r for r in results if r[0] != "ok"]
    if bad:
        pytest.skip("the CPU backend could not run a multi-process "
                    f"collective ({bad[0][1]}); single-process "
                    f"variants still cover the code paths")
    assert all(r[1] == [0, 1] for r in results), \
        f"collectives returned wrong data: {results}"


@pytest.fixture(autouse=True)
def _obs_registry_guard(request):
    """Snapshot-and-restore the PROCESS-WIDE observability state around
    every obs-flavored test (module name contains ``obs`` or ``slo``).

    The obs registry, SLO tracker, trace buffer and metrics server are
    process globals; without this guard an obs test could leak an
    enabled registry into the rest of tier-1 (timing) or inherit
    forced counters from earlier tests (restart.attempts and friends),
    making assertions order-dependent. Non-obs modules pay one string
    check."""
    name = request.module.__name__
    if "obs" not in name and "slo" not in name:
        yield
        return
    from lightgbm_tpu import obs
    from lightgbm_tpu.obs import metrics as _om
    from lightgbm_tpu.obs import server as _osrv
    from lightgbm_tpu.obs import slo as _oslo
    from lightgbm_tpu.obs import tracing as _otr
    reg = _om.registry()
    # VALUE snapshot, not an object-reference copy: the test may
    # mutate a pre-existing metric in place (forced counters), and the
    # restore must bring the old values back, not the shared objects
    saved_state = reg.export_state()
    saved_enabled = obs.enabled()
    saved_dir = _otr._dir
    try:
        yield
    finally:
        obs.disable()
        obs.reset()
        _oslo.reset()
        _osrv.stop_server()
        _otr._dir = saved_dir
        reg.import_state(saved_state)
        if saved_enabled:
            obs.enable(metrics=True)


def pytest_collection_modifyitems(config, items):
    if not TPU_MODE or jax.device_count() >= 8:
        return
    import pytest
    skip = pytest.mark.skip(
        reason="needs the 8-device CPU mesh (TPU mode has "
               f"{jax.device_count()} device(s))")
    multi_device_files = {"test_distributed.py",
                          "test_parallel_learners.py"}
    for item in items:
        if item.fspath.basename in multi_device_files:
            item.add_marker(skip)
