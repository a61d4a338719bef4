"""``chip_smoke.py`` without a chip: it must refuse to pass, and its
phases' control flow must hold.

The script proves the main path on the TPU; here only two things can be
shown. On a CPU it exits non-zero and never prints the ``ok`` line, in
rehearsal mode too (``main`` is driven with stubbed phase bodies for
that). And every phase function, driven in-process at 8,192 rows with
the engine-side TPU assertions relaxed (``on_tpu=False`` — the Pallas
kernels then run in interpret mode), returns its record. The
compile-cache rule the script leans on (a live cache, as named by
``JAX_COMPILATION_CACHE_DIR``, wins over ``tpu_compile_cache_dir``) is
pinned here as well.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

ROWS, HOLDOUT = 8192, 2048


def test_cpu_run_exits_nonzero_and_prints_no_ok_line():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""           # no result of any kind
    assert "no TPU" in proc.stderr


def test_phases_return_their_records(tmp_path):
    """device -> kernels -> ingest -> both trainings -> predict ->
    explain -> cache, in the script's order (later phases read what
    earlier ones left on ``run``), a dozen rounds."""
    run = chip_smoke.Run(rows=ROWS, holdout=HOLDOUT, seed=0,
                         on_tpu=False)
    with chip_smoke.CompileStats() as stats:
        rec = chip_smoke.phase_device(run)
        assert rec["platform"] == "cpu" and run.device_kind
        rec = chip_smoke.phase_kernels(run, rows=8192)
        assert rec["pallas"] == "interpret"
        assert rec["hist_int8"] == "exact"
        rec = chip_smoke.phase_ingest(run)
        assert rec["path"] in ("native", "python")
        assert rec["bins_equal_host"] and run.ds is not None
        rec = chip_smoke.phase_train_goss_quant(run, rounds=12)
        assert rec["engine"] == "GBDT" and rec["trees"] == 13
        assert rec["min_leaves"] > 1 and rec["holdout_auc"] > 0.75
        assert rec["carries_donated"] is False   # tpu_donate=auto, CPU
        rec = chip_smoke.phase_train_plain(run, rounds=4, pair_rounds=2)
        assert rec["trees"] == 5
        assert rec["partition_models_byte_equal"]
        rec = chip_smoke.phase_predict(run, str(tmp_path))
        assert rec["warm_compiles"] == 0
        assert rec["roundtrip_max_diff"] <= 1e-6
        rec = chip_smoke.phase_explain(run, str(tmp_path), rows=256)
        assert rec["sum_vs_raw_max_diff"] <= 1e-3
    rec = chip_smoke.phase_cache(run, stats, 0, 1.0)
    assert rec["jax_compilation_cache_dir"] == \
        jax.config.jax_compilation_cache_dir
    assert rec["cache_requests"] > 0


def test_data_parallel_phase_on_the_virtual_mesh():
    """The ``--chips 4`` phase on the suite's 8 virtual CPU devices:
    mesh over all of them, rows on 8 distinct devices, predictions
    exactly equal to the serial run's."""
    r = chip_smoke.Run(rows=ROWS, holdout=HOLDOUT, seed=0, on_tpu=False)
    rec = chip_smoke.phase_data_parallel(r, rounds=3,
                                         n_devices=jax.device_count())
    assert rec["mesh_devices"] == 8 and len(rec["shard_devices"]) == 8
    assert rec["predictions_exactly_equal_serial"]


def test_a_failed_check_fails_the_phase():
    r = chip_smoke.Run(rows=ROWS, holdout=HOLDOUT, seed=0, on_tpu=True)
    # on_tpu demands a TPU platform and a non-None HBM limit: on this
    # CPU the device phase must raise, not carry on
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_device(r)
    assert chip_smoke.auc(np.array([0, 0, 1, 1.0]),
                          np.array([.1, .4, .35, .8])) == 0.75


@pytest.mark.parametrize("chips,phases", [
    ("1", ["device", "kernels", "ingest", "train_goss_quant",
           "train_plain", "predict", "explain", "cache"]),
    ("4", ["device", "data_parallel", "cache"]),
])
def test_rehearsal_cannot_print_the_ok_line(monkeypatch, capsys, chips,
                                            phases):
    """``main`` under ``--rehearse``: the phases in order (the bodies
    are stubbed — the tests above run them), ``--chips 4`` runs the
    data-parallel phase and nothing else, the last line says
    ``"ok": false`` and the return code is 3."""
    for name in phases:
        monkeypatch.setattr(chip_smoke, f"phase_{name}",
                            lambda run, *a, **k: {})
    rc = chip_smoke.main(["--rehearse", "--chips", chips])
    assert rc == 3
    lines = [json.loads(ln)
             for ln in capsys.readouterr().out.splitlines()]
    assert [ln.get("phase") for ln in lines[:-1]] == phases
    assert all(ln["ok"] and ln["timing"] == chip_smoke.TIMING_NOTE
               for ln in lines[:-1])
    dev = jax.devices()[0]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": dev.device_kind,
        "count": jax.device_count()}}


def _cache_by_call(path):
    from lightgbm_tpu import config as config_mod
    config_mod.setup_compile_cache(path)


def _cache_by_dataset_params(path):
    import lightgbm_tpu as lgb
    X = np.random.default_rng(0).normal(size=(64, 3))
    lgb.Dataset(X, label=(X[:, 0] > 0).astype(np.float64),
                params={"tpu_compile_cache_dir": path,
                        "verbosity": -1}).construct()


@pytest.mark.parametrize("route", [_cache_by_call,
                                   _cache_by_dataset_params])
def test_environment_names_the_compile_cache(route, monkeypatch,
                                             tmp_path):
    """Where a cache is live (the suite's comes from
    JAX_COMPILATION_CACHE_DIR via conftest), ``tpu_compile_cache_dir``
    naming another directory warns and moves nothing, whether it comes
    by a direct call or through a Dataset's params at construct."""
    from lightgbm_tpu import config as config_mod
    live = jax.config.jax_compilation_cache_dir
    assert live == os.environ["JAX_COMPILATION_CACHE_DIR"]
    warned = []
    monkeypatch.setattr(config_mod.log, "warning", warned.append)
    route(str(tmp_path / "elsewhere"))
    assert jax.config.jax_compilation_cache_dir == live
    assert len(warned) == 1 and "ignored" in warned[0]
    route(live)                                  # the same one: silent
    route("")
    assert len(warned) == 1
    assert not (tmp_path / "elsewhere").exists()
