"""Device-native SHAP (``pred_contrib``) through the engine and the
serving stack (docs/perf.md "Device SHAP"; docs/serving.md "Mixed
predict + explain workloads").

What these tests pin:

* **Exactness** — the engine path (path-table cache + bucketed chunked
  dispatch) is f64-EXACT on CPU against the host rows-vectorized
  ``forest_shap_batch`` across binary / multiclass / categorical /
  NaN forests and ``num_iteration`` slices (the host path is itself
  pinned to the per-row recursive oracle in test_shap_vectorized.py).
* **Zero warm compiles** — after one call at a bucket, SHAP at any
  request size inside warmed buckets compiles ZERO XLA programs
  (CompileWatch), the same pow2-bucket guarantee predict carries.
* **Path-table cache** — hits counted, invalidated by forest growth,
  never shared across ``num_iteration`` slices.
* **Tree sharding** — the ``shard_map``+psum scan over 2- and 8-device
  tree meshes matches the unsharded result to f64 reassociation
  tolerance, gated by ``capabilities.SHARDED_SHAP`` (DART and
  linear-tree configs demote to the host path with a warned
  stand-down, never a refusal).
* **(model, kind) queue lanes** — explain riders never coalesce into
  predict batches; served contributions are exact.
"""
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import capabilities, obs
from lightgbm_tpu.serve import PredictService
from lightgbm_tpu.serve.shard import enable_tree_sharding, tree_mesh
from lightgbm_tpu.utils.debug import CompileWatch

pytestmark = pytest.mark.filterwarnings("ignore")


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _train(n=2000, f=8, with_cat=False, with_nan=False, seed=0,
           num_leaves=15, rounds=8, objective="regression", **extra):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    logit = X[:, 0] * 1.2 - 0.8 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
    cat_idx = []
    if with_cat:
        c = rng.integers(0, 9, size=n)
        X[:, f - 1] = c
        logit = logit + np.where(c % 3 == 0, 1.0, -0.4)
        cat_idx = [f - 1]
    if with_nan:
        miss = rng.uniform(size=n) < 0.15
        X[miss, 0] = np.nan
    if objective == "binary":
        y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(float)
    elif objective == "multiclass":
        y = rng.integers(0, 3, size=n).astype(float)
    else:
        y = logit + rng.normal(scale=0.3, size=n)
    params = {"objective": objective, "num_leaves": num_leaves,
              "verbosity": -1, **extra}
    if objective == "multiclass":
        params["num_class"] = 3
    bst = lgb.train(params, lgb.Dataset(X, label=y,
                                        categorical_feature=cat_idx),
                    num_boost_round=rounds)
    return bst, X


# ---------------------------------------------------------------------------
# exactness vs the host path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("with_cat,with_nan,objective", [
    (False, False, "regression"),
    (True, False, "regression"),
    (False, True, "binary"),
    (True, True, "binary"),
    (False, False, "multiclass"),
])
def test_engine_matches_host(with_cat, with_nan, objective):
    bst, X = _train(with_cat=with_cat, with_nan=with_nan,
                    objective=objective)
    got = bst.predict(X[:300], pred_contrib=True)
    want = bst._to_host_model().predict(X[:300], pred_contrib=True)
    # CPU backend: both sides run the same f64 kernel; the engine pads
    # rows to its pow2 bucket, which is allowed to move XLA's
    # vectorization by one ULP — nothing more
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_num_iteration_slices_match_host():
    bst, X = _train(with_cat=True, with_nan=True, objective="binary",
                    rounds=10)
    hm = bst._to_host_model()
    for kw in ({"num_iteration": 4}, {"start_iteration": 3},
               {"start_iteration": 2, "num_iteration": 5}):
        got = bst.predict(X[:200], pred_contrib=True, **kw)
        want = hm.predict(X[:200], pred_contrib=True, **kw)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


def test_local_accuracy_multiclass():
    bst, X = _train(objective="multiclass", rounds=6)
    n_feat = X.shape[1]
    contrib = bst.predict(X[:200], pred_contrib=True)
    raw = bst.predict(X[:200], raw_score=True)
    per_class = contrib.reshape(len(raw), 3, n_feat + 1).sum(axis=2)
    # raw predictions ride the f32 device path; SHAP sums are f64
    np.testing.assert_allclose(per_class, raw, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# compile discipline + the path-table cache
# ---------------------------------------------------------------------------
def test_zero_warm_compiles_across_sizes():
    bst, X = _train(rounds=6)
    for n in (128, 256):            # warm both pow2 buckets the sizes
        bst.predict(X[:n], pred_contrib=True)     # below land in
    with CompileWatch("warm-shap") as w:
        for n in (1, 7, 64, 128, 200, 256):       # inside warm buckets
            bst.predict(X[:n], pred_contrib=True)
    w.assert_compiles(0)


def test_table_cache_hits_and_invalidation():
    bst, X = _train(rounds=6)
    obs.enable(metrics=True)
    eng = bst.engine

    def counter(name):
        m = obs.registry().get(name)
        return getattr(m, "value", 0.0) or 0.0

    bst.predict(X[:64], pred_contrib=True)
    assert counter("predict.contrib_cache_misses") == 1.0
    bst.predict(X[:64], pred_contrib=True)
    assert counter("predict.contrib_cache_hits") >= 1.0
    # a num_iteration slice is a different table set, never a hit
    bst.predict(X[:64], pred_contrib=True, num_iteration=3)
    assert counter("predict.contrib_cache_misses") == 2.0
    # forest growth/eviction drops the device tables with the stack
    eng._invalidate_forest_cache()
    assert eng._shap_cache is None
    misses = counter("predict.contrib_cache_misses")
    bst.predict(X[:64], pred_contrib=True)
    assert counter("predict.contrib_cache_misses") == misses + 1.0


def test_hostmodel_caches_path_tables_per_slice():
    bst, X = _train(rounds=8)
    hm = bst._to_host_model()
    a = hm.predict(X[:32], pred_contrib=True)
    cache = hm._shap_table_cache
    assert len(cache) == 1
    key, tables = next(iter(cache.items()))
    hm.predict(X[:32], pred_contrib=True, num_iteration=3)
    assert len(cache) == 2                 # slice = its own tables
    hm.predict(X[:32], pred_contrib=True)
    assert cache[key] is tables            # full-forest call reused
    np.testing.assert_array_equal(a, hm.predict(X[:32],
                                                pred_contrib=True))


# ---------------------------------------------------------------------------
# tree-sharded SHAP
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("devices", [2, 8])
def test_sharded_matches_unsharded(devices):
    bst, X = _train(with_cat=True, with_nan=True, objective="binary",
                    rounds=8)
    want = bst.predict(X[:200], pred_contrib=True)
    mesh = enable_tree_sharding(bst, tree_mesh(devices))
    assert mesh is not None
    assert bst.engine._predict_mesh is mesh
    got = bst.predict(X[:200], pred_contrib=True)
    # f64 on the CPU backend: the only difference is the psum's
    # reduction order across shards
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)
    sliced = bst.predict(X[:200], pred_contrib=True, num_iteration=4)
    want_sliced = bst._to_host_model().predict(
        X[:200], pred_contrib=True, num_iteration=4)
    np.testing.assert_allclose(sliced, want_sliced, rtol=0, atol=1e-12)


def test_sharded_scan_goes_through_mesh_wrapper(monkeypatch):
    """The sharded SHAP scan is built by ``parallel.mesh.shard_map``
    like every other sharded program of the package (it once imported
    JAX's own and so ran with a replication check the others turn
    off). A 4-device mesh keeps its program out of the kernel cache
    the [2]/[8] cases above fill."""
    from lightgbm_tpu.parallel import mesh as mesh_lib
    seen = []
    real = mesh_lib.shard_map

    def spy(f, **kwargs):
        seen.append(kwargs)
        return real(f, **kwargs)

    monkeypatch.setattr(mesh_lib, "shard_map", spy)
    bst, X = _train(objective="binary", rounds=8)
    want = bst.predict(X[:64], pred_contrib=True)
    mesh = enable_tree_sharding(bst, tree_mesh(4))
    got = bst.predict(X[:64], pred_contrib=True)
    assert [k["mesh"] for k in seen] == [mesh]
    assert seen[0]["check_vma"] is False
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)


# ---------------------------------------------------------------------------
# capability gate
# ---------------------------------------------------------------------------
def test_capability_verdicts():
    assert capabilities.sharded_shap_verdict("gbdt") \
        == capabilities.SUPPORTED
    for eng in ("dart", "rf", "streaming"):
        assert capabilities.sharded_shap_verdict(eng) \
            == capabilities.DEMOTE
        assert eng in capabilities.SHARDED_SHAP_MESSAGES

    class _Cfg:
        linear_tree = True
    assert capabilities.sharded_shap_verdict("gbdt", _Cfg()) \
        == capabilities.DEMOTE


def test_dart_demotes_to_host_path_with_one_warning():
    bst, X = _train(objective="binary", rounds=6, boosting="dart")
    got = bst.predict(X[:100], pred_contrib=True)
    want = bst._to_host_model().predict(X[:100], pred_contrib=True)
    np.testing.assert_array_equal(got, want)
    assert getattr(bst, "_warned_shap_demote", False)
    # the demoted engine never built device SHAP state
    assert getattr(bst.engine, "_shap_cache", None) is None


# ---------------------------------------------------------------------------
# serving: (model, kind) lanes
# ---------------------------------------------------------------------------
def test_service_explain_lanes_never_coalesce_with_predicts():
    bst, X = _train(rounds=4, num_leaves=8)
    obs.enable(metrics=True)
    svc = PredictService({"tpu_serve_batch_budget_ms": 150.0,
                          "tpu_serve_max_batch_rows": 1024,
                          "tpu_serve_shard_trees": "false"})
    try:
        svc.add_model("m", bst)
        Xq = X[:64]
        futs = ([svc.submit("m", Xq) for _ in range(3)]
                + [svc.submit("m", Xq, kind="contrib")
                   for _ in range(3)])
        outs = [f.result(timeout=30) for f in futs]
        direct_p = bst.predict(Xq)
        direct_c = bst.predict(Xq, pred_contrib=True)
        for out in outs[:3]:
            np.testing.assert_array_equal(out, direct_p)
        for out in outs[3:]:
            # coalesced riders run at a bigger row bucket than the
            # direct call — ULP-only freedom, like engine-vs-host
            np.testing.assert_allclose(out, direct_c, rtol=0,
                                       atol=1e-12)
        reg = obs.registry()
        # one batch per lane: the 6 riders coalesced into exactly 2
        # kind-homogeneous dispatches, never a mixed batch
        assert reg.get("serve.dispatches").value == 2.0
        assert reg.get("serve.explain_requests").value == 3.0
        with pytest.raises(ValueError):
            svc.submit("m", Xq, kind="leaf")
    finally:
        svc.close()


def test_service_warmup_contrib_then_zero_compiles():
    bst, X = _train(rounds=4, num_leaves=8)
    svc = PredictService({"tpu_serve_batch_budget_ms": 2.0,
                          "tpu_serve_max_batch_rows": 512,
                          "tpu_serve_shard_trees": "false"})
    try:
        svc.add_model("m", bst)
        svc.warmup("m", X[:1], kinds=("predict", "contrib"))
        Xq = X[:96]
        with CompileWatch("warm-serve-shap") as w:
            out = svc.submit("m", Xq, kind="contrib").result(timeout=30)
            stop = threading.Event()
            stop.wait(0.01)
        w.assert_compiles(0)
        np.testing.assert_allclose(
            out, bst.predict(Xq, pred_contrib=True), rtol=0,
            atol=1e-12)
    finally:
        svc.close()


def test_service_mixed_load_drops_nothing_and_feeds_the_explain_slo():
    """Concurrent clients sending predicts and explains at once through
    one warm service: every request resolves, nothing compiles,
    ``serve.explain_requests`` counts every explain rider, and the
    explain window behind ``slo.explain_p99_ms`` is live."""
    from lightgbm_tpu.obs import slo as obs_slo
    bst, X = _train(rounds=4, num_leaves=8)
    obs.enable(metrics=True, slo=True)
    svc = PredictService({"tpu_serve_batch_budget_ms": 2.0,
                          "tpu_serve_max_batch_rows": 512,
                          "tpu_serve_shard_trees": "false"})
    explains, predicts, drops = [], [], []
    stop = threading.Event()

    def client(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            rows = X[rng.integers(0, len(X), size=64)]
            try:
                if rng.uniform() < 0.5:
                    svc.submit("m", rows, kind="contrib").result(
                        timeout=30)
                    explains.append(1)
                else:
                    svc.predict("m", rows, timeout=30)
                    predicts.append(1)
            except Exception as e:   # noqa: BLE001 - a drop IS the bug
                drops.append(e)

    try:
        svc.add_model("m", bst)
        svc.warmup("m", X[:1], kinds=("predict", "contrib"))
        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True) for i in range(4)]
        with CompileWatch("warm-mixed-load") as w:
            for t in threads:
                t.start()
            stop.wait(0.8)
            stop.set()
            for t in threads:
                t.join(timeout=30)
        w.assert_compiles(0)
    finally:
        svc.close()
    assert not drops, f"dropped {len(drops)}: {drops[:3]}"
    assert explains and predicts, "the window ran only one kind"
    assert obs.registry().get("serve.explain_requests").value >= \
        len(explains)
    assert obs_slo.tracker().compute()["slo.explain_p99_ms"] is not None
