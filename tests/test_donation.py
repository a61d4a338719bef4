"""Buffer donation (``tpu_donate``; docs/perf.md "Iteration floor").

The donation pass aliases the boosting carries in place
(``jax.jit(donate_argnums=...)`` on the per-step / fused-chunk /
valid-update / streamed-final-sweep jits) instead of copying them
through every dispatch. Donation changes WHERE the output lives, never
what it is — so the whole pass is pinned by bit-identity:

- donation-on vs donation-off models are BIT-IDENTICAL across
  {per-iter, fused-chunk, sharded, streamed} x {plain, GOSS,
  quantized};
- valid-set score carries donate too: eval trajectories and the
  early-stop decision match exactly;
- enabling donation adds ZERO XLA programs (CompileWatch: warm
  donated iterations compile nothing, and a donated cold train
  requests no more compiles than an undonated one);
- the ``tpu_debug_checks`` use-after-donate guard turns the latent
  "Array has been deleted" crash of a stale score reference into a
  LightGBMError naming the donating site (the runtime twin of the
  donation-discipline linter, docs/static-analysis.md).

Both arms of every A/B run in this process, against the suite's
persistent compilation cache (conftest.py): donating executables
reloaded from that cache are part of what is tested.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.utils.debug import CompileWatch, donation_enabled
from lightgbm_tpu.utils.log import LightGBMError

N_ROUNDS = 8
VALID_ROUNDS = 12

# learning_rate 0.5 -> GOSS activates at iteration 2 of 8, so the GOSS
# variants exercise BOTH the plain and the sampled step under donation
VARIANTS = {
    "plain": {},
    "goss": {"data_sample_strategy": "goss", "learning_rate": 0.5,
             "top_rate": 0.3, "other_rate": 0.3},
    "quantized": {"use_quantized_grad": True},
}

MODES = {
    "per_iter": {"tpu_fuse_iters": 1},
    "fused_chunk": {"tpu_fuse_iters": 4},
    "sharded": {"tree_learner": "data"},
    "streamed": {"tpu_streaming": "true",
                 "tpu_stream_block_rows": 1024},
}


def make_data(n=2048, f=8, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    w = rng.normal(size=f)
    y = ((X @ w + 0.6 * X[:, 0] * X[:, 1]
          + rng.normal(scale=0.5, size=n)) > 0).astype(np.float64)
    return X, y


def params_for(extra, donate):
    return {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "tpu_donate": donate, **extra}


def test_donation_is_live_off_tpu():
    """The A/B is only real if the donate-true arm actually donates:
    the config resolves to enabled and the CPU client deletes a
    donated input at dispatch."""
    assert donation_enabled(Config(params_for({}, "true")))
    probe = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    a = jnp.ones((8, 8))
    probe(a)
    assert a.is_deleted()


def test_tristate_resolution_with_persistent_cache():
    """"true" donates on any backend with the persistent compilation
    cache configured (as it is for this suite, via conftest); "auto"
    stays off away from the TPU; "false" is off everywhere."""
    assert jax.default_backend() != "tpu"
    assert jax.config.jax_compilation_cache_dir  # conftest set it
    assert donation_enabled(Config(params_for({}, "true")))
    assert not donation_enabled(Config(params_for({}, "auto")))
    assert not donation_enabled(Config(params_for({}, "false")))


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("mode", sorted(MODES))
def test_bit_identical_donation_on_off(mode, variant):
    X, y = make_data()
    out = {}
    for donate in ("true", "false"):
        p = params_for({**MODES[mode], **VARIANTS[variant]}, donate)
        m = lgb.train(p, lgb.Dataset(X, label=y),
                      num_boost_round=N_ROUNDS)
        out[donate] = (m.model_to_string(),
                       m.predict(X, raw_score=True))
    assert np.array_equal(out["true"][1], out["false"][1])
    assert out["true"][0] == out["false"][0]


def test_valid_scores_donation_matches_eval_trajectory():
    # valid carries ride _valid_update_j's donated list (the per-iter
    # path: valid sets disable fusion); the recorded eval trajectory
    # and the early-stop decision must be unchanged
    Xt, yt = make_data(seed=3)
    Xv, yv = make_data(n=1024, seed=4)
    out = {}
    for donate in ("true", "false"):
        rec = {}
        ds = lgb.Dataset(Xt, label=yt)
        bst = lgb.train(
            params_for({"metric": "binary_logloss"}, donate), ds,
            num_boost_round=VALID_ROUNDS,
            valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)],
            valid_names=["v"],
            callbacks=[lgb.record_evaluation(rec),
                       lgb.early_stopping(5, verbose=False)])
        out[donate] = (rec, bst.best_iteration)
    assert out["true"] == out["false"]


def test_donation_adds_zero_programs():
    """The compile pin, both halves: warm donated iterations compile
    NOTHING, and the donated cold train requests no more compiles than
    its undonated twin (donation aliases buffers inside the same
    programs — it must never introduce one; compile REQUESTS count
    persistent-cache hits too, so the two counts compare
    like-for-like)."""
    X, y = make_data(seed=5)
    cold = {}
    for donate in ("true", "false"):
        eng = GBDT(Config(params_for({"tpu_fuse_iters": 4}, donate)),
                   lgb.Dataset(X, label=y))
        with CompileWatch(f"cold donate={donate}") as w:
            eng.train_chunk(8)
        cold[donate] = w.compiles
        if donate == "true":
            with CompileWatch("warm donated") as w_warm:
                eng.train_chunk(8)
            w_warm.assert_compiles(0)
    assert cold["true"] <= cold["false"], (
        f"enabling donation added programs: {cold['true']} compile "
        f"request(s) donated vs {cold['false']} undonated")


def test_use_after_donate_guard_fires_on_stale_score():
    """tpu_debug_checks turns the stale-reference crash into an error
    naming the donating site: re-feeding a score buffer the previous
    iteration already donated fails with the guard's message, not
    XLA's generic deleted-array error."""
    X, y = make_data(seed=6)
    eng = GBDT(Config(params_for({"tpu_debug_checks": True}, "true")),
               lgb.Dataset(X, label=y))
    stale = eng.score
    eng.train_one_iter()
    assert stale.is_deleted()
    eng.score = stale
    with pytest.raises(LightGBMError) as e:
        eng.train_one_iter()
    assert "use-after-donate" in str(e.value)
    assert "the step's donated score" in str(e.value)


def test_guard_silent_without_donate():
    # tpu_donate=false: the same stale-rebind is harmless (no buffer
    # was deleted), so training proceeds — the no-donate arm keeps
    # today's copy semantics
    X, y = make_data(seed=7)
    p = params_for({"tpu_debug_checks": True}, "false")
    eng = GBDT(Config(p), lgb.Dataset(X, label=y))
    s0 = eng.score
    eng.train_one_iter()
    assert not s0.is_deleted()
    eng.score = s0
    eng.train_one_iter()          # re-boosting from the old score is
    assert eng.num_trees() == 2   # numerically odd but not a crash
