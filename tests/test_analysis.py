"""Static analysis suite (tools/analyze) + the capability-table sweep.

Three layers (ISSUE 10 acceptance):

1. per-checker FIXTURES — for each of the six drift linters, a
   snippet that MUST flag and a snippet that MUST pass, including the
   three historical drift-bug classes: a gate literal outside the
   capability table, a raw ``tpu_*`` param read, and a
   ``lax.switch``-wrapped collective (the PR 12 deadlock class), plus
   the use-after-donate class the ``tpu_donate`` pass introduces
   (donation-discipline);
2. allowlist hygiene — unexplained and stale entries are findings;
3. the extended drift-guard sweep — for EVERY engine, the capability
   table's verdicts agree with what the constructor actually does
   (table says fatal ⇒ constructor raises; base config ⇒ constructs),
   driven by the table's own ``example`` witnesses so a new row
   without a witness fails here;

plus the gate the whole PR exists for: ``python -m tools.analyze``
reports ZERO findings at HEAD.
"""
import pathlib
import sys

import numpy as np
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import lightgbm_tpu as lgb                                  # noqa: E402
from lightgbm_tpu import capabilities                       # noqa: E402
from lightgbm_tpu.config import Config                      # noqa: E402
from lightgbm_tpu.utils.log import LightGBMError            # noqa: E402
from tools.analyze import run, run_checker_on_source        # noqa: E402
from tools.analyze.core import Allowlist                    # noqa: E402


def _keys(findings):
    return {f.key for f in findings}


# ---------------------------------------------------------------------------
# the flagship gate: zero findings at HEAD, under the CI time budget
# ---------------------------------------------------------------------------
def test_suite_clean_at_head():
    """`python -m tools.analyze` must be green on the tree as
    committed: this test is the gate that holds the linters at zero
    findings."""
    findings = run()
    assert findings == [], "\n".join(f.render() for f in findings)


# ---------------------------------------------------------------------------
# checker 1: capability-gate — eligibility literals live in the table
# ---------------------------------------------------------------------------
def test_capability_gate_flags_inline_eligibility_literal():
    # the PR-5/PR-10/PR-12 drift class: a private copy of an
    # eligibility list (historical bug #1 re-introduced)
    src = (
        "def _my_gate(config):\n"
        "    return (config.objective in ('binary', 'regression')\n"
        "            and config.tree_learner not in ('voting',))\n")
    ks = _keys(run_checker_on_source("capability-gate", src))
    assert "objective@_my_gate" in ks
    assert "tree_learner@_my_gate" in ks
    # str()-wrapped reads are still reads
    src2 = ("def g(c):\n"
            "    return str(c.boosting) in ('dart', 'rf')\n")
    assert _keys(run_checker_on_source("capability-gate", src2)) \
        == {"boosting@g"}


def test_capability_gate_passes_table_driven_code():
    src = (
        "from lightgbm_tpu import capabilities\n"
        "def _my_gate(config):\n"
        "    # named constant from the table: fine\n"
        "    ok = config.objective in capabilities.AUTO_QUANTIZE_OBJECTIVES\n"
        "    # equality dispatch (not an eligibility list): fine\n"
        "    return ok and config.boosting == 'dart'\n"
        "def other(x):\n"
        "    # non-gate attributes are out of scope\n"
        "    return x.color in ('red', 'green')\n")
    assert run_checker_on_source("capability-gate", src) == []


# ---------------------------------------------------------------------------
# checker 2: config-knobs — raw reads, undeclared knobs, docs
# ---------------------------------------------------------------------------
def test_config_knobs_flags_raw_read_and_undeclared():
    # historical bug #2 re-introduced: a raw params.get with an inline
    # default — plus an undeclared (typo'd) knob read
    src = (
        "def f(params, cfg):\n"
        "    a = params.get('tpu_streaming', 'auto')\n"
        "    b = getattr(cfg, 'tpu_streming', 'auto')  # typo\n"
        "    return a, b\n")
    ks = _keys(run_checker_on_source("config-knobs", src))
    assert "raw-read:tpu_streaming" in ks
    assert "undeclared:tpu_streming" in ks


def test_config_knobs_passes_sanctioned_reads():
    src = (
        "from lightgbm_tpu.config import get_param\n"
        "def f(params, cfg):\n"
        "    a = get_param(params, 'tpu_streaming')\n"
        "    b = getattr(cfg, 'tpu_metrics', False)\n"
        "    c = cfg.tpu_fuse_iters\n"
        "    d = params.get('max_bin', 255)   # non-tpu: out of scope\n"
        "    return a, b, c, d\n")
    assert run_checker_on_source("config-knobs", src) == []


def test_every_declared_tpu_knob_is_documented():
    """The satellite audit, kept green forever: ~48 tpu_* knobs in
    config._PARAMS each appear in README.md or docs/*.md (checker 2's
    doc rule — run here without allowlists so a future allowlist
    cannot quietly mute it)."""
    from tools.analyze import config_knobs
    from tools.analyze.core import SourceSet
    sources = SourceSet(str(REPO_ROOT), [config_knobs.CONFIG_FILE])
    undocumented = [f for f in config_knobs.check(sources)
                    if f.key.startswith("undocumented:")]
    assert undocumented == [], "\n".join(f.render() for f in undocumented)
    # sanity: the rule actually sees the declaration table
    assert len([k for k in config_knobs.declared_knobs(sources)
                if k.startswith("tpu_")]) >= 40


# ---------------------------------------------------------------------------
# checker 3: obs-names — catalogue drift, both directions
# ---------------------------------------------------------------------------
def test_obs_names_flags_undocumented_metric():
    src = ("from lightgbm_tpu import obs\n"
           "def f():\n"
           "    obs.inc('totally.unknown_metric')\n")
    ks = _keys(run_checker_on_source("obs-names", src))
    assert "undocumented:totally.unknown_metric" in ks


def test_obs_names_passes_catalogued_names_and_wildcards():
    src = ("from lightgbm_tpu import obs\n"
           "def f():\n"
           "    obs.inc('train.iterations')\n"
           "    obs.set_gauge('slo.something_new', 1.0)  # slo.*\n"
           "    obs.span('train/round')\n")
    assert run_checker_on_source("obs-names", src) == []


def test_obs_names_doc_parsing_and_unemitted_direction():
    from tools.analyze.obs_names import _covered, documented_names
    exact, wild = documented_names(str(REPO_ROOT))
    # catalogue parsing: real names in, API/file tokens out
    assert "train.iterations" in exact
    assert "predict.stack_cache_misses" in exact
    assert "obs/rank_merge" in exact          # slash-named span kept
    assert "slo" in wild                      # `slo.*`
    assert _covered("slo.queue_wait_p99_ms", exact, wild)
    assert "bench" not in wild                # went with its emitters
    assert not any(t.endswith(".py") for t in exact)
    # docs→code: a catalogued name nothing emits is a finding (the
    # heartbeat gauges are exactly this shape — dynamic f-string
    # emission — and are allowlisted with that reason)
    al = Allowlist.load("obs-names")
    assert ("docs/observability.md", "unemitted:heartbeat.train") \
        in al.entries


# ---------------------------------------------------------------------------
# checker 4: collective-safety — the PR 12 deadlock class
# ---------------------------------------------------------------------------
def test_collective_safety_flags_switch_wrapped_collective():
    # historical bug #3 re-introduced: a collective inside a
    # lax.switch branch (direct, via branches-list, and transitive)
    src = (
        "import jax\n"
        "def _br(x):\n"
        "    return jax.lax.psum(x, 'd')\n"
        "def _helper(x):\n"
        "    return _br(x)          # transitive reach\n"
        "def f(i, x):\n"
        "    branches = []\n"
        "    branches.append(_helper)\n"
        "    return jax.lax.switch(i, branches, x)\n"
        "def g(p, x):\n"
        "    return jax.lax.cond(p, _br, lambda v: v, x)\n")
    ks = _keys(run_checker_on_source("collective-safety", src))
    assert "branch:_helper@f" in ks
    assert "branch:_br@g" in ks


def test_collective_safety_flags_rank_divergent_conditional():
    src = (
        "import jax\n"
        "def f(x):\n"
        "    if jax.process_index() == 0:\n"
        "        return jax.lax.psum(x, 'd')\n"
        "    return x\n")
    ks = _keys(run_checker_on_source("collective-safety", src))
    assert "rank-if:psum@f" in ks
    # the else/elif suites of a rank test are just as divergent
    src2 = (
        "import jax\n"
        "def g(x, rank):\n"
        "    if rank == 0:\n"
        "        x = x + 1\n"
        "    elif rank == 1:\n"
        "        x = x + 2\n"
        "    else:\n"
        "        x = jax.lax.psum(x, 'd')\n"
        "    return x\n")
    assert "rank-if:psum@g" in _keys(
        run_checker_on_source("collective-safety", src2))


def test_collective_safety_flags_thread_dispatched_collective():
    """The ISSUE 17 staging contract: a callable handed to a
    background thread (executor.submit / Thread(target=) / a
    BlockPrefetcher staging slot) must not reach a collective —
    per-rank launch order would become a thread-scheduling accident
    (gang deadlock). Bound-method references (`self._stage`) resolve
    by attr name like the module-local call graph does."""
    src = (
        "import jax\n"
        "from concurrent.futures import ThreadPoolExecutor\n"
        "import threading\n"
        "from lightgbm_tpu.utils.prefetch import BlockPrefetcher\n"
        "def _reduce(x):\n"
        "    return jax.lax.psum(x, 'd')\n"
        "def _stage(x):\n"
        "    return _reduce(x)       # transitive reach\n"
        "def f(pool, x):\n"
        "    return pool.submit(_reduce, x)\n"
        "def g(x):\n"
        "    t = threading.Thread(target=_stage, args=(x,))\n"
        "    t.start()\n"
        "class Eng:\n"
        "    def _stage(self, x):\n"
        "        return _reduce(x)\n"
        "    def h(self):\n"
        "        return BlockPrefetcher(self._stage, [1, 2])\n")
    ks = _keys(run_checker_on_source("collective-safety", src))
    assert "thread:_reduce@f" in ks
    assert "thread:_stage@g" in ks
    assert "thread:_stage@h" in ks


def test_collective_safety_passes_pure_staging_threads():
    # the shape streaming.py actually uses: the staged callable only
    # slices/pads/device_puts; the collective dispatches from the main
    # thread after the window push
    src = (
        "import jax\n"
        "from lightgbm_tpu.utils.prefetch import BlockPrefetcher\n"
        "def _stage(item):\n"
        "    return jax.device_put(item)\n"
        "def f(pool, sched, x):\n"
        "    pf = BlockPrefetcher(_stage, sched)\n"
        "    pool.submit(_stage, x)\n"
        "    h = pf.take()\n"
        "    return jax.lax.psum(h, 'd')   # main thread: fine\n")
    assert run_checker_on_source("collective-safety", src) == []


def test_collective_safety_passes_hoisted_collectives():
    # the shape serial.py actually uses: branches histogram locally,
    # the reduction wraps the switch RESULT
    src = (
        "import jax\n"
        "def _hist(x):\n"
        "    return x * 2\n"
        "def f(i, x):\n"
        "    branches = [_hist, _hist]\n"
        "    h = jax.lax.switch(i, branches, x)\n"
        "    return jax.lax.psum(h, 'd')\n"
        "def g(rank, x):\n"
        "    h = jax.lax.psum(x, 'd')   # outside the if: fine\n"
        "    if rank == 0:\n"
        "        h = h + 1\n"
        "    return h\n")
    assert run_checker_on_source("collective-safety", src) == []


# ---------------------------------------------------------------------------
# checker 5: lock-discipline — obs shared state under self._lock
# ---------------------------------------------------------------------------
_LOCK_REL = "lightgbm_tpu/obs/_fixture.py"


def test_lock_discipline_flags_unlocked_mutation():
    src = (
        "import threading\n"
        "class Tracker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.items = []\n"
        "        self.count = 0\n"
        "    def bad_append(self, x):\n"
        "        self.items.append(x)\n"
        "    def bad_assign(self):\n"
        "        self.count += 1\n")
    ks = _keys(run_checker_on_source("lock-discipline", src,
                                     rel=_LOCK_REL))
    assert ks == {"Tracker.bad_append:items", "Tracker.bad_assign:count"}


def test_lock_discipline_passes_locked_and_declared_helpers():
    src = (
        "import threading\n"
        "class Tracker:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.items = []\n"
        "    def good(self, x):\n"
        "        with self._lock:\n"
        "            self.items.append(x)\n"
        "    def _clear(self):\n"
        "        \"\"\"Caller holds the lock.\"\"\"\n"
        "        self.items.clear()\n"
        "    def read_only(self):\n"
        "        return len(self.items)\n"
        "class NoLock:\n"
        "    def __init__(self):\n"
        "        self.items = []\n"
        "    def fine(self, x):\n"
        "        self.items.append(x)   # class has no lock protocol\n")
    assert run_checker_on_source("lock-discipline", src,
                                 rel=_LOCK_REL) == []


def test_lock_discipline_scope_is_obs_only():
    src = ("import threading\n"
           "class T:\n"
           "    def __init__(self):\n"
           "        self._lock = threading.Lock()\n"
           "        self.x = 0\n"
           "    def bad(self):\n"
           "        self.x = 1\n")
    assert run_checker_on_source("lock-discipline", src,
                                 rel="lightgbm_tpu/engine_fixture.py") \
        == []


# ---------------------------------------------------------------------------
# checker 6: donation-discipline — donated references rebind before reads
# ---------------------------------------------------------------------------
def test_donation_discipline_flags_read_after_donate():
    # the use-after-donate class the tpu_donate pass introduces: the
    # jit deletes its donated argument buffer at dispatch, so the
    # later `score.sum()` reads a deleted array
    src = (
        "import jax\n"
        "_j = jax.jit(lambda s: s + 1, donate_argnums=(0,))\n"
        "def train(score):\n"
        "    out = _j(score)\n"
        "    return out + score.sum()\n")
    ks = _keys(run_checker_on_source("donation-discipline", src))
    assert ks == {"train._j:score"}


def test_donation_discipline_flags_unrebound_loop_carry():
    # a donating call in a loop whose carry is never reassigned in the
    # body re-reads the deleted buffer on the NEXT iteration
    src = (
        "import jax\n"
        "def train(score, keys):\n"
        "    _j = jax.jit(lambda s, k: s + k, donate_argnums=(0,))\n"
        "    for k in keys:\n"
        "        out = _j(score, k)\n"
        "    return out\n")
    ks = _keys(run_checker_on_source("donation-discipline", src))
    assert ks == {"train._j:score"}


def test_donation_discipline_flags_read_after_branch_and_self_attr():
    # reads in the continuation AFTER an `if` that donated, and the
    # __init__-builds / step-calls split on self attributes (the
    # class-scope pre-pass)
    src_if = (
        "import jax\n"
        "def f(score, c):\n"
        "    _j = jax.jit(lambda s: s + 1, donate_argnums=(0,))\n"
        "    if c:\n"
        "        out = _j(score)\n"
        "    return score.sum()\n")
    assert _keys(run_checker_on_source(
        "donation-discipline", src_if)) == {"f._j:score"}
    src_self = (
        "import jax\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self._j = jax.jit(lambda s: s + 1,\n"
        "                          donate_argnums=(0,))\n"
        "    def step(self):\n"
        "        out = self._j(self.score)\n"
        "        return out + self.score\n")
    assert _keys(run_checker_on_source(
        "donation-discipline", src_self)) == {"step.self._j:self.score"}


def test_donation_discipline_passes_rebound_carries():
    # the sanctioned shapes: `score = step(score)` loop carries,
    # return-only wrapper call sites (boosting/gbdt.py's closures),
    # conditional donate_argnums resolved through a local name, and
    # jits that do not donate at all
    src = (
        "import jax\n"
        "def train(score, keys):\n"
        "    _j = jax.jit(lambda s, k: s + k, donate_argnums=(0,))\n"
        "    for k in keys:\n"
        "        score = _j(score, k)\n"
        "    return score\n"
        "def make(guard, flag):\n"
        "    _don = (4,) if flag else ()\n"
        "    _j2 = guard(jax.jit(lambda *a: a[4],\n"
        "                        donate_argnums=_don), 'site')\n"
        "    def step(score):\n"
        "        return _j2(0, 1, 2, 3, score)\n"
        "    return step\n"
        "def plain(score):\n"
        "    _nj = jax.jit(lambda s: s + 1)\n"
        "    out = _nj(score)\n"
        "    return out + score\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self._j = jax.jit(lambda s: s + 1,\n"
        "                          donate_argnums=(0,))\n"
        "    def step(self):\n"
        "        self.score = self._j(self.score)\n"
        "        return self.score\n")
    assert run_checker_on_source("donation-discipline", src) == []


# ---------------------------------------------------------------------------
# allowlist hygiene: exceptions must be explained AND alive
# ---------------------------------------------------------------------------
def test_allowlist_unexplained_and_stale_entries_are_findings(tmp_path):
    path = tmp_path / "demo.txt"
    path.write_text(
        "# demo\n"
        "a.py:key-with-reason  the reason\n"
        "b.py:key-without-reason\n")
    al = Allowlist.load("demo", str(path))
    # nothing filtered -> both entries unmatched; the reasoned one is
    # "stale", the bare one "unexplained"
    al.filter([])
    msgs = [f.message for f in al.hygiene_findings()]
    assert any("no reason" in m for m in msgs)
    assert any("stale" in m for m in msgs)


def test_live_allowlists_are_all_explained():
    from tools.analyze import CHECKERS
    for name in CHECKERS:
        al = Allowlist.load(name)
        assert al.unexplained == [], name


# ---------------------------------------------------------------------------
# the extended drift-guard sweep: table ⟺ constructor, EVERY engine
# ---------------------------------------------------------------------------
def _data(n=640, f=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float64)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float64)
    return X, y


_BASE = {"objective": "binary", "num_leaves": 8, "verbosity": -1,
         "min_data_in_leaf": 5, "tpu_stream_block_rows": 64}
# per-engine params that make the PLAIN base construct
_ENGINE_BASE = {
    "gbdt": {},
    "dart": {"boosting": "dart"},
    "rf": {"boosting": "rf", "bagging_freq": 1, "bagging_fraction": 0.8},
    "streaming": {},
}


def _engine_cls(engine):
    from lightgbm_tpu.boosting.dart import DART
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.boosting.rf import RandomForest
    from lightgbm_tpu.boosting.streaming import StreamingGBDT
    return {"gbdt": GBDT, "dart": DART, "rf": RandomForest,
            "streaming": StreamingGBDT}[engine]


@pytest.mark.parametrize("engine", capabilities.ENGINES)
def test_engine_base_config_constructs(engine):
    """supported ⇒ constructs: every engine accepts its base config
    (the sweep's positive control)."""
    X, y = _data()
    cfg = Config({**_BASE, **_ENGINE_BASE[engine]})
    assert capabilities.supports(engine, cfg)
    eng = _engine_cls(engine)(cfg, lgb.Dataset(X, label=y))
    assert eng is not None


_FATAL_CASES = [
    (feature, engine)
    for feature, cap in capabilities.CAPABILITIES.items()
    for engine, v in cap.verdicts.items()
    if v == capabilities.FATAL and cap.example is not None
]


@pytest.mark.parametrize("feature,engine", _FATAL_CASES,
                         ids=[f"{e}-{f}" for f, e in _FATAL_CASES])
def test_table_fatal_means_constructor_refuses(feature, engine):
    """fatal ⇒ raises: every FATAL (feature, engine) cell, witnessed
    by the table's own example params, must make that engine's
    constructor raise — re-introducing a gate on one side without the
    other goes red here (the drift that produced the PR-5 bugs)."""
    cap = capabilities.CAPABILITIES[feature]
    params = {**_BASE, **_ENGINE_BASE[engine], **cap.example}
    cfg = Config(params)
    assert cap.requested(cfg), (feature, "example does not witness")
    assert not capabilities.supports(engine, cfg)
    X, y = _data()
    with pytest.raises(LightGBMError):
        _engine_cls(engine)(cfg, lgb.Dataset(X, label=y))


def test_every_fatal_row_has_a_witness():
    """A FATAL cell without example params cannot ride the sweep —
    only the runtime-only features (constructor kwargs, covered
    below) are exempt."""
    runtime_only = {"continuation"}
    missing = [f for f, cap in capabilities.CAPABILITIES.items()
               if capabilities.FATAL in cap.verdicts.values()
               and cap.example is None and f not in runtime_only]
    assert missing == []


def test_streaming_runtime_extras_fatal():
    """The runtime-only features (a custom fobj, init_forest
    continuation) fatal through the same table walk."""
    from lightgbm_tpu.boosting.streaming import StreamingGBDT
    X, y = _data()
    cfg = Config(dict(_BASE))
    with pytest.raises(LightGBMError):
        StreamingGBDT(cfg, lgb.Dataset(X, label=y),
                      fobj=lambda preds, ds: (preds, preds))
    with pytest.raises(LightGBMError):
        StreamingGBDT(cfg, lgb.Dataset(X, label=y),
                      init_forest=[object()])


def test_streaming_demote_drops_auto_quantize_only():
    """DEMOTE semantics: auto-enabled quantization is quietly dropped
    by the streaming engine; an EXPLICIT use_quantized_grad survives."""
    from lightgbm_tpu.boosting.streaming import StreamingGBDT
    X, y = _data()
    cfg = Config(dict(_BASE))
    cfg.use_quantized_grad = True
    cfg._quantize_auto = True            # as GBDT's auto switch sets it
    StreamingGBDT(cfg, lgb.Dataset(X, label=y))
    assert cfg.use_quantized_grad is False
    cfg2 = Config(dict(_BASE, use_quantized_grad=True))
    StreamingGBDT(cfg2, lgb.Dataset(X, label=y))
    assert cfg2.use_quantized_grad is True


def test_unhandled_demote_row_fails_loudly(monkeypatch):
    """A DEMOTE table row without a demotion action in StreamingGBDT
    must fatal, not silently no-op — the one-side-edited drift class."""
    from lightgbm_tpu.boosting.streaming import StreamingGBDT
    fake = capabilities.Capability(
        "a future demotable feature", lambda c: True,
        {"streaming": capabilities.DEMOTE})
    monkeypatch.setitem(capabilities.CAPABILITIES, "future_demote", fake)
    X, y = _data()
    with pytest.raises(LightGBMError, match="no.*demotion action"):
        StreamingGBDT(Config(dict(_BASE)), lgb.Dataset(X, label=y))


def test_streaming_compatible_is_the_table():
    """_streaming_compatible (the auto-router's gate) IS the table's
    streaming column — spot-check both polarities so the indirection
    cannot quietly break."""
    from lightgbm_tpu.boosting import _streaming_compatible
    ok = Config(dict(_BASE, tree_learner="data",
                     use_quantized_grad=True))
    assert _streaming_compatible(ok)
    assert capabilities.supports("streaming", ok)
    bad = Config(dict(_BASE, linear_tree=True))
    assert not _streaming_compatible(bad)
    assert "linear_tree" in capabilities.fatal_features("streaming", bad)
