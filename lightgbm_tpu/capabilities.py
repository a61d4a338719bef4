"""THE capability table: feature × engine → supported / demote / fatal.

Reference LightGBM dispatches one config surface across boosting modes
(``Boosting::CreateBoosting``) and tree learners with the eligibility
rules scattered through constructors; through PR 12 this reproduction
was growing the same fragmentation — ``_streaming_compatible`` vs
StreamingGBDT's ``_no()`` gates drifted into bugs three separate times,
and the device-ingest / hist-partition / auto-quantize auto modes each
encoded their own eligibility lists (ROADMAP item 4).

This module is the ONE place those judgments live:

- :data:`CAPABILITIES` — the declarative feature × engine table. A
  *feature* is a named predicate over a resolved :class:`~.config.Config`
  (plus the runtime-only features a constructor sees: a custom ``fobj``,
  ``init_forest`` continuation). An *engine* is one of
  :data:`ENGINES`. The verdict is :data:`SUPPORTED` (engine trains it),
  :data:`DEMOTE` (engine trains it after quietly dropping the feature —
  only ever auto-applied features), or :data:`FATAL` (engine must
  refuse at construction).
- The **eligibility constants** the auto modes consume
  (:data:`AUTO_QUANTIZE_OBJECTIVES`, :data:`STRATIFIABLE_OBJECTIVES`,
  :data:`STREAM_MAX_LEAVES`, ...). Inline copies of these lists
  anywhere else in the tree are flagged by the capability-gate checker
  (``python -m tools.analyze``, docs/static-analysis.md).
- The **auto-mode policies** that route between engines/paths:
  :func:`hist_partition_auto` (the ``tpu_hist_partition=auto`` cost
  model) and :func:`device_ingest_verdict` (can the engine these params
  force adopt device-resident ingest output?).

Consumers: ``boosting.create_boosting`` / ``_streaming_compatible``,
``StreamingGBDT.__init__``, ``RandomForest.__init__``,
``Dataset._want_device_ingest``, ``GBDT.__init__`` (auto-quantize +
hist-partition), ``engine.cv`` (stratification). The drift-guard sweeps
in tests/test_analysis.py and tests/test_streaming_sharded.py pin
table ⟺ constructor agreement for every engine: a gate added or lifted
on one side without the other goes red in CI.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

__all__ = [
    "SUPPORTED", "DEMOTE", "FATAL", "ENGINES", "CAPABILITIES",
    "Capability", "requested_features", "verdict", "engine_verdicts",
    "fatal_features", "demoted_features", "supports",
    "RANKING_OBJECTIVES", "AUTO_QUANTIZE_OBJECTIVES",
    "AUTO_QUANT_MIN_ROWS", "STRATIFIABLE_OBJECTIVES",
    "MULTI_TREE_OBJECTIVES",
    "STREAM_MAX_LEAVES", "STREAM_TREE_LEARNERS",
    "HIST_PARTITION_MIN_ROWS", "hist_partition_auto",
    "DEVICE_INGEST", "device_ingest_verdict", "forced_engine",
    "SHARDED_PREDICT", "sharded_predict_verdict",
    "SHARDED_SHAP", "SHARDED_SHAP_MESSAGES", "sharded_shap_verdict",
    "STREAM_RECUT", "stream_recut_verdict",
    "stream_recut_verdict_params",
]

SUPPORTED = "supported"
DEMOTE = "demote"
FATAL = "fatal"

# the boosting engines create_boosting can return (serving rides GBDT's
# predict surface and has no construction gates of its own)
ENGINES = ("gbdt", "dart", "rf", "streaming")

# ---------------------------------------------------------------------------
# Eligibility constants (the auto modes' lists — keep them HERE)
# ---------------------------------------------------------------------------
# objectives whose training is a ranking problem (need query groups;
# streamed level sweeps cannot evaluate listwise lambdas per block)
RANKING_OBJECTIVES = ("lambdarank", "rank_xendcg")

# tpu_auto_quantize only flips use_quantized_grad on for objectives the
# round-5 >=500k-row equal-round A/B validated at equal-or-better
# holdout quality (docs/perf.md "quantized by default")
AUTO_QUANTIZE_OBJECTIVES = ("binary", "regression", "multiclass",
                            "multiclassova", "cross_entropy")
# ... and only at the scale the A/B measured; below it the exact-f32
# default keeps reference bit-compatibility
AUTO_QUANT_MIN_ROWS = 500_000

# classification objectives cv() can stratify folds for
STRATIFIABLE_OBJECTIVES = ("binary", "multiclass", "multiclassova")

# objectives training one tree PER CLASS per iteration
# (Config.num_tree_per_iteration)
MULTI_TREE_OBJECTIVES = ("multiclass", "multiclassova")

# streaming keeps per-row leaf ids in int16 device state
STREAM_MAX_LEAVES = 32767
# streamed training shards ROWS; voting/feature-parallel split search
# needs the resident column layout
STREAM_TREE_LEARNERS = ("serial", "data")

# tpu_hist_partition=auto only engages where the repartition move
# amortizes (pool-mode Pallas path over a large un-compacted source)
HIST_PARTITION_MIN_ROWS = 1 << 20


class Capability(NamedTuple):
    """One table row: how to detect the feature + per-engine verdicts."""

    describe: str                           # phrase for fatal messages
    requested: Callable[[Any], bool]        # predicate over Config
    verdicts: Dict[str, str]                # engine -> verdict;
    #                                         absent engine = SUPPORTED
    example: Optional[Dict[str, Any]] = None  # params witnessing the
    #                                           feature (sweep tests)
    messages: Dict[str, str] = {}           # engine -> exact fatal text
    #                                         (back-compat error wording)


def _has_cegb(c) -> bool:
    # StreamingGBDT rejects ANY CEGB knob, including a bare non-default
    # cegb_tradeoff
    return (c.cegb_tradeoff != 1.0 or c.cegb_penalty_split > 0
            or bool(c.cegb_penalty_feature_coupled)
            or bool(c.cegb_penalty_feature_lazy))


def _no_bagging(c) -> bool:
    return not (c.bagging_freq > 0
                and (c.bagging_fraction < 1.0
                     or c.pos_bagging_fraction < 1.0
                     or c.neg_bagging_fraction < 1.0))


# ---------------------------------------------------------------------------
# THE TABLE. Every entry name is also the key runtime `extra` flags use
# (StreamingGBDT passes extra={"custom_objective": fobj is not None, ...}).
# `example` params must make the predicate True on top of any base
# config — tests/test_analysis.py constructs every FATAL (feature,
# engine) pair from them and asserts the constructor refuses.
# ---------------------------------------------------------------------------
CAPABILITIES: Dict[str, Capability] = {
    "custom_objective": Capability(
        "a custom objective function",
        lambda c: str(c.objective) == "custom",
        {"streaming": FATAL},
        example={"objective": "custom"}),
    "continuation": Capability(
        "training continuation/init_model",
        lambda c: False,                    # runtime-only (init_forest)
        {"streaming": FATAL}),
    "multiclass": Capability(
        "multiclass",
        lambda c: c.num_tree_per_iteration > 1,
        {"streaming": FATAL},
        example={"objective": "multiclass", "num_class": 3}),
    "ranking_objective": Capability(
        "ranking objectives",
        lambda c: str(c.objective) in RANKING_OBJECTIVES,
        {"streaming": FATAL},
        example={"objective": "lambdarank"}),
    "nonrow_tree_learner": Capability(
        f"tree_learner outside {STREAM_TREE_LEARNERS} (streamed "
        f"training shards ROWS; voting/feature-parallel search needs "
        f"the resident column layout)",
        # WHITELIST, like the pre-table gate: a future learner type is
        # streaming-unsupported until someone adds it to
        # STREAM_TREE_LEARNERS deliberately
        lambda c: c.tree_learner not in STREAM_TREE_LEARNERS,
        {"streaming": FATAL},
        example={"tree_learner": "voting"}),
    "dart_boosting": Capability(
        "boosting=dart",
        lambda c: c.boosting == "dart",
        {"streaming": FATAL},
        example={"boosting": "dart"}),
    "rf_boosting": Capability(
        "boosting=rf",
        lambda c: c.boosting == "rf",
        {"streaming": FATAL},
        example={"boosting": "rf", "bagging_freq": 1,
                 "bagging_fraction": 0.8}),
    "goss": Capability(
        "GOSS sampling",
        lambda c: str(c.data_sample_strategy) == "goss",
        {"rf": FATAL},
        example={"data_sample_strategy": "goss"},
        messages={"rf": "Cannot use GOSS with random forest"}),
    "no_bagging": Capability(
        "training without bagging",
        _no_bagging,
        {"rf": FATAL},
        # explicit spellings so the example composes over ANY base
        # config (the sweep merges it on top of rf's bagging defaults)
        example={"bagging_freq": 0, "bagging_fraction": 1.0,
                 "pos_bagging_fraction": 1.0,
                 "neg_bagging_fraction": 1.0},
        messages={"rf": "Random forest needs bagging: set "
                        "bagging_freq > 0 and bagging_fraction < 1.0"}),
    "linear_tree": Capability(
        "linear_tree",
        lambda c: bool(c.linear_tree),
        {"streaming": FATAL},
        example={"linear_tree": True}),
    "monotone_constraints": Capability(
        "monotone constraints",
        lambda c: bool(c.monotone_constraints),
        {"streaming": FATAL},
        example={"monotone_constraints": [1, 0, 0, 0]}),
    "interaction_constraints": Capability(
        "interaction constraints",
        lambda c: bool(c.interaction_constraints),
        {"streaming": FATAL},
        example={"interaction_constraints": [[0, 1], [2, 3]]}),
    "cegb": Capability(
        "CEGB",
        _has_cegb,
        {"streaming": FATAL},
        example={"cegb_tradeoff": 2.0}),
    "forced_splits": Capability(
        "forced splits",
        lambda c: bool(c.forcedsplits_filename),
        {"streaming": FATAL},
        example={"forcedsplits_filename": "forced.json"}),
    "categorical_features": Capability(
        "categorical features",
        lambda c: bool(c.categorical_feature),
        {"streaming": FATAL},
        example={"categorical_feature": "0"}),
    "wide_leaves": Capability(
        f"num_leaves > {STREAM_MAX_LEAVES} (int16 per-row leaf-id "
        f"state caps streamed trees)",
        lambda c: int(c.num_leaves) > STREAM_MAX_LEAVES,
        {"streaming": FATAL},
        example={"num_leaves": 40_000}),
    "auto_quantize": Capability(
        "auto-enabled quantized gradients (tpu_auto_quantize)",
        lambda c: bool(getattr(c, "_quantize_auto", False)),
        # an un-asked-for discretization would change streamed
        # numerics — quietly demote to exact f32. An EXPLICIT
        # use_quantized_grad stays honored (integer level histograms
        # are what make sharded streaming bit-exact).
        {"streaming": DEMOTE}),
}


# ---------------------------------------------------------------------------
# accessors
# ---------------------------------------------------------------------------
def requested_features(config,
                       extra: Optional[Dict[str, bool]] = None
                       ) -> List[str]:
    """Names of the table features ``config`` (plus runtime ``extra``
    flags) exhibits."""
    extra = extra or {}
    out = []
    for name, cap in CAPABILITIES.items():
        if extra.get(name) or cap.requested(config):
            out.append(name)
    return out


def verdict(feature: str, engine: str) -> str:
    return CAPABILITIES[feature].verdicts.get(engine, SUPPORTED)


def engine_verdicts(engine: str, config,
                    extra: Optional[Dict[str, bool]] = None
                    ) -> List[Tuple[str, Capability, str]]:
    """(feature, capability, verdict) for every non-SUPPORTED verdict
    the engine assigns to a feature this config requests — the loop a
    constructor's gate walks."""
    out = []
    for name in requested_features(config, extra):
        cap = CAPABILITIES[name]
        v = cap.verdicts.get(engine, SUPPORTED)
        if v != SUPPORTED:
            out.append((name, cap, v))
    return out


def fatal_features(engine: str, config,
                   extra: Optional[Dict[str, bool]] = None
                   ) -> List[str]:
    return [n for n, _c, v in engine_verdicts(engine, config, extra)
            if v == FATAL]


def demoted_features(engine: str, config,
                     extra: Optional[Dict[str, bool]] = None
                     ) -> List[str]:
    return [n for n, _c, v in engine_verdicts(engine, config, extra)
            if v == DEMOTE]


def supports(engine: str, config,
             extra: Optional[Dict[str, bool]] = None) -> bool:
    """True iff the engine's constructor would accept this config
    (demotions allowed; dataset-level gates — e.g. pandas-categorical
    bins under streaming — are re-checked by the constructor itself)."""
    return not fatal_features(engine, config, extra)


# ---------------------------------------------------------------------------
# auto-mode policies
# ---------------------------------------------------------------------------
def pallas_histogram_runs(num_bins: int, double_precision_hist) -> bool:
    """Does the Pallas histogram kernel run for a table of this bin
    width? It needs a TPU backend, bin ids that round-trip through int8
    (at most 256 bins a column) and bf16 operands, so
    ``tpu_double_precision_hist`` takes the XLA einsum path, as does
    everything else (quantized mode is the exact-at-speed alternative).
    The engine asks with its histogram width, ingest with the bin count
    its dtype can hold, so the feature-major tile is emitted exactly
    where the kernel will read it."""
    import jax
    return (num_bins <= 256 and not double_precision_hist
            and jax.default_backend() == "tpu")


def hist_partition_auto(config, use_pallas: bool,
                        n_pad: int) -> Tuple[bool, Optional[str]]:
    """The ``tpu_hist_partition=auto`` cost model: engage the
    leaf-ordered row partition only where the per-round repartition
    move pays for itself — the Pallas pool path over a large
    un-compacted source (docs/perf.md "Partitioned histograms").
    Returns ``(engage, stand_down_reason)``; the reason is None when
    engaging or when the path was never plausible (no Pallas kernel,
    where no stand-down message is owed)."""
    if not use_pallas:
        return False, None
    if str(config.data_sample_strategy) == "goss":
        return False, "GOSS already compacts the scan"
    if n_pad < HIST_PARTITION_MIN_ROWS:
        return False, ("dataset too small to amortize the "
                       "repartition move")
    return True, None


# which engines can ADOPT device-resident ingest output (ops/ingest.py):
# the streaming engine's host-block scan never adopts device bins —
# they would sit orphaned in HBM, so device ingest demotes to host
# binning when the params force the out-of-core engine
DEVICE_INGEST: Dict[str, str] = {
    "gbdt": SUPPORTED,
    "dart": SUPPORTED,
    "rf": SUPPORTED,
    "streaming": DEMOTE,
}


def forced_engine(params: Dict[str, Any]) -> str:
    """The engine a raw params dict FORCES, before any dataset-size
    auto-routing: ``tpu_streaming=true`` pins streaming, ``boosting``
    pins dart/rf, everything else resolves at create_boosting time
    (returned as "gbdt", the resident default)."""
    from .config import coerce_tristate, get_param
    if coerce_tristate(get_param(params, "tpu_streaming"),
                       "tpu_streaming") == "true":
        return "streaming"
    b = str(get_param(params, "boosting")).lower()
    if b == "dart":
        return "dart"
    if b in ("rf", "random_forest"):
        return "rf"
    return "gbdt"


def device_ingest_verdict(params: Dict[str, Any]) -> str:
    """Can the engine these params force adopt device-resident ingest
    output?  DEMOTE means: bin host-side (warn if the user forced
    ``tpu_ingest_device=true``)."""
    return DEVICE_INGEST.get(forced_engine(params), SUPPORTED)


# which engines' PREDICT surface can shard the stacked tree axis over
# the local mesh (tpu_serve_shard_trees; serve/shard.py +
# ops/predict.py forest_predict_sharded): DART rescales per-tree leaf
# values in place every iteration (shrink), so its stacks churn
# versions and drop subsets are non-contiguous — demote to the
# unsharded path; the streaming engine predicts through the host model
# and has no stacked device surface at all. Demotion means: serve
# unsharded (single-device stacks), never refuse the predict.
SHARDED_PREDICT: Dict[str, str] = {
    "gbdt": SUPPORTED,
    "rf": SUPPORTED,
    "dart": DEMOTE,
    "streaming": DEMOTE,
}


def sharded_predict_verdict(engine: str, config=None) -> str:
    """Verdict for sharding one engine's stacked predict over the tree
    axis. ``linear_tree`` configs demote on EVERY engine — linear-leaf
    predicts ride the host-model path (raw feature values), which the
    device traversal never sees."""
    if config is not None and bool(getattr(config, "linear_tree",
                                           False)):
        return DEMOTE
    return SHARDED_PREDICT.get(engine, DEMOTE)


# which engines' pred_contrib (TreeSHAP) can take the ENGINE path —
# device-resident cached path tables, bucketed zero-compile dispatch,
# and (mesh permitting) the tree-sharded scan (gbdt.predict_contrib /
# ops/shap.py sharded_scan_kernel). DART's in-place leaf rescales churn
# the cached tables' version every iteration; RF's per-tree averaging
# is host-verified only against forest_shap_batch; the streaming
# engine has no stacked device surface. Demotion means: explain through
# the cached host model (identical values), never refuse the call.
SHARDED_SHAP: Dict[str, str] = {
    "gbdt": SUPPORTED,
    "dart": DEMOTE,
    "rf": DEMOTE,
    "streaming": DEMOTE,
}

# exact warned-stand-down wording (basic.py logs the matching line
# once per booster when a pred_contrib call demotes to the host path)
SHARDED_SHAP_MESSAGES: Dict[str, str] = {
    "dart": ("device SHAP demoted for the DART engine (capabilities."
             "SHARDED_SHAP): in-place leaf rescales churn the cached "
             "path tables every iteration; explaining through the "
             "host model"),
    "rf": ("device SHAP demoted for the random-forest engine "
           "(capabilities.SHARDED_SHAP); explaining through the host "
           "model"),
    "streaming": ("device SHAP demoted for the streaming engine "
                  "(capabilities.SHARDED_SHAP): it predicts through "
                  "the host model and has no stacked device surface"),
    "linear_tree": ("device SHAP demoted for linear_tree models "
                    "(capabilities.SHARDED_SHAP): linear-leaf "
                    "contributions ride the host-model path"),
}


def sharded_shap_verdict(engine: str, config=None) -> str:
    """Verdict for routing one engine's ``pred_contrib`` through the
    device-native SHAP path. ``linear_tree`` configs demote on EVERY
    engine, mirroring :func:`sharded_predict_verdict` (the host SHAP
    path refuses linear trees loudly; the engine path never sees
    them)."""
    if config is not None and bool(getattr(config, "linear_tree",
                                           False)):
        return DEMOTE
    return SHARDED_SHAP.get(engine, DEMOTE)


# can streamed per-(rank, block) score slots be RE-CUT onto a changed
# shard/block topology on resume (boosting/streaming.py
# import_train_state)?  The slots themselves are a deterministic
# function of trees × global rows — reshardable (or recomputable from
# the pickled trees) exactly, for any numerics. What the verdict
# guards is the CONTINUED training: bit-equality vs an uninterrupted
# run at the original cut holds only where per-level histogram
# accumulation is cut-invariant — integer quantized level sums.
# Exact-f32 accumulation reassociates when the block/shard cut moves
# (documented-close, not bit-equal), so that cell is FATAL unless the
# user opts into the divergence via ``tpu_elastic_recut=true``
# (docs/robustness.md "Elastic topology").
STREAM_RECUT: Dict[str, str] = {
    "quantized": SUPPORTED,
    "exact_f32": FATAL,       # tpu_elastic_recut=true demotes to a
    #                           recompute-with-divergence-warning
}


def stream_recut_verdict(config) -> Tuple[str, str]:
    """(verdict, why) for re-cutting streamed score state onto a
    layout different from the one the checkpoint was written under.
    SUPPORTED = re-cut, bit-exact continuation; DEMOTE = re-cut with a
    documented-divergence warning (the ``tpu_elastic_recut=true``
    override); FATAL = refuse, ``why`` names the blocking feature, the
    table cell, and the knob."""
    knob = str(getattr(config, "tpu_elastic_recut", "auto"))
    if knob == "false":
        return FATAL, (
            "tpu_elastic_recut=false pins the strict PR-13 contract: "
            "any shard/block layout change on streamed resume is a "
            "hard error — resume under the original layout, or drop "
            "the pin")
    cell = "quantized" if bool(config.use_quantized_grad) \
        else "exact_f32"
    if STREAM_RECUT[cell] == SUPPORTED:
        return SUPPORTED, (
            "integer quantized level histograms are shard/block-cut-"
            "invariant, so the re-cut continuation is bit-exact")
    if knob == "true":
        return DEMOTE, (
            "tpu_elastic_recut=true forces the re-cut: exact-f32 "
            "histogram sums reassociate under the new cut, so the "
            "continued trees are documented-close to — not bit-equal "
            "with — an uninterrupted run at the original layout")
    return FATAL, (
        "exact-f32 streamed score accumulation (use_quantized_grad "
        "off) is the blocking feature: per-level histogram sums "
        "reassociate under a changed shard/block cut, so the resumed "
        "run would be documented-close rather than bit-equal "
        "(capability cell capabilities.STREAM_RECUT['exact_f32']). "
        "Either train with use_quantized_grad=true (cut-invariant "
        "integer sums — bit-exact elastic resume), force the re-cut "
        "with tpu_elastic_recut=true (recompute with a divergence "
        "warning), or resume under the original layout")


class _RecutParamsView:
    """Minimal Config-shaped view over a raw params dict for
    :func:`stream_recut_verdict` — the launcher's degrade path must
    predict the verdict BEFORE deciding to resume a narrower gang
    (a full Config build has process-wide side effects there)."""

    def __init__(self, params: Dict[str, Any]):
        from .config import coerce_tristate, get_param
        self.tpu_elastic_recut = coerce_tristate(
            get_param(params, "tpu_elastic_recut"),
            "tpu_elastic_recut")
        self.use_quantized_grad = bool(
            get_param(params, "use_quantized_grad"))


def stream_recut_verdict_params(params: Dict[str, Any]
                                ) -> Tuple[str, str]:
    """:func:`stream_recut_verdict` over a raw params dict (alias- and
    type-resolved through ``config.get_param``)."""
    return stream_recut_verdict(_RecutParamsView(params))
