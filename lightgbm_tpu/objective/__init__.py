"""Objective functions: per-row gradient/hessian producers.

Reference: src/objective/*.hpp + ``ObjectiveFunction::CreateObjectiveFunction``
(src/objective/objective_function.cpp, UNVERIFIED — empty mount, see
SURVEY.md banner). Each objective supplies ``GetGradients(score) ->
(grad, hess)``, an optional boost-from-average init score, and the
score→output transform used at predict time.

TPU-first: objectives are pure ``jnp`` element-wise functions, so they fuse
into the training step under jit (the reference dispatches to OpenMP loops
or CUDA kernels, src/objective/cuda/*). Ranking objectives (lambdarank,
rank_xendcg) live in ``ranking.py`` as segment formulations.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import log

Array = jax.Array


class Objective:
    """Base objective. Subclasses implement pure-jnp ``get_gradients``."""

    name = "base"
    is_ranking = False
    # number of boosted models per iteration (K for multiclass)
    def num_models(self, num_class: int) -> int:
        return 1

    def __init__(self, config):
        self.config = config

    def init_score(self, label: np.ndarray,
                   weight: Optional[np.ndarray]) -> float:
        """BoostFromAverage initial score (host-side, once)."""
        return 0.0

    def get_gradients(self, score: Array, label: Array,
                      weight: Optional[Array]) -> Tuple[Array, Array]:
        raise NotImplementedError

    def convert_output(self, score: Array) -> Array:
        """Raw score -> prediction-space transform (identity by default)."""
        return score

    def renew_tree_output(self, *_args, **_kw):
        """Hook for leaf re-fitting (L1/quantile/MAPE median renewal)."""
        return None

    def _apply_weight(self, grad, hess, weight):
        if weight is None:
            return grad, hess
        return grad * weight, hess * weight

    # -- multi-host BoostFromAverage sync (the reference's
    # Network::GlobalSyncUpByMean; SURVEY.md §2.3) ----------------------
    def init_score_all_processes(self, label, weight) -> float:
        """``init_score`` over EVERY process's rows, for engines whose
        processes each hold only a row shard: the mean statistic is
        gathered (bit-exact float64, so a gang starts where one process
        over the same rows would) and every rank gets the same score.
        Percentile-based init scores cannot be synced this way; they
        warn and use the local shard."""
        stats = self.init_mean_stats(label, weight)
        if stats is None:
            from ..utils import log
            log.warning(
                "boost_from_average for this objective is a percentile "
                "statistic that cannot be synced across hosts; using "
                "this process's local shard only")
            return self.init_score(label, weight)
        from ..parallel.multihost import allgather_float64
        tot = allgather_float64(np.asarray(stats, np.float64))
        return self.init_from_mean(
            float(tot[:, 0].sum()) / max(float(tot[:, 1].sum()), 1e-30))

    def init_mean_stats(self, label, weight):
        """``(weighted_sum, weight_total)`` such that
        ``init_from_mean(weighted_sum / weight_total)`` reproduces
        ``init_score`` — the syncable decomposition for multi-host
        boost_from_average. None when the init score is not a mean
        statistic (the median/percentile family)."""
        return None

    def init_from_mean(self, mean: float) -> float:
        raise NotImplementedError

    @staticmethod
    def _mean_stats_of(v: np.ndarray, weight) -> Tuple[float, float]:
        if weight is None:
            return float(np.sum(v)), float(len(v))
        return float(np.sum(v * weight)), float(np.sum(weight))

    @staticmethod
    def _wavg(v: np.ndarray, weight: Optional[np.ndarray]) -> float:
        if weight is None:
            return float(np.mean(v))
        return float(np.sum(v * weight) / np.sum(weight))


# ---------------------------------------------------------------------------
# Regression family (src/objective/regression_objective.hpp, UNVERIFIED)
# ---------------------------------------------------------------------------
class RegressionL2(Objective):
    name = "regression"

    def __init__(self, config):
        super().__init__(config)
        # reg_sqrt (regression_objective.hpp sqrt mode): fit
        # sign(y)*sqrt(|y|) instead of y; predictions convert back as
        # sign(s)*s^2
        self.reg_sqrt = bool(getattr(config, "reg_sqrt", False))

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        if self.reg_sqrt:
            label = np.sign(label) * np.sqrt(np.abs(label))
        return self._wavg(label, weight)

    def get_gradients(self, score, label, weight):
        if self.reg_sqrt:
            label = jnp.sign(label) * jnp.sqrt(jnp.abs(label))
        grad = score - label
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess, weight)

    def convert_output(self, score):
        if self.reg_sqrt:
            return jnp.sign(score) * score * score
        return score

    def init_mean_stats(self, label, weight):
        if self.reg_sqrt:
            label = np.sign(label) * np.sqrt(np.abs(label))
        return self._mean_stats_of(label, weight)

    def init_from_mean(self, mean):
        return float(mean)


class RegressionL1(Objective):
    name = "regression_l1"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        # weighted median of the label
        return _weighted_percentile_np(label, weight, 0.5)

    def get_gradients(self, score, label, weight):
        grad = jnp.sign(score - label)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess, weight)

    def renew_tree_output(self, score, label, weight, leaf_id, num_leaves):
        return _leaf_percentile_renewal(score, label, weight, leaf_id,
                                        num_leaves, 0.5)


class Huber(Objective):
    name = "huber"

    def get_gradients(self, score, label, weight):
        alpha = self.config.alpha
        r = score - label
        grad = jnp.clip(r, -alpha, alpha)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess, weight)


class Fair(Objective):
    name = "fair"

    def get_gradients(self, score, label, weight):
        c = self.config.fair_c
        r = score - label
        denom = jnp.abs(r) + c
        grad = c * r / denom
        hess = c * c / (denom * denom)
        return self._apply_weight(grad, hess, weight)


class Poisson(Objective):
    name = "poisson"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return float(np.log(max(self._wavg(label, weight), 1e-9)))

    def get_gradients(self, score, label, weight):
        grad = jnp.exp(score) - label
        hess = jnp.exp(score + self.config.poisson_max_delta_step)
        return self._apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jnp.exp(score)

    def init_mean_stats(self, label, weight):
        return self._mean_stats_of(np.asarray(label, np.float64), weight)

    def init_from_mean(self, mean):
        return float(np.log(max(mean, 1e-9)))


class Quantile(Objective):
    name = "quantile"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return _weighted_percentile_np(label, weight, self.config.alpha)

    def get_gradients(self, score, label, weight):
        alpha = self.config.alpha
        grad = jnp.where(label - score > 0, -alpha, 1.0 - alpha)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess, weight)

    def renew_tree_output(self, score, label, weight, leaf_id, num_leaves):
        return _leaf_percentile_renewal(score, label, weight, leaf_id,
                                        num_leaves, self.config.alpha)


class MAPE(Objective):
    name = "mape"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return _weighted_percentile_np(label, weight, 0.5)

    def get_gradients(self, score, label, weight):
        scale = 1.0 / jnp.maximum(jnp.abs(label), 1.0)
        grad = jnp.sign(score - label) * scale
        hess = scale
        return self._apply_weight(grad, hess, weight)

    def renew_tree_output(self, score, label, weight, leaf_id, num_leaves):
        # weighted median with the 1/|label| scaling folded into weights
        scale = 1.0 / np.maximum(np.abs(np.asarray(label)), 1.0)
        w = scale if weight is None else scale * np.asarray(weight)
        return _leaf_percentile_renewal(score, label, w, leaf_id,
                                        num_leaves, 0.5)


class Gamma(Objective):
    name = "gamma"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return float(np.log(max(self._wavg(label, weight), 1e-9)))

    def get_gradients(self, score, label, weight):
        e = jnp.exp(-score)
        grad = 1.0 - label * e
        hess = label * e
        return self._apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jnp.exp(score)

    def init_mean_stats(self, label, weight):
        return self._mean_stats_of(np.asarray(label, np.float64), weight)

    def init_from_mean(self, mean):
        return float(np.log(max(mean, 1e-9)))


class Tweedie(Objective):
    name = "tweedie"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        return float(np.log(max(self._wavg(label, weight), 1e-9)))

    def get_gradients(self, score, label, weight):
        rho = self.config.tweedie_variance_power
        e1 = jnp.exp((1.0 - rho) * score)
        e2 = jnp.exp((2.0 - rho) * score)
        grad = -label * e1 + e2
        hess = -label * (1.0 - rho) * e1 + (2.0 - rho) * e2
        return self._apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jnp.exp(score)

    def init_mean_stats(self, label, weight):
        return self._mean_stats_of(np.asarray(label, np.float64), weight)

    def init_from_mean(self, mean):
        return float(np.log(max(mean, 1e-9)))


# ---------------------------------------------------------------------------
# Binary classification (src/objective/binary_objective.hpp, UNVERIFIED)
# ---------------------------------------------------------------------------
class Binary(Objective):
    name = "binary"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        self._pos_weight = 1.0
        self._neg_weight = 1.0

    def prepare(self, label: np.ndarray, weight) -> None:
        """Compute class weights (is_unbalance / scale_pos_weight)."""
        cnt_pos = float(np.sum(label > 0))
        cnt_neg = float(len(label) - cnt_pos)
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                self._pos_weight = 1.0
                self._neg_weight = cnt_pos / cnt_neg
            else:
                self._pos_weight = cnt_neg / cnt_pos
                self._neg_weight = 1.0
        else:
            self._pos_weight = self.config.scale_pos_weight
            self._neg_weight = 1.0

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        pavg = min(max(self._wavg((label > 0).astype(np.float64), weight),
                       1e-15), 1.0 - 1e-15)
        init = float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)
        log.info(f"[binary:BoostFromScore]: pavg={pavg:.6f} -> "
                 f"initscore={init:.6f}")
        return init

    def get_gradients(self, score, label, weight):
        sig = self.sigmoid
        y = (label > 0).astype(score.dtype)
        p = jax.nn.sigmoid(sig * score)
        label_w = jnp.where(y > 0, self._pos_weight, self._neg_weight)
        grad = sig * (p - y) * label_w
        hess = sig * sig * p * (1.0 - p) * label_w
        return self._apply_weight(grad, hess, weight)

    def convert_output(self, score):
        return jax.nn.sigmoid(self.sigmoid * score)

    def init_mean_stats(self, label, weight):
        return self._mean_stats_of((label > 0).astype(np.float64),
                                   weight)

    def init_from_mean(self, mean):
        pavg = min(max(float(mean), 1e-15), 1.0 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)


# ---------------------------------------------------------------------------
# Multiclass (src/objective/multiclass_objective.hpp, UNVERIFIED)
# ---------------------------------------------------------------------------
class MulticlassSoftmax(Objective):
    name = "multiclass"

    def num_models(self, num_class):
        return num_class

    def get_gradients(self, score, label, weight):
        # score: [n, K]
        K = score.shape[1]
        y = jax.nn.one_hot(label.astype(jnp.int32), K, dtype=score.dtype)
        p = jax.nn.softmax(score, axis=1)
        grad = p - y
        # the factor-2 hessian follows the reference's multiclass softmax
        hess = 2.0 * p * (1.0 - p)
        if weight is not None:
            grad = grad * weight[:, None]
            hess = hess * weight[:, None]
        return grad, hess

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=-1)


class MulticlassOVA(Objective):
    name = "multiclassova"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = config.sigmoid

    def num_models(self, num_class):
        return num_class

    def get_gradients(self, score, label, weight):
        K = score.shape[1]
        y = jax.nn.one_hot(label.astype(jnp.int32), K, dtype=score.dtype)
        sig = self.sigmoid
        p = jax.nn.sigmoid(sig * score)
        grad = sig * (p - y)
        hess = sig * sig * p * (1.0 - p)
        if weight is not None:
            grad = grad * weight[:, None]
            hess = hess * weight[:, None]
        return grad, hess

    def convert_output(self, score):
        p = jax.nn.sigmoid(self.sigmoid * score)
        return p / jnp.sum(p, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# Cross-entropy family (src/objective/xentropy_objective.hpp, UNVERIFIED)
# ---------------------------------------------------------------------------
class CrossEntropy(Objective):
    name = "cross_entropy"

    def init_score(self, label, weight):
        if not self.config.boost_from_average:
            return 0.0
        pavg = min(max(self._wavg(label, weight), 1e-15), 1.0 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)))

    def get_gradients(self, score, label, weight):
        p = jax.nn.sigmoid(score)
        if weight is None:
            return p - label, p * (1.0 - p)
        # weighted cross-entropy: gradient scales with weight
        return (p - label) * weight, p * (1.0 - p) * weight

    def convert_output(self, score):
        return jax.nn.sigmoid(score)


class CrossEntropyLambda(Objective):
    name = "cross_entropy_lambda"

    def get_gradients(self, score, label, weight):
        # intensity parameterization: score = log(exp(eps)-1) domain;
        # follows the reference's xentlambda with weights folded in
        w = jnp.ones_like(score) if weight is None else weight
        eps = jnp.log1p(jnp.exp(score))     # softplus
        sig = jax.nn.sigmoid(score)
        hhat = 1.0 - jnp.exp(-w * eps)
        grad = sig * (w * (1.0 - label / jnp.maximum(hhat, 1e-15)
                           * jnp.exp(-w * eps)))
        hess_base = sig * (1.0 - sig)
        hess = jnp.maximum(hess_base * w, 1e-15)
        return grad, hess

    def convert_output(self, score):
        return jnp.log1p(jnp.exp(score))


class CustomObjective(Objective):
    """Placeholder for user-supplied fobj (engine handles the callable)."""

    name = "custom"

    def get_gradients(self, score, label, weight):
        log.fatal("custom objective must be provided as a callable fobj")


# ---------------------------------------------------------------------------
# helpers + factory
# ---------------------------------------------------------------------------
def _weighted_percentile_np(v: np.ndarray, weight: Optional[np.ndarray],
                            alpha: float) -> float:
    v = np.asarray(v, dtype=np.float64)
    if weight is None:
        return float(np.percentile(v, alpha * 100.0,
                                   method="inverted_cdf"))
    order = np.argsort(v)
    cw = np.cumsum(np.asarray(weight, dtype=np.float64)[order])
    cut = alpha * cw[-1]
    idx = int(np.searchsorted(cw, cut))
    return float(v[order[min(idx, len(v) - 1)]])


def _leaf_percentile_renewal(score, label, weight, leaf_id, num_leaves,
                             alpha):
    """Per-leaf weighted percentile of residuals (RenewTreeOutput).

    Host-side numpy (runs once per tree for L1-family objectives).
    """
    score = np.asarray(score)
    label = np.asarray(label)
    leaf_id = np.asarray(leaf_id)
    out = np.zeros(num_leaves, dtype=np.float64)
    resid = label - score
    for lf in range(num_leaves):
        m = leaf_id == lf
        if not m.any():
            continue
        w = None if weight is None else np.asarray(weight)[m]
        out[lf] = _weighted_percentile_np(resid[m], w, alpha)
    return out


_REGISTRY: Dict[str, Callable[..., Objective]] = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": Huber,
    "fair": Fair,
    "poisson": Poisson,
    "quantile": Quantile,
    "mape": MAPE,
    "gamma": Gamma,
    "tweedie": Tweedie,
    "binary": Binary,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "custom": CustomObjective,
}


def create_objective(config) -> Objective:
    """Factory by canonical objective name (after Config alias resolution)."""
    name = config.objective
    if name in _REGISTRY:
        return _REGISTRY[name](config)
    if name in ("lambdarank", "rank_xendcg"):
        from .ranking import LambdaRank, RankXENDCG
        return (LambdaRank if name == "lambdarank" else RankXENDCG)(config)
    log.fatal(f"Unknown objective {name}")
