"""Best-split search over bin histograms.

Reference: ``FeatureHistogram::FindBestThreshold*`` + ``SplitInfo``
(src/treelearner/feature_histogram.hpp, split_info.hpp, UNVERIFIED — empty
mount, see SURVEY.md banner). The reference scans each feature's bins
left-to-right and right-to-left (the two scans realize missing-value
default-left vs default-right); gain is the L1/L2-regularized variance
reduction; constraints: ``min_data_in_leaf``, ``min_sum_hessian_in_leaf``,
``min_gain_to_split``.

TPU-first design: the per-feature sequential scans become one vectorized
``cumsum`` over the bin axis for ALL features at once, with BOTH missing
directions evaluated as a stacked axis; the argmax over
``[features, bins, directions]`` replaces the reference's OpenMP
per-feature loop + reduction. Everything is fixed-shape and jit-safe, so it
runs inside the tree-growth ``while_loop`` and under ``shard_map`` for the
distributed learners.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from .. import obs

# a plain Python float (weak-typed -> f32 under jnp ops), NOT a device
# array: materializing an array at import time would initialize the XLA
# backend and break jax.distributed.initialize for multi-host users
NEG_INF = float("-inf")


def _cumsum_bins(hist_vals: jax.Array) -> jax.Array:
    """Inclusive cumsum over the bin axis of ``[F, B, C]`` as a
    triangular-matrix product. XLA lowers ``jnp.cumsum`` to a VPU
    reduce-window (~10 ms per 64-child round at B=256 on v5e); the same
    O(F*B^2*C) MACs ride the MXU in microseconds. Exactness holds for
    the COUNT channel only: counts are integers < 2^24, and 0/1-weighted
    f32 sums of such values are exact in any summation order at HIGHEST
    precision. The f32 grad/hess channels are accumulated in a different
    order than ``jnp.cumsum``, so their prefix sums can differ in ULPs
    between the TPU matmul path and the CPU/wide-B path — enough to flip
    near-tied split choices across backends.

    TPU-only: the matmul trades O(F*B*C) adds for O(F*B^2*C) MACs — a
    win only where the MXU makes MACs ~free. The CPU/XLA path (and the
    B > 512 wide-histogram route) keeps ``jnp.cumsum``."""
    f, b, c = hist_vals.shape
    if jax.default_backend() != "tpu" or b > 512:
        return jnp.cumsum(hist_vals, axis=1)
    tri = (jnp.arange(b, dtype=jnp.int32)[:, None]
           <= jnp.arange(b, dtype=jnp.int32)[None, :])
    cum = jax.lax.dot_general(
        hist_vals, tri.astype(hist_vals.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)       # [F, C, B]
    return cum.transpose(0, 2, 1)


@dataclasses.dataclass(frozen=True)
class SplitConfig:
    """Static split-search hyperparameters (subset of Config)."""

    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    # categorical split search (FindBestThresholdCategorical):
    # one-hot below max_cat_to_onehot distinct values, else sorted
    # many-vs-many by grad/(hess+cat_smooth) with cat_l2 regularization
    has_categorical: bool = False
    # static tuple of categorical feature indices: when non-empty, the
    # categorical scan slices these rows out of the histogram before its
    # per-feature argsorts (sorting all F rows costs ~4x the whole
    # numerical search at Criteo shape: 26 cats of 199 features). Left
    # empty for dynamically-sliced search spaces (scatter/feature-
    # parallel shards, voting-elected subsets).
    cat_positions: tuple = ()
    max_cat_threshold: int = 32
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    # monotone constraints, "basic" method (monotone_constraints.hpp
    # BasicLeafConstraints): child outputs are clipped to the leaf's
    # inherited [lower, upper] range, gains are evaluated at the clipped
    # outputs, and thresholds whose outputs violate the feature's
    # direction are vetoed
    has_monotone: bool = False
    # CEGB (cost_effective_gradient_boosting.hpp): split gains are
    # discounted by tradeoff * (penalty_split * n_rows_in_leaf +
    # per-feature coupled penalty for model-unused features)
    has_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    # monotone_penalty (monotone_constraints.hpp
    # ComputeMonotoneSplitGainPenalty): gains of splits on constrained
    # features are scaled by a depth-dependent factor < 1, discouraging
    # them near the root; needs the `depth` argument
    monotone_penalty: float = 0.0
    # path smoothing (feature_histogram.hpp CalculateSplittedLeafOutput
    # USE_SMOOTHING): child outputs shrink toward the parent leaf's
    # output by n/(n+path_smooth); gains evaluated at smoothed outputs
    path_smooth: float = 0.0
    # extremely randomized trees (feature_histogram.hpp USE_RAND_SEED):
    # the numerical scan evaluates ONE random threshold per feature per
    # node (categorical search is not randomized here — extension gap,
    # documented)
    extra_trees: bool = False
    # feature_contri: per-feature split-gain multiplier (read from the
    # `contri` array argument when True)
    has_contri: bool = False


def threshold_l1(s: jax.Array, l1: float) -> jax.Array:
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_gain(sum_g: jax.Array, sum_h: jax.Array, l1: float,
              l2: float) -> jax.Array:
    """Variance-reduction leaf gain: ThresholdL1(g)^2 / (h + l2)."""
    t = threshold_l1(sum_g, l1)
    denom = sum_h + l2
    return jnp.where(denom > 0.0, t * t / jnp.maximum(denom, 1e-30), 0.0)


def calc_leaf_output(sum_g: jax.Array, sum_h: jax.Array, l1: float,
                     l2: float, max_delta_step: float = 0.0) -> jax.Array:
    """Leaf output: -ThresholdL1(g) / (h + l2), optionally clipped."""
    denom = sum_h + l2
    out = jnp.where(denom > 0.0,
                    -threshold_l1(sum_g, l1) / jnp.maximum(denom, 1e-30),
                    0.0)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def leaf_gain_at_output(sum_g: jax.Array, sum_h: jax.Array, l1: float,
                        l2: float, output: jax.Array) -> jax.Array:
    """Leaf gain evaluated at a GIVEN (possibly clipped) output —
    ``GetLeafSplitGainGivenOutput`` (feature_histogram.hpp): equals
    ``leaf_gain`` when the output is the unconstrained optimum."""
    t = threshold_l1(sum_g, l1)
    return -(2.0 * t * output + (sum_h + l2) * output * output)


def smooth_output(raw: jax.Array, count: jax.Array, parent_out,
                  alpha: float) -> jax.Array:
    """Path smoothing (feature_histogram.hpp USE_SMOOTHING):
    ``raw * n/(n+alpha) + parent_out * alpha/(n+alpha)``."""
    w = count / (count + alpha)
    return raw * w + parent_out * (1.0 - w)


def monotone_penalty_factor(depth, penalization: float) -> jax.Array:
    """Gain multiplier for splits on monotone-constrained features
    (monotone_constraints.hpp ComputeMonotoneSplitGainPenalty):
    ~0 while depth + 1 <= penalization, then decays toward 1."""
    eps = 1e-10
    d = depth.astype(jnp.float32) if hasattr(depth, "astype") else float(depth)
    f_small = 1.0 - penalization / (2.0 ** d) + eps        # pen <= 1
    f_large = 1.0 - 2.0 ** (penalization - 1.0 - d) + eps  # pen > 1
    f = jnp.where(jnp.asarray(penalization) <= 1.0, f_small, f_large)
    return jnp.where(penalization >= d + 1.0, eps, f)


def _pack_bitset(inset: jax.Array, n_words: int) -> jax.Array:
    """Pack a ``[B]`` bool left-set into ``[n_words]`` uint32 words."""
    b = inset.shape[0]
    pad = n_words * 32 - b
    if pad > 0:
        inset = jnp.concatenate([inset, jnp.zeros(pad, inset.dtype)])
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :]
    return jnp.sum(inset.reshape(n_words, 32).astype(jnp.uint32) * weights,
                   axis=1, dtype=jnp.uint32)


@obs.scope("grower/cat_search")
def _categorical_candidates(hist, parent_sums, num_bin, allowed_feature,
                            is_cat, cfg: SplitConfig,
                            out_lower=None, out_upper=None,
                            cegb_pen=None, parent_out=None, contri=None):
    """Candidate categorical gains: ``(all_gain [F, 3, B], orders
    [F, 2, B], cum [F, 2, B, 3], valid_bin [F, B])`` — modes are
    (one-hot, sorted-asc, sorted-desc). With monotone bounds active,
    gains are evaluated at range-clipped outputs like the numerical
    scan, so the cat-vs-numerical comparison stays fair in bounded
    leaves (categorical features themselves carry no direction)."""
    f, b, _ = hist.shape
    bin_idx = jnp.arange(b, dtype=jnp.int32)[None, :]
    cnt = hist[..., 2]
    l1, l2c = cfg.lambda_l1, cfg.lambda_l2 + cfg.cat_l2
    pg, ph, pc = parent_sums[0], parent_sums[1], parent_sums[2]
    bounded = cfg.has_monotone and out_lower is not None
    smoothed = cfg.path_smooth > 0.0 and parent_out is not None
    if bounded or smoothed:
        p_out = calc_leaf_output(pg, ph, l1, l2c, cfg.max_delta_step)
        if smoothed:
            p_out = smooth_output(p_out, pc, parent_out, cfg.path_smooth)
        if bounded:
            p_out = jnp.clip(p_out, out_lower, out_upper)
        parent_gain = leaf_gain_at_output(pg, ph, l1, l2c, p_out)
    else:
        parent_gain = leaf_gain(pg, ph, l1, l2c)
    min_cnt = float(max(cfg.min_data_in_leaf, cfg.min_data_per_group))

    cat_ok = is_cat & allowed_feature
    valid_bin = ((bin_idx >= 1) & (bin_idx < num_bin[:, None])
                 & (cnt > 0) & cat_ok[:, None])               # [F, B]

    def child_gain(lg, lh, lc):
        rg, rh, rc = pg - lg, ph - lh, pc - lc
        if bounded or smoothed:
            lo = calc_leaf_output(lg, lh, l1, l2c, cfg.max_delta_step)
            ro = calc_leaf_output(rg, rh, l1, l2c, cfg.max_delta_step)
            if smoothed:
                lo = smooth_output(lo, lc, parent_out, cfg.path_smooth)
                ro = smooth_output(ro, rc, parent_out, cfg.path_smooth)
            if bounded:
                lo = jnp.clip(lo, out_lower, out_upper)
                ro = jnp.clip(ro, out_lower, out_upper)
            g = (leaf_gain_at_output(lg, lh, l1, l2c, lo)
                 + leaf_gain_at_output(rg, rh, l1, l2c, ro)
                 - parent_gain)
        else:
            g = (leaf_gain(lg, lh, l1, l2c) + leaf_gain(rg, rh, l1, l2c)
                 - parent_gain)
        ok = ((lc >= min_cnt) & (rc >= min_cnt)
              & (lh >= cfg.min_sum_hessian_in_leaf)
              & (rh >= cfg.min_sum_hessian_in_leaf)
              & (g > cfg.min_gain_to_split))
        return jnp.where(ok, g, NEG_INF)

    # ---- one-hot (one category vs rest) ------------------------------
    use_onehot = (num_bin - 1) <= cfg.max_cat_to_onehot       # [F]
    gain_oh = child_gain(hist[..., 0], hist[..., 1], cnt)
    gain_oh = jnp.where(valid_bin & use_onehot[:, None], gain_oh, NEG_INF)

    # ---- sorted many-vs-many -----------------------------------------
    ratio = jnp.where(valid_bin,
                      hist[..., 0] / (hist[..., 1] + cfg.cat_smooth),
                      jnp.inf)
    # two scan directions; invalid bins sort to the end in both
    order_asc = jnp.argsort(ratio, axis=1)
    order_desc = jnp.argsort(jnp.where(valid_bin, -ratio, jnp.inf), axis=1)
    orders = jnp.stack([order_asc, order_desc], axis=1)       # [F, 2, B]
    sorted_hist = jnp.take_along_axis(hist[:, None], orders[..., None],
                                      axis=2)                 # [F, 2, B, 3]
    sorted_valid = jnp.take_along_axis(valid_bin[:, None], orders, axis=2)
    cum = jnp.cumsum(sorted_hist, axis=2)
    prefix_ok = (jnp.cumprod(sorted_valid.astype(jnp.int32), axis=2) > 0)
    k_idx = bin_idx[None]                                     # prefix len-1
    gain_sorted = child_gain(cum[..., 0], cum[..., 1], cum[..., 2])
    gain_sorted = jnp.where(
        prefix_ok & (k_idx < cfg.max_cat_threshold)
        & ~use_onehot[:, None, None] & cat_ok[:, None, None],
        gain_sorted, NEG_INF)                                 # [F, 2, B]

    all_gain = jnp.concatenate(
        [gain_oh[:, None, :], gain_sorted], axis=1)           # [F, 3, B]
    if cfg.has_contri and contri is not None:
        all_gain = jnp.where(jnp.isfinite(all_gain),
                             all_gain * contri[:, None, None], all_gain)
    if cfg.has_cegb:
        # penalize BEFORE the argmax so the per-feature selection sees
        # the discounted gains, mirroring the numerical path
        pen = cfg.cegb_tradeoff * cfg.cegb_penalty_split * pc
        if cegb_pen is not None:
            pen = pen + cegb_pen
            all_gain = all_gain - pen[:, None, None]
        else:
            all_gain = all_gain - pen
        all_gain = jnp.where(all_gain > cfg.min_gain_to_split, all_gain,
                             NEG_INF)
    return all_gain, orders, cum, valid_bin


@obs.scope("grower/cat_search")
def _categorical_best(hist, parent_sums, num_bin, allowed_feature, is_cat,
                      cfg: SplitConfig, out_lower=None, out_upper=None,
                      cegb_pen=None, parent_out=None, contri=None):
    """Best categorical split (one-hot + sorted many-vs-many).

    Reference: ``FindBestThresholdCategoricalInner``
    (src/treelearner/feature_histogram.hpp, UNVERIFIED): features with
    few categories scan one-vs-rest; otherwise categories are sorted by
    ``sum_grad / (sum_hess + cat_smooth)`` and prefixes of the sorted
    order (both directions, capped at ``max_cat_threshold``) form the
    left set, with ``cat_l2`` added to the L2 term.
    ``min_data_per_group`` is applied to both children of a categorical
    split. Bin 0 (the NaN/unseen-category bin) is never elected into a
    left set — unseen categories route right at predict, matching the
    bitset-miss semantics of the reference.

    Returns (gain [scalar], feature, left_sums, inset [B] bool over bins).
    """
    f, b, _ = hist.shape
    bin_idx = jnp.arange(b, dtype=jnp.int32)[None, :]
    all_gain, orders, cum, valid_bin = _categorical_candidates(
        hist, parent_sums, num_bin, allowed_feature, is_cat, cfg,
        out_lower=out_lower, out_upper=out_upper, cegb_pen=cegb_pen,
        parent_out=parent_out, contri=contri)
    flat = all_gain.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    feature = (best // (3 * b)).astype(jnp.int32)
    mode = ((best // b) % 3).astype(jnp.int32)                # 0=oh,1=asc,2=desc
    j = (best % b).astype(jnp.int32)

    onehot_inset = bin_idx[0] == j                            # [B]
    order_w = orders[feature, jnp.maximum(mode - 1, 0)]       # [B]
    inv = jnp.zeros(b, jnp.int32).at[order_w].set(
        jnp.arange(b, dtype=jnp.int32))
    sorted_inset = (inv <= j) & valid_bin[feature]
    inset = jnp.where(mode == 0, onehot_inset, sorted_inset)

    left_oh = hist[feature, j]
    left_sorted = cum[feature, jnp.maximum(mode - 1, 0), j]
    left_sums = jnp.where(mode == 0, left_oh, left_sorted)
    return best_gain, feature, left_sums, inset


def _numerical_candidates(hist, parent_sums, num_bin, has_nan,
                          num_allowed, cfg: SplitConfig,
                          mono=None, out_lower=None, out_upper=None,
                          parent_out=None, extra_u=None, contri=None,
                          depth=None):
    """Numerical threshold-scan gains: ``(gain [F, B, 2],
    left [F, B, 2, 3])`` — dir 0: missing right, dir 1: missing left.

    With ``cfg.has_monotone``: ``mono [F]`` in {-1, 0, +1} and the
    leaf's inherited output range ``[out_lower, out_upper]`` (scalars);
    candidate outputs are clipped to the range, gains evaluated at the
    clipped outputs, and direction-violating thresholds vetoed.
    With ``cfg.path_smooth > 0``: candidate outputs shrink toward
    ``parent_out`` (the leaf's stored output) before any clipping.
    With ``cfg.extra_trees``: ``extra_u [F]`` uniforms pick ONE random
    threshold per feature; all others are vetoed.
    With ``cfg.has_contri``: valid gains scale by ``contri [F]``
    (validity is checked on the unscaled gain, like the reference's
    penalty-after-threshold-check order)."""
    f, b, _ = hist.shape
    bin_idx = jnp.arange(b, dtype=jnp.int32)[None, :]          # [1, B]
    nan_bin = (num_bin - 1)[:, None]                           # [F, 1]
    is_nan_bin = has_nan[:, None] & (bin_idx == nan_bin)       # [F, B]

    hist_vals = jnp.where(is_nan_bin[..., None], 0.0, hist)
    nan_sums = jnp.sum(jnp.where(is_nan_bin[..., None], hist, 0.0),
                       axis=1)                                 # [F, 3]
    cum = _cumsum_bins(hist_vals)                              # [F, B, 3]
    parent = parent_sums[None, None, :]

    # direction 0: missing goes right; direction 1: missing goes left
    left = jnp.stack([cum, cum + nan_sums[:, None, :]], axis=2)  # [F,B,2,3]
    right = parent[:, :, None, :] - left

    lg, lh, lc = left[..., 0], left[..., 1], left[..., 2]
    rg, rh, rc = right[..., 0], right[..., 1], right[..., 2]

    use_mono = cfg.has_monotone and mono is not None
    use_smooth = cfg.path_smooth > 0.0 and parent_out is not None
    violates = None
    if use_mono or use_smooth:
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        l_out = calc_leaf_output(lg, lh, l1, l2, cfg.max_delta_step)
        r_out = calc_leaf_output(rg, rh, l1, l2, cfg.max_delta_step)
        p_out = calc_leaf_output(parent_sums[0], parent_sums[1],
                                 l1, l2, cfg.max_delta_step)
        if use_smooth:
            a = cfg.path_smooth
            l_out = smooth_output(l_out, lc, parent_out, a)
            r_out = smooth_output(r_out, rc, parent_out, a)
            p_out = smooth_output(p_out, parent_sums[2], parent_out, a)
        if use_mono:
            # the parent's gain must be evaluated at ITS clipped output
            # too, or clipped leaves have every candidate gain deflated
            l_out = jnp.clip(l_out, out_lower, out_upper)
            r_out = jnp.clip(r_out, out_lower, out_upper)
            p_out = jnp.clip(p_out, out_lower, out_upper)
        parent_gain_c = leaf_gain_at_output(parent_sums[0],
                                            parent_sums[1], l1, l2, p_out)
        gain = (leaf_gain_at_output(lg, lh, l1, l2, l_out)
                + leaf_gain_at_output(rg, rh, l1, l2, r_out)
                - parent_gain_c)
        if use_mono:
            # veto thresholds that violate the feature's direction:
            # +1 (increasing): left (smaller values) must not exceed right
            violates = (mono[:, None, None].astype(jnp.float32)
                        * (l_out - r_out)) > 0
    else:
        parent_gain = leaf_gain(parent_sums[0], parent_sums[1],
                                cfg.lambda_l1, cfg.lambda_l2)
        gain = (leaf_gain(lg, lh, cfg.lambda_l1, cfg.lambda_l2)
                + leaf_gain(rg, rh, cfg.lambda_l1, cfg.lambda_l2)
                - parent_gain)

    n_value_bins = num_bin - has_nan.astype(jnp.int32)
    # thresholds t split value-bins {<=t} | {>t}; the extra slot when a NaN
    # bin exists realizes the "all values vs NaN" split
    valid_t = bin_idx < (n_value_bins[:, None] - 1
                         + has_nan.astype(jnp.int32)[:, None])
    valid = (valid_t[:, :, None]
             & num_allowed[:, None, None]
             & (lc >= cfg.min_data_in_leaf) & (rc >= cfg.min_data_in_leaf)
             & (lh >= cfg.min_sum_hessian_in_leaf)
             & (rh >= cfg.min_sum_hessian_in_leaf)
             & (gain > cfg.min_gain_to_split))
    if violates is not None:
        valid = valid & ~violates
    if cfg.extra_trees and extra_u is not None:
        # one random threshold per feature (valid thresholds occupy
        # bin_idx < num_bin - 1 regardless of the NaN bin)
        t_extra = (extra_u * (num_bin - 1).astype(jnp.float32)
                   ).astype(jnp.int32)                         # [F]
        valid = valid & (bin_idx == t_extra[:, None])[:, :, None]
    if cfg.has_contri and contri is not None:
        gain = gain * contri[:, None, None]
    if cfg.monotone_penalty > 0.0 and mono is not None \
            and depth is not None:
        # applied AFTER the min_gain validity check, like the
        # reference's post-FindBestThreshold gain scaling
        pf = monotone_penalty_factor(depth, cfg.monotone_penalty)
        gain = jnp.where((mono != 0)[:, None, None], gain * pf, gain)
    return jnp.where(valid, gain, NEG_INF), left


def per_feature_gains(hist: jax.Array, parent_sums: jax.Array,
                      num_bin: jax.Array, has_nan: jax.Array,
                      allowed_feature: jax.Array, cfg: SplitConfig,
                      is_cat: jax.Array = None, mono=None,
                      out_lower=None, out_upper=None,
                      cegb_pen=None, parent_out=None, extra_u=None,
                      contri=None, depth=None) -> jax.Array:
    """Best achievable gain per feature (``[F]``) — the local VOTE metric
    of the voting-parallel learner (PV-Tree,
    voting_parallel_tree_learner.cpp: machines propose their top-k
    features by local best gain)."""
    num_allowed = allowed_feature
    if cfg.has_categorical and is_cat is not None:
        num_allowed = allowed_feature & ~is_cat
    gain, _ = _numerical_candidates(hist, parent_sums, num_bin, has_nan,
                                    num_allowed, cfg, mono=mono,
                                    out_lower=out_lower,
                                    out_upper=out_upper,
                                    parent_out=parent_out,
                                    extra_u=extra_u, contri=contri,
                                    depth=depth)
    pf = jnp.max(gain, axis=(1, 2))                            # [F]
    if cfg.has_cegb:
        # vote on PENALIZED gains (the coupled term changes feature
        # ranking); categorical gains below are already penalized
        # inside _categorical_candidates
        pen = cfg.cegb_tradeoff * cfg.cegb_penalty_split * parent_sums[2]
        if cegb_pen is not None:
            pen = pen + cegb_pen
        pf = jnp.where(jnp.isfinite(pf), pf - pen, pf)
    if cfg.has_categorical and is_cat is not None:
        if cfg.cat_positions:
            ca = jnp.asarray(cfg.cat_positions, jnp.int32)
            all_gain_c, _, _, _ = _categorical_candidates(
                hist[ca], parent_sums, num_bin[ca], allowed_feature[ca],
                jnp.ones(len(cfg.cat_positions), jnp.bool_), cfg,
                out_lower=out_lower, out_upper=out_upper,
                cegb_pen=(None if cegb_pen is None else cegb_pen[ca]),
                parent_out=parent_out,
                contri=(None if contri is None else contri[ca]))
            pf_cat = jnp.full(pf.shape[0], NEG_INF).at[ca].set(
                jnp.max(all_gain_c, axis=(1, 2)))
            pf = jnp.maximum(pf, pf_cat)
        else:
            all_gain, _, _, _ = _categorical_candidates(
                hist, parent_sums, num_bin, allowed_feature, is_cat, cfg,
                out_lower=out_lower, out_upper=out_upper,
                cegb_pen=cegb_pen, parent_out=parent_out, contri=contri)
            pf = jnp.maximum(pf, jnp.max(all_gain, axis=(1, 2)))
    return pf


def elect_best(best: Dict[str, jax.Array],
               axis_name: str) -> Dict[str, jax.Array]:
    """Cross-device election of per-child best splits: all_gather the
    records over the mesh axis and keep the max-gain device's entry per
    child — the reference's ``SyncUpGlobalBestSplit`` (AllGather of
    serialized SplitInfo + max-gain pick, parallel_tree_learner.h).
    ``best`` fields carry a leading child dim ``[C]``; ``feature`` must
    already be a GLOBAL index."""
    gathered = jax.lax.all_gather(best, axis_name)             # [D, C, ...]
    win = jnp.argmax(gathered["gain"], axis=0)                 # [C]

    def take(a):
        idx = win.reshape((1, -1) + (1,) * (a.ndim - 2))
        return jnp.take_along_axis(a, idx.astype(jnp.int32), axis=0)[0]

    return jax.tree.map(take, gathered)


def find_best_split(hist: jax.Array, parent_sums: jax.Array,
                    num_bin: jax.Array, has_nan: jax.Array,
                    allowed_feature: jax.Array,
                    cfg: SplitConfig,
                    is_cat: jax.Array = None, mono=None,
                    out_lower=None, out_upper=None,
                    cegb_pen: jax.Array = None,
                    parent_out=None, extra_u=None, contri=None,
                    depth=None) -> Dict[str, jax.Array]:
    """Best split for one leaf given its histogram.

    Args:
      hist: ``[F, B, 3]`` float32 — (sum_grad, sum_hess, count) per bin.
      parent_sums: ``[3]`` — leaf totals (grad, hess, count).
      num_bin: ``[F]`` int32 — bins actually used per feature (incl. NaN bin).
      has_nan: ``[F]`` bool — whether the LAST used bin is the NaN bin.
      allowed_feature: ``[F]`` bool — column-sampling / interaction mask.
      cfg: static hyperparameters.
      is_cat: ``[F]`` bool — categorical features (scanned by
        ``_categorical_best`` instead of the threshold scan). Only read
        when ``cfg.has_categorical``.

    Returns dict of scalars: ``gain`` (−inf if no valid split), ``feature``,
    ``threshold_bin`` (split sends ``bin <= t`` left), ``default_left``,
    ``left_sums``/``right_sums`` (each ``[3]``), ``is_cat`` (categorical
    split?) and ``cat_bitset`` (``[ceil(B/32)]`` uint32 left-set over bins).
    """
    f, b, _ = hist.shape
    n_words = (b + 31) // 32

    num_allowed = allowed_feature
    if cfg.has_categorical and is_cat is not None:
        num_allowed = allowed_feature & ~is_cat

    gain, left = _numerical_candidates(hist, parent_sums, num_bin,
                                       has_nan, num_allowed, cfg,
                                       mono=mono, out_lower=out_lower,
                                       out_upper=out_upper,
                                       parent_out=parent_out,
                                       extra_u=extra_u, contri=contri,
                                       depth=depth)
    if cfg.has_cegb:
        # CEGB gain discount; candidates whose PENALIZED gain no longer
        # clears min_gain_to_split are rejected (the actual pruning)
        pen = cfg.cegb_tradeoff * cfg.cegb_penalty_split * parent_sums[2]
        if cegb_pen is not None:
            pen = pen + cegb_pen                    # [F] coupled penalty
            gain = gain - pen[:, None, None]
        else:
            gain = gain - pen
        gain = jnp.where(gain > cfg.min_gain_to_split, gain, NEG_INF)
    flat = gain.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    feature = (best // (b * 2)).astype(jnp.int32)
    threshold_bin = ((best // 2) % b).astype(jnp.int32)
    default_left = (best % 2).astype(jnp.bool_)
    left_best = left[feature, threshold_bin,
                     default_left.astype(jnp.int32)]

    if cfg.has_categorical and is_cat is not None:
        if cfg.cat_positions:
            ca = jnp.asarray(cfg.cat_positions, jnp.int32)
            cgain, cfeat_l, cleft, cinset = _categorical_best(
                hist[ca], parent_sums, num_bin[ca], allowed_feature[ca],
                jnp.ones(len(cfg.cat_positions), jnp.bool_), cfg,
                out_lower=out_lower, out_upper=out_upper,
                cegb_pen=(None if cegb_pen is None else cegb_pen[ca]),
                parent_out=parent_out,
                contri=(None if contri is None else contri[ca]))
            cfeat = ca[cfeat_l]
        else:
            cgain, cfeat, cleft, cinset = _categorical_best(
                hist, parent_sums, num_bin, allowed_feature, is_cat, cfg,
                out_lower=out_lower, out_upper=out_upper,
                cegb_pen=cegb_pen, parent_out=parent_out, contri=contri)
        take_cat = cgain > best_gain
        best_gain = jnp.maximum(best_gain, cgain)
        feature = jnp.where(take_cat, cfeat, feature)
        threshold_bin = jnp.where(take_cat, 0, threshold_bin)
        default_left = jnp.where(take_cat, False, default_left)
        left_best = jnp.where(take_cat, cleft, left_best)
        cat_bitset = jnp.where(take_cat,
                               _pack_bitset(cinset, n_words),
                               jnp.zeros(n_words, jnp.uint32))
        is_cat_split = take_cat
    else:
        cat_bitset = jnp.zeros(n_words, jnp.uint32)
        is_cat_split = jnp.array(False)

    right_best = parent_sums - left_best
    return {
        "gain": best_gain,
        "feature": feature,
        "threshold_bin": threshold_bin,
        "default_left": default_left,
        "left_sums": left_best,
        "right_sums": right_best,
        "is_cat": is_cat_split,
        "cat_bitset": cat_bitset,
    }
