"""Leaf-wise tree learner: batched best-first growth under jit.

Reference: ``SerialTreeLearner::Train`` (src/treelearner/serial_tree_learner
.cpp, UNVERIFIED — empty mount, see SURVEY.md banner): best-first growth —
repeatedly construct the smaller new leaf's histogram, derive the sibling
by SUBTRACTION from the parent, find per-leaf best splits, expand the best
leaf, partition its rows.

TPU-first design (SURVEY.md §7.1):
- The reference's ``DataPartition`` per-leaf index buckets become a per-row
  ``leaf_id`` vector; splitting is a masked ``where`` update — no dynamic
  shapes.
- The growth loop is ONE ``lax.while_loop``; tree structure lives in
  fixed-size flat arrays exactly like the reference's ``Tree`` (~leaf child
  encoding). Each array has one trailing TRASH slot so vectorized scatters
  for inactive batch lanes are harmless.
- BATCHED best-first: each round expands the top-``leaf_batch`` leaves at
  once, and the Pallas kernel (ops/pallas_histogram.py) computes ALL their
  smaller-child histograms in one fused data scan — the masks pack into
  the matmul N dimension, amortizing both the scan and the MXU's N-padding.
  ``leaf_batch=1`` reproduces the reference's exact leaf-wise order; larger
  batches are a bounded relaxation (each round's choices are still the
  current best leaves) trading exact split ORDER for ~10-20x fewer scans.
- The histogram pool (``HistogramPool`` LRU) becomes a dense
  ``[L+1, F, B, 3]`` array so sibling subtraction is a slice.
- Data-parallel: with ``cfg.axis_name`` set, rows are sharded over that
  mesh axis and every histogram/leaf-sum is psum'd — the TPU-native
  replacement for the reference's socket ReduceScatter (SURVEY.md §3.4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..ops.pallas_histogram import (multi_leaf_histogram,
                                    multi_leaf_histogram_xla,
                                    onehot_layout)
from ..ops.route import route_nodes, route_rows, route_rows_xla
from ..ops.split import (NEG_INF, SplitConfig, calc_leaf_output,
                         elect_best, find_best_split, per_feature_gains,
                         smooth_output)


@dataclasses.dataclass(frozen=True)
class GrowConfig:
    """Static tree-growth hyperparameters."""

    num_leaves: int = 31
    max_depth: int = -1
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    num_bins: int = 256
    rows_per_block: int = 1024
    precise_histogram: bool = False
    # number of leaves expanded per round (1 = exact reference order)
    leaf_batch: int = 1
    # use the fused Pallas kernel (TPU) vs the XLA einsum fallback
    use_pallas: bool = False
    # quantized-gradient int8 x int8 -> int32 kernel variant (exact
    # integer accumulation at 2x MXU rate); only valid when vals carry
    # small integer levels (use_quantized_grad, engine-enforced)
    int_hist: bool = False
    # static bin count of each PHYSICAL column of the histogram source
    # (bins_t's rows: bundle_plan.phys_num_bin under EFB, 1 for the
    # shard-width padding columns): the Pallas kernel builds one-hot
    # rows for those bins only. () = every column has num_bins
    hist_col_bins: Tuple[int, ...] = ()
    # GOSS histogram-only compaction: histograms scan the compacted
    # sampled-row buffer (grow_tree's `compact` argument); the table's
    # own leaf ids, which the score update reads, are routed once after
    # the loop (in it only where lazy CEGB reads them)
    hist_compact: bool = False
    # forced splits (forcedsplits_filename): number of entries in the
    # PREORDER-flattened forced-split table (grow_tree's `forced`
    # argument; parents must precede children — the target-slot
    # resolution depends on it); the first n_forced growth rounds
    # apply them one per round, engine-gated to the serial pool-mode
    # learner
    n_forced: int = 0
    # mesh axis for data-parallel histogram reduction ("" = single device)
    axis_name: str = ""
    # -- distributed modes (SURVEY.md §3.4) ---------------------------
    # packed quantized collective wire (tpu_hist_packed_wire): with
    # use_quantized_grad, each (g,h) level-sum pair rides ONE int32
    # (g in the high 16 bits, non-negative h in the low 16) and count
    # rides a second int32 — 2/3 of the f32 psum payload, bit-exact.
    # A 3-scalar guard psum checks sum-of-local-extreme bounds per
    # round; any risk of int16 overflow (or a negative hessian) falls
    # back to the f32 reduction inside the same jitted step.
    packed_wire: bool = False
    # data-parallel + hist_scatter: ReduceScatter feature ownership —
    # each device reduces/owns F/num_shards features, finds its local
    # best, and the winner is elected by all_gather
    # (data_parallel_tree_learner.cpp)
    hist_scatter: bool = False
    num_shards: int = 1
    # data-parallel + voting: PV-Tree — local top_k feature votes,
    # global top-2k elected, only elected columns psum'd
    # (voting_parallel_tree_learner.cpp)
    voting: bool = False
    top_k: int = 20
    # feature-parallel: rows replicated, feature columns sharded over
    # this axis; split search local, winner elected, partition via
    # ownership-psum (feature_parallel_tree_learner.cpp)
    feature_axis: str = ""
    # constraints (monotone_constraints.hpp; ColSampler interaction
    # constraints): zero-cost when False. monotone_intermediate uses
    # the realized child outputs as the children's bounds
    # (IntermediateLeafConstraints) instead of basic's midpoint —
    # WITHOUT the reference's retroactive ancestor updates (documented
    # divergence); monotone_penalty discounts constrained-feature
    # splits near the root
    has_monotone: bool = False
    monotone_intermediate: bool = False
    # advanced mode (AdvancedLeafConstraints, monotone_constraints.hpp):
    # intermediate's per-round bound recompute, but each node's bound
    # aggregates only the opposing subtree's BOUNDARY-ADJACENT strip —
    # leaves whose split-feature bin range touches the node's threshold
    # — instead of the whole subtree (shielded leaves are ordered
    # transitively through the strip chain). Tracked via per-leaf
    # per-feature bin-range carries.
    monotone_advanced: bool = False
    monotone_penalty: float = 0.0
    has_interaction: bool = False
    # EFB (dataset_loader.cpp FastFeatureBundling): bins is the bundled
    # PHYSICAL matrix; histograms are expanded to logical features via
    # the bundle maps before split finding. Mutually exclusive with
    # hist_scatter / feature_axis (engine enforces).
    has_bundles: bool = False
    # leaf-ordered device row partition (ops/partition.py;
    # tpu_hist_partition): rows ride the carry physically grouped by
    # leaf (per-leaf offset/count tables + a stable cumsum front/back
    # move per round), and each round's histogram scans only the
    # elected children's padded spans — a lax.switch over a static pow2
    # budget ladder, falling back to the masked full scan whenever the
    # spans would not shrink it. Siblings still come from pool
    # subtraction.
    partition: bool = False
    # block size of the TPU compact_rows-based repartition move
    # (<= 1024, divides the padded row count; the engine computes it)
    part_rpb: int = 1024
    # per-NODE column sampling (ColSampler feature_fraction_bynode)
    feature_fraction_bynode: float = 1.0
    # CEGB gain discounts (cost_effective_gradient_boosting.hpp)
    has_cegb: bool = False
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0
    # lazy per-row feature-acquisition penalty: grow_tree's `lazy`
    # argument carries (U [n, F] acquired-matrix, penalty [F]); each
    # candidate child's penalty is penalty[f] x #unacquired rows,
    # counted with a membership-mask matmul per round
    has_cegb_lazy: bool = False
    # path smoothing (feature_histogram.hpp USE_SMOOTHING): children
    # shrink toward the parent leaf's stored output by n/(n+alpha)
    path_smooth: float = 0.0
    # extra_trees (extremely randomized trees): one random numerical
    # threshold per feature per node, drawn from node_key + extra_seed
    extra_trees: bool = False
    extra_seed: int = 6
    # feature_contri per-feature gain multipliers (the `contri` array
    # argument of grow_tree)
    has_contri: bool = False
    # categorical split search (zero-cost when has_categorical=False);
    # cat_positions: static categorical indices for the sliced fast
    # path (empty under scatter/feature-parallel whose search space is
    # a dynamic shard)
    has_categorical: bool = False
    cat_positions: Tuple = ()
    max_cat_threshold: int = 32
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100

    @property
    def cat_words(self) -> int:
        """uint32 words per categorical bitset (over bins)."""
        return (self.num_bins + 31) // 32

    @property
    def split_config(self) -> SplitConfig:
        return SplitConfig(
            lambda_l1=self.lambda_l1, lambda_l2=self.lambda_l2,
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            min_gain_to_split=self.min_gain_to_split,
            max_delta_step=self.max_delta_step,
            has_categorical=self.has_categorical,
            cat_positions=self.cat_positions,
            max_cat_threshold=self.max_cat_threshold,
            cat_smooth=self.cat_smooth, cat_l2=self.cat_l2,
            max_cat_to_onehot=self.max_cat_to_onehot,
            min_data_per_group=self.min_data_per_group,
            has_monotone=self.has_monotone,
            monotone_penalty=self.monotone_penalty,
            has_cegb=self.has_cegb,
            cegb_tradeoff=self.cegb_tradeoff,
            cegb_penalty_split=self.cegb_penalty_split,
            path_smooth=self.path_smooth,
            extra_trees=self.extra_trees,
            has_contri=self.has_contri)


class GrowState(NamedTuple):
    """while_loop carry. Leaf arrays sized L+1 (slot L = trash); node
    arrays sized L (slot L-1 = trash; real nodes use 0..L-2).

    Carry-width note (round-6 %copy trim): per-leaf/per-node float
    stats that update together are PACKED into one array each
    (``best_lr_sums``, ``node_vcg``, ``leaf_vcw``, ``leaf_bounds``) —
    the round-5 trace attributed ~9% of device busy to while-loop
    ``%copy`` traffic whose cost is per-ARRAY overhead, so fewer carry
    tuple elements means fewer copies per round at identical numerics.

    Donation note (round 7, ``tpu_donate`` — docs/perf.md "Iteration
    floor"): this carry — including the leaf-ordered partition arrays
    (``part_bins``/``part_vals``, the largest elements) — lives
    entirely INSIDE grow_tree's jit, and ``lax.while_loop`` exposes no
    donation control; XLA's buffer assignment already aliases the
    carry slots where liveness permits. The jit-boundary carries the
    donation pass CAN reach (the step/chunk score, valid scores, the
    streamed score slots, cegb_U) donate in boosting/gbdt.py and
    boosting/streaming.py; the residual in-loop ``%copy`` is attacked
    structurally (fewer arrays, above), not by donation.
    """

    split_idx: jnp.ndarray
    num_leaves: jnp.ndarray
    has_split: jnp.ndarray
    leaf_id: jnp.ndarray            # [n]
    leaf_hist: jnp.ndarray          # [L+1, F, B, 3]
    leaf_sums: jnp.ndarray          # [L+1, 3]
    leaf_depth: jnp.ndarray         # [L+1]
    best_gain: jnp.ndarray          # [L+1]
    best_feature: jnp.ndarray
    best_threshold: jnp.ndarray
    best_default_left: jnp.ndarray
    best_lr_sums: jnp.ndarray       # [L+1, 2, 3] (left, right)
    best_is_cat: jnp.ndarray        # [L+1]
    best_cat_bitset: jnp.ndarray    # [L+1, W]
    split_feature: jnp.ndarray      # [L]
    threshold_bin: jnp.ndarray
    default_left: jnp.ndarray
    node_is_cat: jnp.ndarray        # [L]
    node_cat_bitset: jnp.ndarray    # [L, W]
    left_child: jnp.ndarray
    right_child: jnp.ndarray
    node_vcg: jnp.ndarray           # [L, 3] (internal value/count/gain)
    leaf_vcw: jnp.ndarray           # [L+1, 3] (value, count, weight)
    leaf_parent: jnp.ndarray
    leaf_is_left: jnp.ndarray
    # monotone "basic" bounds ([L+1, 2] = lower/upper; ±inf when
    # unconstrained) and interaction-constraint path features
    # ([L+1, F or 1-dummy]; the per-leaf allowed set is derived from
    # this at split time)
    leaf_bounds: jnp.ndarray
    leaf_used: jnp.ndarray
    # intermediate monotone mode: [L, L+1] membership of each leaf in
    # each node's left/right subtree ([1, 1] placeholder otherwise) —
    # bounds are recomputed per round from CURRENT leaf outputs via
    # masked min/max over these, the TPU-native replacement for
    # IntermediateLeafConstraints' recursive constraint walks
    mono_left: jnp.ndarray
    mono_right: jnp.ndarray
    # advanced monotone mode: per-leaf per-feature bin ranges
    # ([L+1, F_meta] when active, [1, 1] placeholders otherwise) — the
    # adjacency test for strip-bounded constraints
    leaf_flo: jnp.ndarray
    leaf_fhi: jnp.ndarray
    # compact-row leaf ids for GOSS histogram-only compaction ([1]
    # placeholder otherwise): partitioned by the same splits as leaf_id
    leaf_id_c: jnp.ndarray
    # the leaf each node split ([L]; [1] placeholder unless the table is
    # routed after the loop): left_child forgets it when that leaf
    # splits again, and ops/route.py replays the nodes by it
    node_leaf: jnp.ndarray
    # forced-split machinery (placeholder when cfg.n_forced == 0):
    # each entry's state: -1 waiting on parent, >=0 realized target
    # leaf slot, -2 cancelled (skipped parent), -3 applied
    forced_target: jnp.ndarray
    # leaf-ordered row partition (cfg.partition; [1]/[1,1] placeholders
    # otherwise): the histogram source arrays physically grouped by
    # leaf, the per-POSITION leaf ids, and the (offset, count) tables
    part_bins: jnp.ndarray          # [F, n] fm (Pallas) / [n, F] rm
    part_vals: jnp.ndarray          # [C, n] fm / [n, C] rm
    part_leaf: jnp.ndarray          # [n]
    part_off: jnp.ndarray           # [L+1]
    part_cnt: jnp.ndarray           # [L+1]
    # rows the histogram scans touched so far this tree (always
    # maintained — the masked path counts n per round) — the
    # hist.cols_scanned work counter
    rows_scanned: jnp.ndarray
    # invocations of the histogram kernel so far this tree (the root's
    # included) and, summed over them, the leaf slots that held a leaf
    # (id not -1): the hist.calls / hist.leaf_slots_filled counters
    hist_calls: jnp.ndarray
    hist_slots_filled: jnp.ndarray


def _masked_gains(gain, leaf_depth, num_leaves, max_depth):
    Lp1 = gain.shape[0]
    active = jnp.arange(Lp1, dtype=jnp.int32) < num_leaves
    gains = jnp.where(active, gain, NEG_INF)
    if max_depth > 0:
        gains = jnp.where(leaf_depth < max_depth, gains, NEG_INF)
    return gains


@functools.partial(jax.jit, static_argnames=("cfg",))
def grow_tree(bins: jax.Array, vals: jax.Array,
              feat_num_bin: jax.Array, feat_has_nan: jax.Array,
              allowed_feature: jax.Array, cfg: GrowConfig,
              bins_t: jax.Array = None,
              is_cat: jax.Array = None,
              mono: jax.Array = None,
              groups: jax.Array = None,
              bundle: Tuple = None,
              chan_scale: jax.Array = None,
              node_key: jax.Array = None,
              cegb_pen: jax.Array = None,
              contri: jax.Array = None,
              compact: Tuple = None,
              forced: Tuple = None,
              lazy: Tuple = None,
              ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Grow one tree.

    Args:
      bins: ``[n, F]`` row-major binned matrix (partition gathers).
      vals: ``[n, 3]`` float32 (grad*mask, hess*mask, count-mask).
      feat_num_bin / feat_has_nan: ``[F]`` per-feature bin metadata.
      allowed_feature: ``[F]`` bool feature-sampling mask for this tree.
      cfg: static growth config.
      bins_t: ``[F, n]`` int8 feature-major copy; required (and only read)
        when ``cfg.use_pallas`` — the Pallas kernel input.
      is_cat: ``[F]`` bool categorical-feature mask; only read when
        ``cfg.has_categorical``.

    Returns:
      (tree dict of fixed-size arrays + ``num_leaves``, per-row leaf_id).
    """
    n_rows, F = bins.shape          # F = LOCAL width under feature_axis
    L = cfg.num_leaves
    B = cfg.num_bins
    Kb = max(1, min(cfg.leaf_batch, L))
    i32 = jnp.int32
    scfg = cfg.split_config

    # GOSS histogram-only compaction (cfg.hist_compact): histograms scan
    # a COMPACTED buffer of just the sampled rows — the reference's
    # bag_data_indices_ subset scan, without its gather. The buffer's
    # leaf ids ride the carry and are routed at every loop trip; the
    # table's are not (`defer_full` below).
    if not cfg.hist_compact:
        compact = None
    if compact is not None:
        bins_c, bins_t_c, vals_c = compact
        n_rows_c = bins_c.shape[0]
        h_bins, h_bins_t, h_vals = bins_c, bins_t_c, vals_c
    else:
        bins_c = bins_t_c = vals_c = None
        n_rows_c = 1
        h_bins, h_bins_t, h_vals = bins, bins_t, vals

    # ---- distributed search modes (SURVEY.md §3.4) -------------------
    mode_feature = bool(cfg.feature_axis)
    mode_voting = bool(cfg.axis_name) and cfg.voting
    mode_scatter = (bool(cfg.axis_name) and cfg.hist_scatter
                    and not cfg.voting and cfg.num_shards > 1
                    and F % cfg.num_shards == 0 and not mode_feature)
    if mode_scatter:
        F_s = F // cfg.num_shards       # owned feature slice per device
    else:
        F_s = F

    # packed wire is a quantized-only, cross-device-reduce-only
    # optimization; voting reduces elected columns later and feature-
    # parallel/serial histograms are already complete
    use_packed = (cfg.packed_wire and chan_scale is not None
                  and bool(cfg.axis_name)
                  and not (mode_voting or mode_feature))

    @obs.scope("grower/histogram")
    def hist_reduce(h):
        """Mode-specific cross-device histogram reduction — ONE
        collective through the shared packed-int32 wire
        (learner/collective.py; the streaming engine reduces through
        the same helper). With quantized gradients
        (use_quantized_grad), ``vals`` hold small integer levels —
        EXACT in the bf16 matmul and reduced as ints (the reference's
        int-histogram allreduce, cuda_gradient_discretizer.cu) — and
        are rescaled to real units here, right after the reduction."""
        from .collective import hist_allreduce
        if use_packed:
            h = hist_allreduce(h, cfg.axis_name, scatter=mode_scatter,
                               packed=True)
        elif cfg.axis_name and not (mode_voting or mode_feature):
            h = hist_allreduce(h, cfg.axis_name, scatter=mode_scatter)
        if chan_scale is not None:
            h = h * chan_scale
        return h

    if cfg.use_pallas:
        if h_bins_t is None:
            raise ValueError("cfg.use_pallas=True requires bins_t ([F, n] "
                             "feature-major int8 binned matrix)")
        if B > 256:
            raise ValueError(
                f"Pallas histogram path supports at most 256 bins (int8 "
                f"storage round-trips 0..255); got num_bins={B}. Use the "
                f"XLA path for wider histograms.")
        h_vals_t = h_vals.T
        col_bins = cfg.hist_col_bins or (B,) * h_bins_t.shape[0]
        # block size must divide the padded row count; rows_per_block does
        # (padding guarantees it), so cap via gcd to keep the streamed
        # one-hot within scoped VMEM without breaking divisibility.
        # On the v5e the row block hardly matters since the kernel's
        # lane loop (PERF.md §6, PR 30: R 2,048 / 4,096 / 8,192 read
        # 7.44 / 7.42 / 7.28 ms a call at airline's 13 columns), but the
        # feature-blocked grid (more one-hot rows than one block holds,
        # e.g. MSLR widths) overflows the 16MB scoped-vmem budget at
        # 4096 — those shapes cap at 2048.
        import math
        r_cap = 4096 if onehot_layout(col_bins, B).n_fb == 1 else 2048
        pr = math.gcd(cfg.rows_per_block, r_cap)
        base_rpb = pr

        @obs.scope("grower/histogram")
        def hist_kernel(b_src, v_src, l_src, ids, rpb):
            """Raw local multi-leaf histogram over an arbitrary source
            (the whole data, the GOSS buffer, or partition spans) —
            cross-device reduction stays with the caller so the span
            lax.switch never encloses a collective."""
            return multi_leaf_histogram(
                b_src, v_src, l_src, ids, num_bins=B, col_bins=col_bins,
                rows_per_block=rpb, int_mode=cfg.int_hist)

        def hist_multi(leaf_id, small_ids):
            return hist_reduce(hist_kernel(
                h_bins_t, h_vals_t, leaf_id, small_ids, pr))
    else:
        import math
        base_rpb = cfg.rows_per_block

        @obs.scope("grower/histogram")
        def hist_kernel(b_src, v_src, l_src, ids, rpb):
            return multi_leaf_histogram_xla(
                b_src, v_src, l_src, ids, num_bins=B,
                rows_per_block=rpb, precise=cfg.precise_histogram)

        def hist_multi(leaf_id, small_ids):
            return hist_reduce(hist_kernel(
                h_bins, h_vals, leaf_id, small_ids,
                cfg.rows_per_block))

    # ---- leaf-ordered row partition (cfg.partition) -------------------
    # ops/partition.py: rows (of the histogram SOURCE — the GOSS buffer
    # under hist_compact, else all rows) ride the carry grouped by leaf;
    # each round's histogram scans only the elected children's padded
    # spans via a static pow2 budget ladder, falling back to the masked
    # full scan when the spans would not shrink it.
    use_part = cfg.partition
    part_fm = cfg.use_pallas            # feature-major partition layout
    n_h = h_bins.shape[0]               # histogram-source row count
    if use_part:
        from ..ops import partition as part_ops
        part_budgets = part_ops.span_budgets(n_h, Kb)
        # float32: the counter reaches n x rounds (x shards after the
        # psum) — int32 wraps at the very scales the metric watches
        _span_rows = jnp.asarray(
            tuple(Kb * s for s in part_budgets) + (n_h,),
            jnp.float32)

        @obs.scope("grower/histogram")
        def span_hist(pb, pv, pl, ids, offs, cnts):
            """[M, F_h, B, 3] local histograms of the elected children
            + the rows this round's scan touched."""
            branches = []
            for S in part_budgets:
                def mk(S):
                    rpb_b = math.gcd(S, base_rpb)

                    def br(pb, pv, pl, ids, offs, cnts):
                        bcat, vcat, lcat = part_ops.slice_spans(
                            pb, pv, pl, offs, cnts, S, part_fm)
                        return hist_kernel(bcat, vcat, lcat, ids, rpb_b)
                    return br
                branches.append(mk(S))

            def full_br(pb, pv, pl, ids, offs, cnts):
                # masked full scan over the partition (pl is a valid
                # per-position leaf vector) — the degenerate-budget
                # fallback, never worse than the masked path
                return hist_kernel(pb, pv, pl, ids, base_rpb)
            branches.append(full_br)
            need = jnp.max(jnp.where(ids >= 0, cnts, 0))
            if not part_budgets:
                return full_br(pb, pv, pl, ids, offs, cnts), \
                    jnp.asarray(n_h, jnp.float32)
            idx = jnp.sum((jnp.asarray(part_budgets, i32) < need)
                          .astype(i32))
            hist = jax.lax.switch(idx, branches, pb, pv, pl, ids,
                                  offs, cnts)
            return hist, _span_rows[idx]

    W = cfg.cat_words
    if not cfg.has_categorical:
        is_cat = None
    if not cfg.has_monotone:
        mono = None
    if not cfg.has_interaction:
        groups = None
    if not cfg.has_bundles:
        bundle = None
    if not cfg.has_contri:
        contri = None
    F_meta = feat_num_bin.shape[0]      # GLOBAL (logical) feature count
    if bundle is not None:
        assert not (mode_scatter or mode_feature), \
            "EFB composes with serial/psum/voting learners only"
        (bmap_pf, bmap_pb, bmap_valid, bat_def, bbundled, bphys_col,
         bstart, bdef) = bundle

        def expand_hist(hists, totals):
            """Physical [C, F_b, Bb, 3] -> logical [C, F_meta, B, 3];
            each bundled feature's DEFAULT-bin mass is recovered as the
            leaf-total residual (injected at its default slot)."""
            g = hists[:, bmap_pf, bmap_pb, :]
            g = jnp.where(bmap_valid[None, :, :, None], g, 0.0)
            resid = totals[:, None, :] - jnp.sum(g, axis=2)  # [C, F, 3]
            return g + (bat_def[None, :, :, None]
                        * resid[:, :, None, :])

    # search-slice metadata: under scatter/feature-parallel each device
    # searches only the F_s features it owns, offset into the GLOBAL
    # feature index space
    if mode_scatter or mode_feature:
        _ax = cfg.axis_name if mode_scatter else cfg.feature_axis
        off = (jax.lax.axis_index(_ax) * F_s).astype(i32)
        nb_s = jax.lax.dynamic_slice_in_dim(feat_num_bin, off, F_s)
        hn_s = jax.lax.dynamic_slice_in_dim(feat_has_nan, off, F_s)
        al_s = jax.lax.dynamic_slice_in_dim(allowed_feature, off, F_s)
        ic_s = (jax.lax.dynamic_slice_in_dim(is_cat, off, F_s)
                if is_cat is not None else None)
        mn_s = (jax.lax.dynamic_slice_in_dim(mono, off, F_s)
                if mono is not None else None)
        cp_s = (jax.lax.dynamic_slice_in_dim(cegb_pen, off, F_s)
                if cegb_pen is not None else None)
        ct_s = (jax.lax.dynamic_slice_in_dim(contri, off, F_s)
                if contri is not None else None)
    else:
        off = jnp.zeros((), i32)
        nb_s, hn_s, al_s, ic_s, mn_s, cp_s, ct_s = (
            feat_num_bin, feat_has_nan, allowed_feature, is_cat,
            mono, cegb_pen, contri)

    def bynode_mask(allow2, round_tag):
        """Exact-k per-child column sampling
        (ColSampler feature_fraction_bynode): k is the fraction of each
        child's CURRENTLY-ALLOWED features (after per-tree sampling,
        interaction constraints, and shard padding), like the
        reference's per-node resample of the valid set."""
        if cfg.feature_fraction_bynode >= 1.0 or node_key is None:
            return allow2
        C2 = allow2.shape[0]
        kk = jax.random.fold_in(node_key, round_tag)
        u = jnp.where(allow2, jax.random.uniform(kk, (C2, F_meta)),
                      jnp.inf)
        n_allow = jnp.sum(allow2, axis=1)
        k_idx = jnp.clip(
            jnp.ceil(cfg.feature_fraction_bynode
                     * n_allow.astype(jnp.float32)).astype(i32) - 1,
            0, F_meta - 1)
        kth = jnp.take_along_axis(jnp.sort(u, axis=1), k_idx[:, None],
                                  axis=1)
        return allow2 & (u <= kth)

    def extra_uniforms(C, round_tag):
        """Per-(child, feature) uniforms for extra_trees' one random
        threshold per node — GLOBAL feature width, drawn from a common
        key so every device slices a consistent random field."""
        if not cfg.extra_trees or node_key is None:
            return None
        kk = jax.random.fold_in(
            jax.random.fold_in(node_key, 0xE77A + cfg.extra_seed),
            round_tag)
        return jax.random.uniform(kk, (C, F_meta))

    if not cfg.has_cegb_lazy:
        lazy = None
    # Under a compact buffer the histograms read leaf_id_c alone, and
    # inside the loop nothing else reads the table's own ids but the
    # lazy penalty: so the loop does not route the table's rows at all.
    # They are routed once, after it, through the finished tree
    # (ops/route.py), for the one reader a tree has: the score update.
    # The same holds under the leaf-ordered partition, whose histograms
    # read the partition's own per-position ids (where the route can
    # replay the tree: unbundled columns, all of them on this device).
    defer_full = lazy is None and (
        compact is not None
        or (cfg.partition and not cfg.has_bundles
            and not cfg.feature_axis))
    if compact is not None and defer_full:
        assert not cfg.has_bundles and not cfg.feature_axis, \
            "a compact buffer is handed by the serial, unbundled step only"
    if lazy is not None:
        lazy_U, lazy_pen = lazy
        notU = (1.0 - lazy_U.astype(jnp.float32)).astype(jnp.bfloat16)

        def lazy_pen2(child_ids, lid_vec, pathf=None):
            """[C] candidate leaf ids -> [C, F] lazy penalties:
            penalty[f] x #rows of the child that never acquired f
            (0/1 bf16 operands, exact f32 accumulation). ``pathf``
            ([C, F] bool) marks features already split on the child's
            path THIS tree: every row of the child acquired those on
            split application (cost_effective_gradient_boosting.hpp),
            so re-splitting them deeper is penalty-free."""
            mk = (lid_vec[:, None]
                  == child_ids[None, :]).astype(jnp.bfloat16)  # [n, C]
            cnt = jax.lax.dot_general(
                mk, notU, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)            # [C, F]
            if pathf is not None:
                cnt = cnt * (1.0 - pathf.astype(jnp.float32))
            return cnt * lazy_pen[None, :]
    else:
        lazy_pen2 = None

    @obs.scope("grower/split_search")
    def search_best(hists, sums, lowers=None, uppers=None, allows=None,
                    parent_outs=None, round_tag=0, depths=None,
                    pen2=None):
        """Best split per child: ``hists [C, F_h, B, 3]`` (mode-reduced),
        ``sums [C, 3]`` global leaf totals, optional per-child monotone
        output bounds (``[C]``), interaction-constrained allowed
        masks (``[C, F_meta]``, GLOBAL width), and per-child parent
        outputs (``[C]``; path smoothing). Returns per-child best
        dict with GLOBAL feature indices, identical on every device."""
        C = hists.shape[0]
        if lowers is None:
            lowers = jnp.full(C, -jnp.inf, jnp.float32)
            uppers = jnp.full(C, jnp.inf, jnp.float32)
        allows_g = (jnp.broadcast_to(allowed_feature, (C, F_meta))
                    if allows is None else allows)
        eu = extra_uniforms(C, round_tag)                   # [C, F_meta]
        if mode_voting:
            # PV-Tree (voting_parallel_tree_learner.cpp): vote with
            # LOCAL histograms + local totals, elect global top-2k by
            # vote count, reduce only those columns
            local_sums = jnp.sum(hists[:, 0], axis=1)        # [C, 3]
            if bundle is not None:
                hists = expand_hist(hists, local_sums)
            pf = jax.vmap(lambda h, s, al, lo, hi, po, eu_, dp:
                          per_feature_gains(
                              h, s, feat_num_bin, feat_has_nan, al, scfg,
                              is_cat, mono=mono, out_lower=lo,
                              out_upper=hi, cegb_pen=cegb_pen,
                              parent_out=po, extra_u=eu_,
                              contri=contri, depth=dp))(
                hists, local_sums, allows_g, lowers, uppers,
                parent_outs, eu, depths)                     # [C, F]
            k_ = min(cfg.top_k, F_meta)
            vk = min(2 * cfg.top_k, F_meta)
            _, top_local = jax.lax.top_k(pf, k_)             # [C, k]
            votes = jnp.zeros((C, F_meta), jnp.float32).at[
                jnp.arange(C)[:, None], top_local].add(1.0)
            votes = jax.lax.psum(votes, cfg.axis_name)
            _, elected = jax.lax.top_k(votes, vk)            # [C, vk]
            hist_e = jnp.take_along_axis(
                hists, elected[:, :, None, None], axis=1)
            hist_e = jax.lax.psum(hist_e, cfg.axis_name)
            nb_e, hn_e = feat_num_bin[elected], feat_has_nan[elected]
            al_e = jnp.take_along_axis(allows_g, elected, axis=1)
            ic_e = is_cat[elected] if is_cat is not None else None
            mn_e = mono[elected] if mono is not None else None
            cp_e = cegb_pen[elected] if cegb_pen is not None else None
            ct_e = contri[elected] if contri is not None else None
            eu_e = (jnp.take_along_axis(eu, elected, axis=1)
                    if eu is not None else None)
            scfg_e = dataclasses.replace(scfg, cat_positions=())
            best = jax.vmap(
                lambda h, s, nb, hn, al, ic, mn, cp, lo, hi, po, eu_,
                ct, dp:
                find_best_split(
                    h, s, nb, hn, al, scfg_e, is_cat=ic, mono=mn,
                    out_lower=lo, out_upper=hi, cegb_pen=cp,
                    parent_out=po, extra_u=eu_, contri=ct, depth=dp))(
                hist_e, sums, nb_e, hn_e, al_e, ic_e, mn_e, cp_e,
                lowers, uppers, parent_outs, eu_e, ct_e, depths)
            best["feature"] = jnp.take_along_axis(
                elected, best["feature"][:, None], axis=1)[:, 0]
            return best
        if bundle is not None:
            hists = expand_hist(hists, sums)
        allows_s = (jax.lax.dynamic_slice_in_dim(allows_g, off, F_s,
                                                 axis=1)
                    if (mode_scatter or mode_feature) else allows_g)
        eu_s = (jax.lax.dynamic_slice_in_dim(eu, off, F_s, axis=1)
                if eu is not None and (mode_scatter or mode_feature)
                else eu)
        # one penalty shape for both CEGB flavors: per-child lazy (+
        # coupled), broadcast coupled, or None — a single vmap call
        # (None vmaps as an empty pytree)
        if pen2 is not None:
            pen_c = pen2 + (cp_s[None, :] if cp_s is not None else 0.0)
        elif cp_s is not None:
            pen_c = jnp.broadcast_to(cp_s[None, :],
                                     (hists.shape[0], cp_s.shape[0]))
        else:
            pen_c = None
        best = jax.vmap(lambda h, s, al, lo, hi, po, eu_, dp, p2:
                        find_best_split(
                            h, s, nb_s, hn_s, al, scfg, is_cat=ic_s,
                            mono=mn_s, out_lower=lo, out_upper=hi,
                            cegb_pen=p2, parent_out=po, extra_u=eu_,
                            contri=ct_s, depth=dp))(
            hists, sums, allows_s, lowers, uppers, parent_outs,
            eu_s, depths, pen_c)
        best["feature"] = best["feature"] + off
        if mode_scatter:
            # SyncUpGlobalBestSplit across feature owners
            return elect_best(best, cfg.axis_name)
        if mode_feature:
            return elect_best(best, cfg.feature_axis)
        return best

    @obs.scope("grower/leaf_values")
    def leaf_out(sums):
        return calc_leaf_output(sums[..., 0], sums[..., 1], cfg.lambda_l1,
                                cfg.lambda_l2, cfg.max_delta_step)

    use_mono_inter = cfg.has_monotone and cfg.monotone_intermediate
    use_mono_adv = use_mono_inter and cfg.monotone_advanced

    # forced splits (forcedsplits_filename; Tree::AddSplit forced paths
    # in serial_tree_learner.cpp ForceSplits — UNVERIFIED): a PREORDER
    # table (parents before children). Every READY entry (parent
    # realized) is applied in the SAME leaf-batch round — sibling
    # entries land together, so a k-entry table consumes ~depth(table)
    # rounds, not k (round 4; was one-entry-per-round). Numerical AND
    # categorical entries (one-vs-rest bin bitsets) are supported.
    # forced_target codes: -1 waiting on parent, >=0 target leaf slot,
    # -2 cancelled (skipped parent), -3 applied. Requires the pool
    # (leaf_hist) for the forced threshold's child sums; the engine
    # gates eligibility.
    if cfg.n_forced <= 0:
        forced = None
    if forced is not None:
        f_parent, f_is_left, f_feat, f_tbin, f_is_cat, f_bitset = forced
        M_f = cfg.n_forced

    # ---- root ----------------------------------------------------------
    leaf_id0 = jnp.zeros(1 if defer_full else n_rows, dtype=i32)
    leaf_id0_c = jnp.zeros(n_rows_c, dtype=i32)
    if use_part:
        # initial layout: every histogram-source row belongs to the
        # root, one contiguous span covering the whole buffer
        part_bins0 = h_bins_t if part_fm else h_bins
        part_vals0 = h_vals_t if part_fm else h_vals
        part_leaf0 = jnp.zeros(n_h, dtype=i32)
        part_off0 = jnp.zeros(L + 1, dtype=i32)
        part_cnt0 = jnp.zeros(L + 1, dtype=i32).at[0].set(n_h)
    else:
        part_bins0 = jnp.zeros((1, 1), jnp.int8)
        part_vals0 = jnp.zeros((1, 1), jnp.float32)
        part_leaf0 = jnp.zeros(1, dtype=i32)
        part_off0 = jnp.zeros(1, dtype=i32)
        part_cnt0 = jnp.zeros(1, dtype=i32)
    root_small = jnp.concatenate(
        [jnp.zeros(1, i32), jnp.full(Kb - 1, -1, i32)]) if Kb > 1 \
        else jnp.zeros(1, i32)
    # every row of the histogram source starts in leaf 0 (the table's own
    # ids are a placeholder where the loop does not route them)
    root_hist = hist_multi(leaf_id0_c if compact is not None
                           else part_leaf0 if use_part
                           else leaf_id0, root_small)[0]
    root_sums = jnp.sum(h_vals, axis=0)
    if cfg.axis_name:
        root_sums = jax.lax.psum(root_sums, cfg.axis_name)
    if chan_scale is not None:
        root_sums = root_sums * chan_scale
    if cfg.has_interaction:
        # features in no constraint group can never be used
        root_allow = jnp.any(groups, axis=0) & allowed_feature  # [F_meta]
    else:
        root_allow = None
    root_allows = (root_allow[None] if root_allow is not None else None)
    if cfg.feature_fraction_bynode < 1.0 and node_key is not None:
        base = (root_allows if root_allows is not None
                else jnp.broadcast_to(allowed_feature, (1, F_meta)))
        root_allows = bynode_mask(base, L + 7)
    root_parent_out = (leaf_out(root_sums)[None]
                       if cfg.path_smooth > 0.0 else None)
    root_best = jax.tree.map(
        lambda a: a[0], search_best(
            root_hist[None], root_sums[None], allows=root_allows,
            parent_outs=root_parent_out, round_tag=L + 7,
            depths=(jnp.zeros(1, i32)
                    if cfg.monotone_penalty > 0.0 else None),
            pen2=(lazy_pen2(jnp.zeros(1, i32), leaf_id0)
                  if lazy is not None else None)))

    def set0(arr, value):
        return arr.at[0].set(value)

    with obs.scope("grower/leaf_values"):
        state = GrowState(
            split_idx=jnp.array(0, i32),
            num_leaves=jnp.array(1, i32),
            # pending forced entries must enter the loop even when the free
            # root search found nothing (forced splits bypass gain checks)
            has_split=(jnp.array(True) if forced is not None
                       else jnp.isfinite(root_best["gain"])),
            leaf_id=leaf_id0,
            leaf_hist=set0(jnp.zeros((L + 1,) + root_hist.shape,
                                     jnp.float32), root_hist),
            leaf_sums=set0(jnp.zeros((L + 1, 3), jnp.float32), root_sums),
            leaf_depth=jnp.zeros(L + 1, i32),
            best_gain=set0(jnp.full(L + 1, NEG_INF), root_best["gain"]),
            best_feature=set0(jnp.zeros(L + 1, i32), root_best["feature"]),
            best_threshold=set0(jnp.zeros(L + 1, i32),
                                root_best["threshold_bin"]),
            best_default_left=set0(jnp.zeros(L + 1, jnp.bool_),
                                   root_best["default_left"]),
            best_lr_sums=set0(jnp.zeros((L + 1, 2, 3), jnp.float32),
                              jnp.stack([root_best["left_sums"],
                                         root_best["right_sums"]])),
            best_is_cat=set0(jnp.zeros(L + 1, jnp.bool_),
                             root_best["is_cat"]),
            best_cat_bitset=set0(jnp.zeros((L + 1, W), jnp.uint32),
                                 root_best["cat_bitset"]),
            split_feature=jnp.zeros(L, i32),
            threshold_bin=jnp.zeros(L, i32),
            default_left=jnp.zeros(L, jnp.bool_),
            node_is_cat=jnp.zeros(L, jnp.bool_),
            node_cat_bitset=jnp.zeros((L, W), jnp.uint32),
            left_child=jnp.zeros(L, i32),
            right_child=jnp.zeros(L, i32),
            node_vcg=jnp.zeros((L, 3), jnp.float32),
            leaf_vcw=set0(jnp.zeros((L + 1, 3), jnp.float32),
                          jnp.stack([leaf_out(root_sums), root_sums[2],
                                     root_sums[1]])),
            leaf_parent=jnp.full(L + 1, -1, i32),
            leaf_is_left=jnp.zeros(L + 1, jnp.bool_),
            leaf_bounds=jnp.stack(
                [jnp.full(L + 1, -jnp.inf, jnp.float32),
                 jnp.full(L + 1, jnp.inf, jnp.float32)], axis=1),
            leaf_used=jnp.zeros(
                (L + 1, F_meta if (cfg.has_interaction or cfg.has_cegb_lazy)
                 else 1), jnp.bool_),
            mono_left=jnp.zeros(
                (L, L + 1) if use_mono_inter else (1, 1), jnp.bool_),
            mono_right=jnp.zeros(
                (L, L + 1) if use_mono_inter else (1, 1), jnp.bool_),
            leaf_flo=(jnp.zeros((L + 1, F_meta), i32) if use_mono_adv
                      else jnp.zeros((1, 1), i32)),
            leaf_fhi=(jnp.broadcast_to(feat_num_bin[None, :],
                                       (L + 1, F_meta)).astype(i32)
                      if use_mono_adv else jnp.zeros((1, 1), i32)),
            leaf_id_c=(leaf_id0_c if compact is not None
                       else jnp.zeros(1, i32)),
            node_leaf=jnp.zeros(L if defer_full else 1, i32),
            forced_target=(jnp.where(f_parent < 0, 0, -1).astype(i32)
                           if forced is not None else jnp.zeros(1, i32)),
            part_bins=part_bins0,
            part_vals=part_vals0,
            part_leaf=part_leaf0,
            part_off=part_off0,
            part_cnt=part_cnt0,
            # the root histogram above scanned the whole source once
            # (float32: n x rounds x shards overflows int32 at prod scale)
            rows_scanned=jnp.asarray(n_h, jnp.float32),
            hist_calls=jnp.array(1, i32),
            hist_slots_filled=jnp.array(1, i32),
        )

    node_trash = L - 1  # real nodes occupy 0..L-2
    leaf_trash = L

    def cond(s: GrowState):
        return (s.split_idx < L - 1) & s.has_split

    def body(s: GrowState) -> GrowState:
        gains = _masked_gains(s.best_gain, s.leaf_depth, s.num_leaves,
                              cfg.max_depth)
        if forced is not None:
            # ---- forced rounds: every READY entry this round ---------
            tgt = s.forced_target                          # [M]
            ready = tgt >= 0
            in_forced = jnp.any(ready | (tgt == -1))
            tgt_cl = jnp.clip(tgt, 0, L)
            is_forced_leaf = jnp.zeros(L + 1, jnp.bool_).at[
                jnp.where(ready, tgt_cl, L)].set(True).at[L].set(False)
            # while entries remain, ONLY forced targets may split
            # (reference applies all forced splits before free growth)
            gains = jnp.where(
                in_forced,
                jnp.where(is_forced_leaf, jnp.float32(3e38), NEG_INF),
                gains)
        top_gain, top_leaf = jax.lax.top_k(gains, Kb)
        remaining = (L - 1) - s.split_idx
        valid = jnp.isfinite(top_gain) \
            & (jnp.arange(Kb, dtype=i32) < remaining)
        if forced is not None:
            from ..ops.split import leaf_gain as _lg
            # match each batch lane to its forced entry (targets are
            # unique per leaf slot, so at most one entry per lane)
            lane_match = ((top_leaf[:, None] == tgt[None, :])
                          & ready[None, :])                  # [Kb, M]
            flane = jnp.any(lane_match, axis=1) & in_forced  # [Kb]

            def esel(arr):
                return jnp.sum(
                    jnp.where(lane_match, arr[None, :].astype(i32), 0),
                    axis=1)

            ff_k = esel(f_feat)                              # [Kb]
            ftb_k = esel(f_tbin)
            fcat_k = jnp.any(lane_match & f_is_cat[None, :], axis=1)
            fbs_k = jnp.sum(
                jnp.where(lane_match[:, :, None],
                          f_bitset[None, :, :],
                          jnp.uint32(0)), axis=1)            # [Kb, W]
            # per-lane child sums from the pool histogram: gather the
            # target leaves' histograms with the one-hot matmul trick
            oh_tf = (top_leaf[:, None]
                     == jnp.arange(L + 1, dtype=i32)[None, :]
                     ).astype(jnp.float32)
            fhist = jax.lax.dot_general(
                oh_tf, s.leaf_hist.reshape(L + 1, -1),
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST).reshape(
                    Kb, s.leaf_hist.shape[1], B, 3)
            oh_ff = (ff_k[:, None]
                     == jnp.arange(F_meta, dtype=i32)[None, :])
            col_f = jnp.sum(
                jnp.where(oh_ff[:, :, None, None], fhist, 0.0),
                axis=1)                                       # [Kb,B,3]
            bidx_f = jnp.arange(B, dtype=i32)[None, :]
            nanb_f = (feat_has_nan[ff_k][:, None]
                      & (bidx_f == feat_num_bin[ff_k][:, None] - 1))
            num_lm = (bidx_f <= ftb_k[:, None]) & ~nanb_f
            word_k = jnp.take_along_axis(
                fbs_k, (bidx_f >> 5).astype(i32), axis=1)
            cat_lm = ((word_k >> (bidx_f & 31).astype(jnp.uint32))
                      & jnp.uint32(1)) > 0
            lm_f = jnp.where(fcat_k[:, None], cat_lm, num_lm) \
                & (bidx_f < feat_num_bin[ff_k][:, None])
            f_lsums = jnp.sum(col_f * lm_f[:, :, None], axis=1)
            f_psums2 = s.leaf_sums[jnp.clip(top_leaf, 0, L)]
            f_rsums = f_psums2 - f_lsums
            # forced splits bypass gain/min_data checks, but both
            # children must receive rows (and respect max_depth);
            # otherwise the entry and its subtree are skipped
            applied_k = (flane & (f_lsums[:, 2] > 0)
                         & (f_rsums[:, 2] > 0))
            if cfg.max_depth > 0:
                applied_k = applied_k \
                    & (s.leaf_depth[jnp.clip(top_leaf, 0, L)]
                       < cfg.max_depth)
            valid = valid & (~flane | applied_k)
        nv = jnp.sum(valid).astype(i32)
        rank = jnp.cumsum(valid.astype(i32)) - 1
        node_ids = jnp.where(valid, s.split_idx + rank, node_trash)
        new_ids = jnp.where(valid, s.num_leaves + rank, leaf_trash)
        tl_safe = jnp.where(valid, top_leaf, leaf_trash)

        # ---- partition: apply all selected splits in one row pass ------
        # TPU note: per-row gathers into tiny tables (feat[lf], thr[lf],
        # ...) run on the scalar unit at ~100M elem/s — 5 of them cost
        # ~45ms/round at 1M rows. Instead build the [n, Kb] membership
        # mask of the selected leaves once and contract it against the
        # per-leaf attributes packed as a [Kb, 6] matrix: one small MXU
        # matmul replaces every per-row lookup.
        lf = s.leaf_id
        # per-lane split attributes; a forced round substitutes the
        # forced entry's feature/threshold for lane 0
        feat_sel = s.best_feature[tl_safe]
        thr_sel = s.best_threshold[tl_safe]
        dl_sel = s.best_default_left[tl_safe]
        gain_rec = top_gain
        lr_sel = s.best_lr_sums[tl_safe]           # [Kb, 2, 3]
        lsums_sel = lr_sel[:, 0]                   # [Kb, 3]
        rsums_sel = lr_sel[:, 1]
        cat_sel = (s.best_is_cat[tl_safe] if cfg.has_categorical
                   else None)
        bs_sel = (s.best_cat_bitset[tl_safe] if cfg.has_categorical
                  else None)
        if forced is not None:
            # substitute the forced entries' attributes on their lanes
            # (analysis arrays computed above, before `valid`)
            feat_sel = jnp.where(flane, ff_k, feat_sel)
            thr_sel = jnp.where(flane, ftb_k, thr_sel)
            dl_sel = jnp.where(flane, False, dl_sel)
            lsums_sel = jnp.where(flane[:, None], f_lsums, lsums_sel)
            rsums_sel = jnp.where(flane[:, None], f_rsums, rsums_sel)
            g_forced = (_lg(f_lsums[:, 0], f_lsums[:, 1], cfg.lambda_l1,
                            cfg.lambda_l2)
                        + _lg(f_rsums[:, 0], f_rsums[:, 1],
                              cfg.lambda_l1, cfg.lambda_l2)
                        - _lg(f_psums2[:, 0], f_psums2[:, 1],
                              cfg.lambda_l1, cfg.lambda_l2))
            gain_rec = jnp.where(flane, g_forced, gain_rec)
            if cfg.has_categorical:
                cat_sel = jnp.where(flane, fcat_k, cat_sel)
                bs_sel = jnp.where(flane[:, None], fbs_k, bs_sel)
        attr_cols = [feat_sel.astype(jnp.float32),
                     thr_sel.astype(jnp.float32),
                     dl_sel.astype(jnp.float32),
                     new_ids.astype(jnp.float32),
                     feat_num_bin[feat_sel].astype(jnp.float32),
                     feat_has_nan[feat_sel].astype(jnp.float32)]
        if cfg.has_categorical:
            # bitset words split into 16-bit halves: exact in float32,
            # so the same masked matmul carries them per row
            attr_cols.append(cat_sel.astype(jnp.float32))
            attr_cols.extend(jnp.moveaxis(
                (bs_sel & jnp.uint32(0xFFFF)).astype(jnp.float32), 1, 0))
            attr_cols.extend(jnp.moveaxis(
                (bs_sel >> jnp.uint32(16)).astype(jnp.float32), 1, 0))
        if cfg.has_bundles:
            # EFB: the row pass reads the PHYSICAL bundle column and
            # recovers the logical bin via the member's offset/default
            attr_cols.extend([
                bphys_col[feat_sel].astype(jnp.float32),
                bstart[feat_sel].astype(jnp.float32),
                bbundled[feat_sel].astype(jnp.float32),
                bdef[feat_sel].astype(jnp.float32)])
        packed = jnp.stack(attr_cols, axis=1)

        @obs.scope("grower/partition")
        def apply_splits(lf_vec, bins_mat, fm=False):
            """Route one row set through this round's selected splits
            (shared by the full partition, the compacted buffer's
            partition under hist_compact, and the leaf-ordered row
            partition's per-position ids under cfg.partition). With
            ``fm`` the source is the FEATURE-MAJOR ``[F, n]`` int8
            matrix (wraparound storage) — the one-hot column read
            reduces over the leading axis, so no transpose is ever
            materialized."""
            mk = (lf_vec[:, None] == tl_safe[None, :]) & valid[None, :]
            sel_rows = jnp.any(mk, axis=1)
            row_attr = jax.lax.dot_general(
                mk.astype(jnp.float32), packed,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)  # [n, 6(+1+2W)]
            feat_r = row_attr[:, 0].astype(i32)
            thr_r = row_attr[:, 1].astype(i32)
            dl_r = row_attr[:, 2] > 0.5
            new_leaf_r = row_attr[:, 3].astype(i32)
            nb_r = row_attr[:, 4].astype(i32)
            hn_r = row_attr[:, 5] > 0.5
            # bins[row, feat_r] without a per-row gather: one-hot over
            # F, fused compare-select-reduce on the VPU (exact in
            # int32). Under feature-parallel, only the winning
            # feature's OWNER has the column — its contribution is
            # broadcast by the psum (every other device contributes
            # zeros), the TPU-native replacement for the reference's
            # full-data local split.
            if cfg.has_bundles:
                bidx = 6 + ((1 + 2 * W) if cfg.has_categorical else 0)
                pcol_r = row_attr[:, bidx].astype(i32)
                start_r = row_attr[:, bidx + 1].astype(i32)
                bundled_r = row_attr[:, bidx + 2] > 0.5
                def_r = row_attr[:, bidx + 3].astype(i32)
            else:
                pcol_r = feat_r
            col_ids = jnp.arange(F, dtype=i32)
            if mode_feature:
                col_ids = col_ids + off
            if fm:
                # int8 wraparound storage -> restore uint8 bin values
                oh_f = pcol_r[None, :] == col_ids[:, None]     # [F, n]
                col = jnp.sum(
                    jnp.where(oh_f, bins_mat.astype(i32) & 0xFF, 0),
                    axis=0)
            else:
                oh_f = pcol_r[:, None] == col_ids[None, :]
                col = jnp.sum(jnp.where(oh_f, bins_mat.astype(i32), 0),
                              axis=1)
            if mode_feature:
                col = jax.lax.psum(col, cfg.feature_axis)
            if cfg.has_bundles:
                # invert the bundle relabeling: phys v -> logical bin
                # (the member's default bin was skipped in the
                # enumeration)
                idx = col - start_r
                in_r = (idx >= 0) & (idx <= nb_r - 2)
                b_log = idx + (idx >= def_r).astype(i32)
                col = jnp.where(bundled_r,
                                jnp.where(in_r, b_log, def_r), col)
            is_missing = hn_r & (col == nb_r - 1)
            goes_left = jnp.where(is_missing, dl_r, col <= thr_r)
            if cfg.has_categorical:
                is_cat_r = row_attr[:, 6] > 0.5
                oh_w = ((col >> 5)[:, None]
                        == jnp.arange(W, dtype=i32)[None, :])  # [n, W]
                lo16 = jnp.sum(jnp.where(oh_w, row_attr[:, 7:7 + W],
                                         0.0), axis=1).astype(jnp.uint32)
                hi16 = jnp.sum(
                    jnp.where(oh_w, row_attr[:, 7 + W:7 + 2 * W], 0.0),
                    axis=1).astype(jnp.uint32)
                word = lo16 | (hi16 << jnp.uint32(16))
                cat_left = ((word >> (col & 31).astype(jnp.uint32))
                            & jnp.uint32(1)) > 0
                goes_left = jnp.where(is_cat_r, cat_left, goes_left)
            return jnp.where(sel_rows & ~goes_left, new_leaf_r, lf_vec)

        leaf_id = lf if defer_full else apply_splits(lf, bins)
        # under the leaf-ordered partition the compact-buffer masked ids
        # are dead (histograms read part_leaf instead) — skip the pass
        leaf_id_c = (apply_splits(s.leaf_id_c, bins_c)
                     if compact is not None and not use_part
                     else s.leaf_id_c)
        hist_lid = leaf_id_c if compact is not None else leaf_id

        # ---- leaf-ordered repartition (cfg.partition) ------------------
        # one stable front/back move per round: rows that routed to a
        # RIGHT child pack (stably) to the back of the buffer, everything
        # else packs to the front — per-leaf contiguity and within-leaf
        # source order both survive, and the (offset, count) tables
        # update from the same prefix sums (ops/partition.py).
        with obs.scope("grower/partition"):
            if use_part:
                part_leaf_mv = apply_splits(s.part_leaf, s.part_bins,
                                            fm=part_fm)
                moved = part_leaf_mv != s.part_leaf
                dest, n_front, cum = part_ops.plan_split_move(moved)
                p_off, p_cnt = part_ops.update_tables(
                    s.part_off, s.part_cnt, cum, n_front, tl_safe, new_ids,
                    valid)
                if part_fm:
                    # TPU: two compact_rows passes (front keys, back keys);
                    # the int32 leaf ids ride as one extra float32 value
                    # channel (exact via the kernel's bf16x3 split)
                    pv_aug = jnp.concatenate(
                        [s.part_vals,
                         part_leaf_mv[None].astype(jnp.float32)])
                    p_bins, pv2 = part_ops.move_cols_tpu(
                        s.part_bins, pv_aug, moved, n_front, cfg.part_rpb)
                    p_vals = pv2[:-1]
                    p_leaf = pv2[-1].astype(i32)
                else:
                    p_bins, p_vals, p_leaf = part_ops.move_rows_xla(
                        [s.part_bins, s.part_vals, part_leaf_mv], dest)
            else:
                p_bins, p_vals, p_leaf = (s.part_bins, s.part_vals,
                                          s.part_leaf)
                p_off, p_cnt = s.part_off, s.part_cnt

        def span_tables(ids):
            """Per-elected-child (offset, count) rows for slice_spans
            (-1 lanes get count 0, so they match nothing)."""
            safe = jnp.clip(ids, 0, L)
            return p_off[safe], jnp.where(ids >= 0, p_cnt[safe], 0)

        lsums = lsums_sel                      # [Kb, 3]
        rsums = rsums_sel
        psums = s.leaf_sums[tl_safe]
        # ---- smaller-child histogram + sibling subtraction ---------
        left_smaller = lsums[:, 2] <= rsums[:, 2]
        small_ids = jnp.where(
            valid, jnp.where(left_smaller, top_leaf, new_ids),
            -1).astype(i32)
        if use_part:
            # partitioned: scan only the Kb smaller children's spans
            offs_k, cnts_k = span_tables(small_ids)
            raw_s, span_rows = span_hist(p_bins, p_vals, p_leaf,
                                         small_ids, offs_k, cnts_k)
            hist_small = hist_reduce(raw_s)      # [Kb, F, B, 3]
        else:
            hist_small = hist_multi(hist_lid, small_ids)
            span_rows = jnp.asarray(n_h, jnp.float32)
        with obs.scope("grower/histogram"):
            # TPU note: the [L+1, F, B, 3] pool gather/scatter by leaf id
            # lowers to serialized dynamic slices (~13 ms/round at
            # nl=127); both become one-hot matmuls on the MXU instead.
            # 0/1 weights with disjoint rows keep values exact; the
            # trash lane L may accumulate a SUM of invalid lanes rather
            # than the last write, but slot L is never an active leaf.
            F_h = s.leaf_hist.shape[1]
            pool_flat = s.leaf_hist.reshape(L + 1, -1)
            leaf_ids_ax = jnp.arange(L + 1, dtype=i32)
            oh_parent = (tl_safe[:, None]
                         == leaf_ids_ax[None, :]).astype(jnp.float32)
            parent_hist = jax.lax.dot_general(
                oh_parent, pool_flat,
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST).reshape(
                    Kb, F_h, B, 3)
            hist_large = parent_hist - hist_small
            ls4 = left_smaller[:, None, None, None]
            left_hist = jnp.where(ls4, hist_small, hist_large)
            right_hist = jnp.where(ls4, hist_large, hist_small)
            oh_new = (new_ids[:, None]
                      == leaf_ids_ax[None, :]).astype(jnp.float32)
            upd = jax.lax.dot_general(
                jnp.concatenate([oh_parent, oh_new]).T,
                jnp.concatenate([left_hist, right_hist]).reshape(
                    2 * Kb, -1),
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)
            written = (jnp.sum(oh_parent, axis=0)
                       + jnp.sum(oh_new, axis=0)) > 0       # [L+1]
            leaf_hist = jnp.where(written[:, None], upd,
                                  pool_flat).reshape(s.leaf_hist.shape)

        depth2 = s.leaf_depth[tl_safe] + 1
        lvals = leaf_out(lsums)
        rvals = leaf_out(rsums)
        if cfg.has_categorical:
            # children of a categorical split are regularized with
            # lambda_l2 + cat_l2, matching the gain computed in
            # ops/split.py (reference: feature_histogram.hpp categorical
            # CalculateSplittedLeafOutput uses the cat-augmented l2)
            @obs.scope("grower/leaf_values")
            def leaf_out_cat(sums):
                return calc_leaf_output(
                    sums[..., 0], sums[..., 1], cfg.lambda_l1,
                    cfg.lambda_l2 + cfg.cat_l2, cfg.max_delta_step)
            cat_split = cat_sel
            lvals = jnp.where(cat_split, leaf_out_cat(lsums), lvals)
            rvals = jnp.where(cat_split, leaf_out_cat(rsums), rvals)

        if cfg.path_smooth > 0.0:
            # children shrink toward the SPLIT leaf's stored output
            # (feature_histogram.hpp passes tree->LeafOutput(leaf) as
            # parent_output); smoothing applies before constraint clips
            pvals = s.leaf_vcw[tl_safe, 0]
            lvals = smooth_output(lvals, lsums[:, 2], pvals,
                                  cfg.path_smooth)
            rvals = smooth_output(rvals, rsums[:, 2], pvals,
                                  cfg.path_smooth)

        # ---- constraint propagation (monotone_constraints.hpp) ---------
        if cfg.has_monotone:
            m_k = mono[feat_sel].astype(jnp.float32)
            if use_mono_inter:
                # intermediate mode: bounds recomputed each round from
                # the CURRENT leaf outputs of every constrained node's
                # opposing subtree (IntermediateLeafConstraints'
                # semantics) — masked min/max over the [L, L+1]
                # membership matrices instead of recursive tree walks.
                # Cached best splits from earlier rounds may predate a
                # bound tightening; the clip below re-applies the
                # CURRENT bound at split time, keeping every realized
                # output sound by induction.
                leaf_ax = jnp.arange(L + 1, dtype=i32)
                node_ok = jnp.arange(L, dtype=i32) < s.split_idx
                node_m = jnp.where(node_ok,
                                   mono[s.split_feature], 0)     # [L]
                act = leaf_ax < s.num_leaves                     # [L+1]
                vals_c = s.leaf_vcw[:, 0]
                big = jnp.float32(jnp.inf)
                if use_mono_adv:
                    # ADVANCED (AdvancedLeafConstraints): each node
                    # binds only the leaves of either subtree that are
                    # ADJACENT to its boundary in its split feature
                    # (leaf bin range touching the threshold); shielded
                    # leaves are ordered transitively through the
                    # adjacent strip chain, so their bounds — and the
                    # strip aggregates below — are strictly looser than
                    # intermediate's whole-subtree min/max.
                    oh_nf = (s.split_feature[:, None]
                             == jnp.arange(F_meta, dtype=i32)[None, :]
                             ).astype(jnp.float32)       # [L, F_meta]
                    lo_f = jax.lax.dot_general(
                        oh_nf, s.leaf_flo.astype(jnp.float32),
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST)  # [L, L+1]
                    hi_f = jax.lax.dot_general(
                        oh_nf, s.leaf_fhi.astype(jnp.float32),
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST)
                    tjf = s.threshold_bin.astype(jnp.float32)[:, None]
                    ncat = s.node_is_cat[:, None]        # [L, 1]
                    ml_eff = s.mono_left & (ncat | (hi_f == tjf))
                    mr_eff = s.mono_right & (ncat | (lo_f == tjf + 1.0))
                else:
                    ml_eff, mr_eff = s.mono_left, s.mono_right
                inf_r = jnp.where(mr_eff & act[None, :],
                                  vals_c[None, :], big)
                inf_l = jnp.where(ml_eff & act[None, :],
                                  vals_c[None, :], big)
                rmin = jnp.min(inf_r, axis=1)                    # [L]
                lmin = jnp.min(inf_l, axis=1)
                rmax = jnp.max(jnp.where(mr_eff & act[None, :],
                                         vals_c[None, :], -big), axis=1)
                lmax = jnp.max(jnp.where(ml_eff & act[None, :],
                                         vals_c[None, :], -big), axis=1)
                in_l = ml_eff[:, tl_safe]                        # [L, Kb]
                in_r = mr_eff[:, tl_safe]
                # batch race guard: when THIS round splits leaves on
                # BOTH sides of a constrained node, each side would use
                # the other's pre-round value and their children could
                # cross; those nodes fall back to a shared midpoint cut
                # (sound for concurrent updates), everything else keeps
                # the looser one-sided bound
                both = (jnp.any(in_l & valid[None, :], axis=1)
                        & jnp.any(in_r & valid[None, :], axis=1))  # [L]
                c_inc = jnp.where(both, 0.5 * (lmax + rmin), 0.0)
                c_dec = jnp.where(both, 0.5 * (lmin + rmax), 0.0)
                nup_l = jnp.where(both, c_inc, rmin)  # inc, leaf on left
                nlo_r = jnp.where(both, c_inc, lmax)  # inc, leaf on right
                nup_r = jnp.where(both, c_dec, lmin)  # dec, leaf on right
                nlo_l = jnp.where(both, c_dec, rmax)  # dec, leaf on left
                pos = (node_m > 0)[:, None]
                neg = (node_m < 0)[:, None]
                phi = jnp.min(jnp.where(
                    pos & in_l, nup_l[:, None],
                    jnp.where(neg & in_r, nup_r[:, None], big)), axis=0)
                plo = jnp.max(jnp.where(
                    pos & in_r, nlo_r[:, None],
                    jnp.where(neg & in_l, nlo_l[:, None], -big)), axis=0)
            else:
                plo = s.leaf_bounds[tl_safe, 0]
                phi = s.leaf_bounds[tl_safe, 1]
            lvals = jnp.clip(lvals, plo, phi)
            rvals = jnp.clip(rvals, plo, phi)
            if use_mono_inter:
                # children are bounded by the SIBLING's realized output
                # (looser than basic's midpoint; later tightenings are
                # picked up by the per-round recompute above)
                bound_l, bound_r = rvals, lvals
            else:
                # basic mode: the mid-point of the realized outputs
                # becomes the shared bound of the two children, so any
                # LATER split below either child cannot cross it
                bound_l = bound_r = 0.5 * (lvals + rvals)
            lo_l = jnp.where(m_k < 0, jnp.maximum(plo, bound_l), plo)
            hi_l = jnp.where(m_k > 0, jnp.minimum(phi, bound_l), phi)
            lo_r = jnp.where(m_k > 0, jnp.maximum(plo, bound_r), plo)
            hi_r = jnp.where(m_k < 0, jnp.minimum(phi, bound_r), phi)
            child_lower = jnp.concatenate([lo_l, lo_r])
            child_upper = jnp.concatenate([hi_l, hi_r])
        else:
            child_lower = child_upper = None
        if cfg.has_interaction or cfg.has_cegb_lazy:
            fk = feat_sel
            # only lanes that actually split extend their path set
            used_k = s.leaf_used[tl_safe] \
                | ((fk[:, None] == jnp.arange(F_meta, dtype=i32)[None, :])
                   & valid[:, None])
            child_used = jnp.concatenate([used_k, used_k])
        else:
            child_used = None
        if cfg.has_interaction:
            # a group is usable iff it contains EVERY feature on the path
            viol = jnp.any(used_k[:, None, :] & ~groups[None],
                           axis=2)                            # [Kb, G]
            allow_k = jnp.any(groups[None] & ~viol[:, :, None],
                              axis=1) & allowed_feature[None]  # [Kb, F]
            child_allow = jnp.concatenate([allow_k, allow_k])
        else:
            child_allow = None
        if cfg.feature_fraction_bynode < 1.0 and node_key is not None:
            base = (child_allow if child_allow is not None
                    else jnp.broadcast_to(allowed_feature,
                                          (2 * Kb, F_meta)))
            child_allow = bynode_mask(base, s.split_idx)

        # ---- intermediate-mode membership updates ----------------------
        if use_mono_inter:
            # children inherit the split leaf's subtree memberships
            # (column copy), then register under the new node
            ml = s.mono_left.at[:, new_ids].set(s.mono_left[:, tl_safe])
            mr = s.mono_right.at[:, new_ids].set(
                s.mono_right[:, tl_safe])
            ml = ml.at[node_ids, tl_safe].set(True)
            mr = mr.at[node_ids, new_ids].set(True)
        else:
            ml, mr = s.mono_left, s.mono_right
        ids2 = jnp.concatenate([tl_safe, new_ids])
        if use_mono_adv:
            # per-leaf feature bin ranges: children inherit the split
            # leaf's ranges; a NUMERICAL split narrows the split
            # feature's range at the threshold (categorical splits
            # leave ranges whole — their nodes bind whole subtrees)
            flo_p = s.leaf_flo[tl_safe]                  # [Kb, F_meta]
            fhi_p = s.leaf_fhi[tl_safe]
            oh_sf = (feat_sel[:, None]
                     == jnp.arange(F_meta, dtype=i32)[None, :])
            upd = oh_sf & valid[:, None]
            if cfg.has_categorical:
                upd = upd & ~cat_sel[:, None]
            fhi_left = jnp.where(upd, thr_sel[:, None], fhi_p)
            flo_right = jnp.where(upd, thr_sel[:, None] + 1, flo_p)
            leaf_flo2 = s.leaf_flo.at[ids2].set(
                jnp.concatenate([flo_p, flo_right]))
            leaf_fhi2 = s.leaf_fhi.at[ids2].set(
                jnp.concatenate([fhi_left, fhi_p]))
        else:
            leaf_flo2, leaf_fhi2 = s.leaf_flo, s.leaf_fhi

        # ---- best splits for all 2*Kb children -------------------------
        child_hists = jnp.concatenate([left_hist, right_hist])
        child_sums = jnp.concatenate([lsums, rsums])
        bests = search_best(child_hists, child_sums,
                            child_lower, child_upper, child_allow,
                            parent_outs=(jnp.concatenate([lvals, rvals])
                                         if cfg.path_smooth > 0.0
                                         else None),
                            round_tag=s.split_idx,
                            depths=(jnp.concatenate([depth2, depth2])
                                    if cfg.monotone_penalty > 0.0
                                    else None),
                            pen2=(lazy_pen2(ids2, leaf_id, child_used)
                                  if lazy is not None else None))

        # ---- tree wiring -----------------------------------------------
        with obs.scope("grower/leaf_values"):
            lc = s.left_child.at[node_ids].set(-top_leaf - 1)
            rc = s.right_child.at[node_ids].set(-new_ids - 1)
            p = s.leaf_parent[tl_safe]
            was_left = s.leaf_is_left[tl_safe]
            fix_l = jnp.where(valid & (p >= 0) & was_left, p, node_trash)
            fix_r = jnp.where(valid & (p >= 0) & ~was_left, p, node_trash)
            # trash-lane writes land in the unused node slot L-1
            lc = lc.at[fix_l].set(jnp.where(fix_l == node_trash, lc[fix_l],
                                            node_ids))
            rc = rc.at[fix_r].set(jnp.where(fix_r == node_trash, rc[fix_r],
                                            node_ids))

        # ---- forced-entry state resolution -----------------------------
        if forced is not None:
            sel_applied = lane_match & applied_k[:, None]    # [Kb, M]
            applied_entry = jnp.any(sel_applied, axis=0)     # [M]
            attempted = jnp.any(lane_match & flane[:, None], axis=0)
            skipped = attempted & ~applied_entry
            fp_c = jnp.clip(f_parent, 0, M_f - 1)
            # children resolve against the lane where their parent
            # applied: left child keeps the parent's leaf slot, right
            # child takes the new leaf id minted in that lane
            pm = sel_applied[:, fp_c]                        # [Kb, M]
            child_tgt = jnp.where(
                f_is_left,
                jnp.sum(jnp.where(pm, tl_safe[:, None], 0), axis=0),
                jnp.sum(jnp.where(pm, new_ids[:, None], 0), axis=0))
            resolved_now = jnp.any(pm, axis=0) & (f_parent >= 0)
            parent_dead = (f_parent >= 0) & (
                skipped[fp_c] | (tgt[fp_c] == -2))
            forced_tgt_next = jnp.where(
                applied_entry, -3,
                jnp.where(skipped, -2,
                          jnp.where(tgt == -1,
                                    jnp.where(resolved_now, child_tgt,
                                              jnp.where(parent_dead,
                                                        -2, -1)),
                                    tgt))).astype(i32)

        with obs.scope("grower/leaf_values"):
            new = GrowState(
                split_idx=s.split_idx + nv,
                num_leaves=s.num_leaves + nv,
                has_split=jnp.array(True),
                leaf_id=leaf_id,
                leaf_hist=leaf_hist,
                leaf_sums=s.leaf_sums.at[ids2].set(child_sums),
                leaf_depth=s.leaf_depth.at[ids2].set(
                    jnp.concatenate([depth2, depth2])),
                best_gain=s.best_gain.at[ids2].set(bests["gain"]),
                best_feature=s.best_feature.at[ids2].set(bests["feature"]),
                best_threshold=s.best_threshold.at[ids2].set(
                    bests["threshold_bin"]),
                best_default_left=s.best_default_left.at[ids2].set(
                    bests["default_left"]),
                best_lr_sums=s.best_lr_sums.at[ids2].set(
                    jnp.stack([bests["left_sums"], bests["right_sums"]],
                              axis=1)),
                best_is_cat=s.best_is_cat.at[ids2].set(bests["is_cat"]),
                best_cat_bitset=s.best_cat_bitset.at[ids2].set(
                    bests["cat_bitset"]),
                split_feature=s.split_feature.at[node_ids].set(feat_sel),
                threshold_bin=s.threshold_bin.at[node_ids].set(thr_sel),
                default_left=s.default_left.at[node_ids].set(dl_sel),
                node_is_cat=s.node_is_cat.at[node_ids].set(
                    cat_sel if cfg.has_categorical
                    else s.best_is_cat[tl_safe]),
                node_cat_bitset=s.node_cat_bitset.at[node_ids].set(
                    bs_sel if cfg.has_categorical
                    else s.best_cat_bitset[tl_safe]),
                left_child=lc,
                right_child=rc,
                node_vcg=s.node_vcg.at[node_ids].set(jnp.stack(
                    [s.leaf_vcw[tl_safe, 0] if cfg.path_smooth > 0.0
                     else leaf_out(psums),
                     psums[:, 2], gain_rec], axis=1)),
                leaf_vcw=s.leaf_vcw.at[ids2].set(jnp.stack(
                    [jnp.concatenate([lvals, rvals]),
                     child_sums[:, 2], child_sums[:, 1]], axis=1)),
                leaf_parent=s.leaf_parent.at[ids2].set(
                    jnp.concatenate([node_ids, node_ids])),
                leaf_is_left=s.leaf_is_left.at[ids2].set(
                    jnp.concatenate([jnp.ones(Kb, jnp.bool_),
                                     jnp.zeros(Kb, jnp.bool_)])),
                leaf_bounds=(s.leaf_bounds.at[ids2].set(
                    jnp.stack([child_lower, child_upper], axis=1))
                    if cfg.has_monotone else s.leaf_bounds),
                leaf_used=(s.leaf_used.at[ids2].set(child_used)
                           if (cfg.has_interaction or cfg.has_cegb_lazy)
                           else s.leaf_used),
                mono_left=ml,
                mono_right=mr,
                leaf_flo=leaf_flo2,
                leaf_fhi=leaf_fhi2,
                leaf_id_c=leaf_id_c,
                node_leaf=(s.node_leaf.at[node_ids].set(tl_safe)
                           if defer_full else s.node_leaf),
                forced_target=(forced_tgt_next if forced is not None
                               else s.forced_target),
                part_bins=p_bins,
                part_vals=p_vals,
                part_leaf=p_leaf,
                part_off=p_off,
                part_cnt=p_cnt,
                rows_scanned=s.rows_scanned + span_rows,
                hist_calls=s.hist_calls + 1,
                hist_slots_filled=s.hist_slots_filled
                + jnp.sum(small_ids >= 0).astype(i32),
            )
        next_gains = _masked_gains(new.best_gain, new.leaf_depth,
                                   new.num_leaves, cfg.max_depth)
        keep_going = jnp.isfinite(jnp.max(next_gains)) & (nv > 0)
        if forced is not None:
            # forced rounds may split nothing (entries skipped at
            # runtime: empty child, depth cap). Growth must neither
            # terminate while entries remain NOR when the LAST entries
            # cancel in a zero-split round — free growth resumes next
            # round as long as any leaf still has finite gain.
            keep_going = (keep_going
                          | jnp.any((forced_tgt_next == -1)
                                    | (forced_tgt_next >= 0))
                          | (in_forced
                             & jnp.isfinite(jnp.max(next_gains))))
        return new._replace(has_split=keep_going)

    final = jax.lax.while_loop(cond, body, state)

    nn = max(L - 1, 1)
    if defer_full:
        # nodes are numbered in the order they were made and node j's
        # right child is leaf j + 1, so replaying nodes 0 .. num_leaves
        # - 2 in order gives every row the id the in-loop pass would
        # have: the decision is apply_splits' own, in integers
        with obs.scope("grower/partition"):
            nodes = route_nodes(
                final.num_leaves - 1, final.split_feature[:nn],
                final.threshold_bin[:nn], final.default_left[:nn],
                final.node_leaf[:nn], feat_num_bin, feat_has_nan,
                is_cat=(final.node_is_cat[:nn] if cfg.has_categorical
                        else None),
                cat_bitset=(final.node_cat_bitset[:nn]
                            if cfg.has_categorical else None))
            leaf_id_out = (route_rows(bins_t, nodes) if cfg.use_pallas
                           else route_rows_xla(bins, nodes))
    else:
        leaf_id_out = final.leaf_id
    # rows the row -> leaf passes were handed this tree (the table's and
    # the compact buffer's; the leaf-ordered partition's per-position
    # pass is the move's own): each loop trip's, and the one after it
    trips = (final.hist_calls - 1).astype(jnp.float32)
    rows_routed = trips * float(
        (0 if defer_full else n_rows)
        + (n_rows_c if compact is not None and not use_part else 0)
    ) + float(n_rows if defer_full else 0)
    if cfg.axis_name:
        rows_routed = jax.lax.psum(rows_routed, cfg.axis_name)
    # total rows the histogram scans touched this tree: the structural
    # "fewer rows" win of the partition path (masked = n per round);
    # summed over shards so every device reports the global figure
    rows_scanned = final.rows_scanned
    if cfg.axis_name:
        rows_scanned = jax.lax.psum(rows_scanned, cfg.axis_name)
    tree = {
        "num_leaves": final.num_leaves,
        "split_feature": final.split_feature[:nn],
        "threshold_bin": final.threshold_bin[:nn],
        "default_left": final.default_left[:nn],
        "left_child": final.left_child[:nn],
        "right_child": final.right_child[:nn],
        "split_gain": final.node_vcg[:nn, 2],
        "internal_value": final.node_vcg[:nn, 0],
        "internal_count": final.node_vcg[:nn, 1],
        "leaf_value": final.leaf_vcw[:L, 0],
        "leaf_count": final.leaf_vcw[:L, 1],
        "leaf_weight": final.leaf_vcw[:L, 2],
        "hist_rows": rows_scanned,
        # the grower's own work counts (boosting/gbdt.py feeds them to
        # the hist.* counters); every call has Kb slots
        "hist_calls": final.hist_calls,
        "hist_slots": Kb * final.hist_calls,
        "hist_slots_filled": final.hist_slots_filled,
        # and its row -> leaf work (the partition.* counters)
        "route_rows": rows_routed,
        "route_final": jnp.array(int(defer_full), i32),
    }
    if use_part:
        # the leaf-ordered partition's mover: one stable front/back move
        # of the whole histogram source a loop trip (two kernel passes
        # on the TPU); only emitted on that path, so every other
        # program is the one it was
        moves = trips
        if cfg.axis_name:
            moves = jax.lax.psum(moves, cfg.axis_name)
        tree["move_calls"] = moves
        tree["move_rows"] = moves * float(n_h)
    if cfg.has_categorical:
        # only emitted when categorical features exist, so downstream
        # traversal (tree_predict_binned) skips the bitset branch — and
        # its per-row gathers — on pure-numerical datasets
        tree["is_cat"] = final.node_is_cat[:nn]
        tree["cat_bitset"] = final.node_cat_bitset[:nn]
    if cfg.has_cegb_lazy:
        # per-leaf path-feature sets ([L, F]): the boosting engine
        # folds them into the per-row acquisition matrix device-side
        # (rows acquire a feature when a split on it is applied above
        # them — cost_effective_gradient_boosting.hpp)
        tree["leaf_used"] = final.leaf_used[:L]
    return tree, leaf_id_out
