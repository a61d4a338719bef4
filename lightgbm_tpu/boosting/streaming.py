"""Out-of-core (larger-than-HBM) boosting: host-resident bins, streamed
level sweeps — optionally SHARDED over a data mesh (beyond one host).

Closes the last scale-axis gap vs the reference (VERDICT r4 item 3):
upstream LightGBM trains any dataset that fits host RAM/disk — its
two-round loader + row-wise bin storage never require the binned matrix
on the accelerator (``src/io/dataset_loader.cpp``, SURVEY.md §2.1,
UNVERIFIED — empty mount). The resident engine here (`gbdt.GBDT`)
uploads the full binned matrix to HBM, capping trainable size at
~HBM/(F bytes-per-row). This module removes that cap for the configs
that need it, and with ``tree_learner=data`` removes the ONE-HOST cap
too: each rank streams only its own row shard's blocks and the
per-level histograms meet in a single collective.

Design (SURVEY.md §7.4 hard-part 4, "sharded binning on host, streamed
epochs"; §3.4 data-parallel learner for the sharded composition):

- The BINNED matrix (uint8/16, the big object) stays in host RAM; the
  native binner builds it at ~GB/s. Device-resident state is one row
  BLOCK at a time plus the accumulated `[K, F, B, 3]` histograms
  (~11 MB at K=128/F=28/B=256) — HBM use is O(block), not O(n).
- Trees grow LEVEL-WISE: one streamed pass over the blocks per level
  computes the histograms of every frontier leaf at once (the same
  multi-leaf one-hot-matmul histogram the resident engine uses), so a
  depth-d tree costs d+1 sweeps of PCIe traffic instead of the
  resident engine's zero. Best-first order inside a level is
  preserved by gain-ranking when the leaf budget runs out, but
  cross-level best-first interleaving is NOT — a documented
  divergence from the reference's queue (`serial_tree_learner.cpp`):
  per-sweep cost makes strict best-first (one sweep per leaf)
  ~num_leaves/depth times more expensive.
- SHARDED (``tree_learner=data``): the row range splits contiguously
  per rank (mesh device; on a multi-process gang each process streams
  only its own shard's blocks), every rank accumulates its local
  `[K, F, B, 3]` level histogram across its blocks exactly like the
  serial path, and then issues **ONE** ``psum`` (or ``psum_scatter``
  honoring ``tpu_hist_reduce``) of the ACCUMULATED histogram per tree
  level through the shared packed-int32 collective wire
  (learner/collective.py, the same wire the resident data-parallel
  learner reduces on) — never one collective per block. Split finding
  sees the global histogram, so every rank grows bit-identical trees;
  with exact (quantized-integer or small-scale bf16-rounded) histogram
  sums the trees are also bit-identical to single-shard streaming.
- Per-row state (score, leaf id) lives device-resident per block;
  gradients are recomputed on device per block from the streamed
  score (cheaper than streaming g/h separately).
- PIPELINED (``tpu_stream_overlap``, default on): the next block's
  upload stages on a worker thread while the device sweeps the
  current one, the per-level histogram collective dispatches without
  a blocking host sync, and the round-end score sweep drains behind
  the next round's first level sweep. Bit-identical on/off by
  construction — only where the host blocks moves — and checkpoint
  exports drain pending updates first (docs/perf.md
  "Communication/compute overlap").
- BAGGING / GOSS ride per-block row masks derived on device from a
  counter-based hash of each row's GLOBAL index — no mask storage, no
  host traffic, and the same row keeps the same draw no matter how
  the rows are cut into blocks or shards. GOSS thresholds come from a
  GLOBAL |g*h| order statistic via a small per-round collective (a
  65536-bucket float-bit histogram of the metric — the same
  small-collective pattern the serial learner's guard psum uses), so
  the kept set is shard-invariant; the selected count can exceed
  ``top_rate*n`` by the boundary bucket's population (<=0.4% relative
  metric granularity — a documented divergence from the resident
  engine's exact top-k).
- Quantized gradients (``use_quantized_grad``) are supported: integer
  level histograms make the accumulated sums EXACT at any scale (the
  bit-identical-across-shards guarantee) and engage the packed int32
  wire (2/3 payload) on the per-level collective.

Durable checkpoints / resume: the engine exports and imports complete
training state through the recovery subsystem (export_train_state /
import_train_state below) — a streamed, even sharded, run interrupted
mid-training resumes BIT-EXACT from its newest round-boundary
checkpoint (docs/robustness.md "Streamed (out-of-core) resume").

Supported configs (all checked at construction): single-output
objectives (binary, regression family, xentropy) on numerical
features, tree_learner serial or data, bagging (incl. pos/neg
fractions), GOSS, quantized gradients, feature_fraction, extra_trees.
Everything else — multiclass, ranking, categorical splits, DART/RF,
linear trees, monotone/CEGB/interaction constraints, EFB, forced
splits, continuation, voting-/feature-parallel learners — stays on
the resident engine; `create_boosting` only routes here when the data
cannot fit (or ``tpu_streaming=true`` forces it). Split-rule parity
(L1/L2, min_data, min_hessian, min_gain, max_delta_step, path
smoothing, extra-trees, missing directions) comes for free: the same
`find_best_split` evaluates the accumulated histograms.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..metric import metrics_for_config
from ..objective import create_objective
from ..ops.pallas_histogram import multi_leaf_histogram_xla
from ..ops.split import SplitConfig, find_best_split
from ..tree import Tree
from ..utils import log
from ..utils.prefetch import BlockPrefetcher, InflightWindow

# |g*h| bucket count for the GOSS threshold histogram: the top 16 bits
# of the positive-f32 bit pattern (8 exponent + 8 mantissa bits) are
# monotone in the value, so a bucketed order statistic is exact up to
# one bucket width (~0.4% relative)
_GOSS_BUCKETS = 1 << 16


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _even_split(n: int, k: int) -> List[int]:
    """Contiguous near-even row split: first ``n % k`` parts get one
    extra row (the launcher's shard convention)."""
    base, rem = divmod(n, k)
    return [base + (1 if i < rem else 0) for i in range(k)]


def _hash_u01(idx_u32, salt_u32):
    """Counter-based uniform in [0, 1): a pure function of the GLOBAL
    row index and a per-round salt, so bagging/GOSS/stochastic-rounding
    draws are identical no matter how rows are cut into blocks or
    shards (lowne-style 32-bit mix; 24-bit mantissa-exact floats)."""
    x = idx_u32 + salt_u32 * jnp.uint32(0x9E3779B9)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _apply_table(bins_blk, leaf_blk, tbl):
    """Route rows through one level's split table (tbl arrays are [S]).
    Left child KEEPS the parent's leaf id; rows routed right get the
    new leaf id. NaN rows (last bin when has_nan) follow default_left —
    same semantics as the resident partition (learner/serial.py
    apply_splits). ``leaf_blk`` is int16 (device-resident per-row
    state: 2 bytes/row matters at 1e9 rows)."""
    lid = leaf_blk.astype(jnp.int32)
    mk = lid[:, None] == tbl["leaf"][None, :]            # [R, S]
    sel = jnp.any(mk, axis=1)

    def pick(a):
        return jnp.sum(jnp.where(mk, a[None, :].astype(jnp.int32), 0),
                       axis=1)

    feat_r = pick(tbl["feat"])
    thr_r = pick(tbl["thr"])
    dl_r = pick(tbl["dl"]) > 0
    new_r = pick(tbl["new_leaf"])
    nb_r = pick(tbl["nb"])
    hn_r = pick(tbl["hn"]) > 0
    col = jnp.take_along_axis(
        bins_blk.astype(jnp.int32),
        jnp.clip(feat_r, 0, bins_blk.shape[1] - 1)[:, None],
        axis=1)[:, 0]
    is_missing = hn_r & (col == nb_r - 1)
    goes_left = jnp.where(is_missing, dl_r, col <= thr_r)
    return jnp.where(sel & ~goes_left, new_r, lid).astype(jnp.int16)


class StreamingGBDT:
    """Boosting engine for datasets whose binned matrix exceeds HBM —
    single-shard, or data-parallel over a mesh when the per-rank shard
    would still exceed HBM (the Criteo-1TB-class composition).

    Quacks like `gbdt.GBDT` for the surfaces the Booster/engine.train
    loop and the model writer touch; everything per-row lives on host.
    """

    _UNSUPPORTED_MSG = (
        "tpu_streaming (out-of-core) supports single-output objectives "
        "on numerical features with tree_learner=serial or data "
        "(bagging, GOSS and quantized gradients included); {what} "
        "requires the resident engine — reduce the dataset, raise the "
        "device budget, or drop the option")

    def __init__(self, config: Config, train_set: Dataset,
                 fobj=None, mesh=None, init_forest=None):
        self.config = config
        self.train_set = train_set.construct()
        ds = self.train_set

        def _no(cond, what):
            if cond:
                log.fatal(self._UNSUPPORTED_MSG.format(what=what))

        # config-level eligibility: ONE walk of the capability table's
        # "streaming" column (lightgbm_tpu/capabilities.py) — the same
        # rows _streaming_compatible reads, so auto-routing and this
        # constructor can no longer drift (the PR-5 bug class; the
        # sweep in tests/test_streaming_sharded.py pins the iff).
        # Runtime-only features ride the `extra` flags.
        from .. import capabilities
        for name, cap, v in capabilities.engine_verdicts(
                "streaming", config,
                extra={"custom_objective": fobj is not None,
                       "continuation": init_forest is not None}):
            if v == capabilities.FATAL:
                _no(True, cap.describe)
            elif name == "auto_quantize":
                # DEMOTE: tpu_auto_quantize targets the resident int8
                # histogram kernels; an un-asked-for discretization
                # would change streamed numerics — quietly drop it. An
                # EXPLICIT use_quantized_grad stays honored: integer
                # level histograms are what make sharded streaming
                # bit-exact and engage the packed collective wire.
                config.use_quantized_grad = False
            else:
                # a DEMOTE row added to the table without a demotion
                # action here would otherwise be a silent no-op — the
                # one-side-edited drift this engine exists to refuse
                log.fatal(f"capability table DEMOTEs {name!r} for the "
                          f"streaming engine but StreamingGBDT has no "
                          f"demotion action for it — add one here")
        # runtime-shape gates (not feature drift; stay constructor-local)
        _no(mesh is not None and config.tree_learner == "serial",
            "an explicit mesh with tree_learner=serial")
        # dataset-level gate: pandas-category / auto-detected
        # categorical BINS fatal even when categorical_feature is unset
        is_cat = [ds.bin_mappers[f].bin_type == "categorical"
                  for f in ds.used_features]
        _no(any(is_cat), "categorical features")
        self.objective = create_objective(config)
        # belt-and-braces behind the table's name-based ranking row: a
        # custom objective OBJECT flagging is_ranking still fatals
        _no(getattr(self.objective, "is_ranking", False),
            "ranking objectives")

        self.num_class = 1
        self.average_output = False
        self.models: List[Tree] = []
        # mutation version for host-model / hot-swap cache keys (the
        # resident engine's _invalidate_forest_cache analog; bumped by
        # serving.ModelWatcher when it swaps a new forest in)
        self._models_version = 0
        self.iter_ = 0
        self.valid_data: list = []
        self.valid_names: list = []
        self._valid_raw_cache: Dict[int, tuple] = {}
        self.fobj = None
        self.metrics = metrics_for_config(config)

        self.binned = ds.binned                     # host [n, F] uint
        if ds.device_ingested() is not None:
            # streamed blocks are uploaded one at a time per rank —
            # release a device-resident ingest copy (possible when a
            # standalone construct picked device ingest before a forced
            # tpu_streaming run) instead of leaving it orphaned in HBM
            ds._ingest = None
        self.n = int(ds.num_data)
        F = len(ds.used_features)
        self.num_features = F
        num_bin = ds.feature_num_bins()
        self.max_num_bin = int(num_bin.max()) if F else 2
        self.B = max(8, _ceil_to(self.max_num_bin, 8))
        has_nan = np.array(
            [ds.bin_mappers[f].missing_type == "nan"
             for f in ds.used_features], dtype=bool)
        self.feat_num_bin = jnp.asarray(num_bin.astype(np.int32))
        self.feat_has_nan = jnp.asarray(has_nan)
        self._num_bin_np = num_bin.astype(np.int32)
        self._has_nan_np = has_nan

        # ---- mesh / rank layout (tree_learner=data) ------------------
        self.mesh = None
        self._axis = ""
        R = 1
        if config.tree_learner == "data":
            if mesh is not None:
                self.mesh = mesh
            else:
                from ..parallel.mesh import create_data_mesh
                nd = (int(config.tpu_mesh_shape)
                      if str(config.tpu_mesh_shape).strip() else None)
                self.mesh = create_data_mesh(nd)
            R = int(self.mesh.devices.size)
            if R == 1:
                self.mesh = None    # one shard: the serial path IS it
            else:
                self._axis = self.mesh.axis_names[0]
        self.R = R
        self._build_ranks()

        if int(config.num_leaves) > 32767:
            log.fatal("tpu_streaming caps num_leaves at 32767 (int16 "
                      "row state)")
        md = ds.metadata
        self.label = np.asarray(md.label, np.float32)
        self.weight = (None if md.weight is None
                       else np.asarray(md.weight, np.float32))
        self.init_scores = np.zeros(1, dtype=np.float64)
        if md.label is not None:
            # a multi-process gang: each process holds only its row
            # shard, and every rank must start from the same score
            synced = (jax.process_count() > 1
                      and config.boost_from_average)
            self.init_scores[0] = (
                self.objective.init_score_all_processes(md.label,
                                                        md.weight)
                if synced
                else self.objective.init_score(md.label, md.weight))

        self._scfg = SplitConfig(
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            max_delta_step=config.max_delta_step,
            path_smooth=config.path_smooth,
            extra_trees=config.extra_trees,
        )
        self.lr = float(config.learning_rate)
        self._rng = np.random.default_rng(int(config.seed) & 0x7FFFFFFF)
        self._ff = float(config.feature_fraction)

        # ---- row sampling + quantization statics ---------------------
        c = config
        self._use_goss = str(c.data_sample_strategy) == "goss"
        self._use_bag = (not self._use_goss and c.bagging_freq > 0
                         and (c.bagging_fraction < 1.0
                              or c.pos_bagging_fraction < 1.0
                              or c.neg_bagging_fraction < 1.0))
        self._bag_posneg = self._use_bag and (
            c.pos_bagging_fraction < 1.0 or c.neg_bagging_fraction < 1.0)
        self._top_rate = float(c.top_rate)
        self._other_rate = float(c.other_rate)
        self._goss_amp = ((1.0 - self._top_rate)
                          / max(self._other_rate, 1e-12))
        self._use_quant = bool(c.use_quantized_grad)
        self._use_sr = self._use_quant and bool(c.stochastic_rounding)
        qbins = max(2, int(c.num_grad_quant_bins))
        self._glevels = max(qbins // 2, 1)
        self._hlevels = max(qbins - 1, 1)
        self._track_stats = self._use_goss or self._use_quant
        self._seed_u32 = np.uint32(int(c.seed) & 0xFFFFFFFF)
        self._bag_seed_u32 = np.uint32(int(c.bagging_seed) & 0xFFFFFFFF)
        self._pending_stats = None
        if (self._use_bag or self._use_goss or self._use_sr) \
                and self.n_global > 0x7FFFFFFF:
            log.fatal("tpu_streaming row sampling hashes int32 global "
                      "row indices; > 2^31-1 rows need sampling off")
        # collective wire mode (mirrors the resident data learner):
        # psum_scatter feature ownership when tpu_hist_reduce=scatter
        # and the width divides; packed int32 wire under quantization
        self._scatter = (str(c.tpu_hist_reduce) == "scatter"
                         and self.R > 1 and F > 0 and F % self.R == 0)
        self._packed_wire = (self._use_quant and self.R > 1
                             and bool(c.tpu_hist_packed_wire))
        # host-side comm/stream counters — always on (plain ints), the
        # obs registry mirrors them when metrics are enabled
        self.comm_stats = {"allreduce_calls": 0, "allreduce_bytes": 0,
                           "blocks_scanned": 0, "levels": 0}

        # communication/compute overlap (tpu_stream_overlap; docs/
        # perf.md "Communication/compute overlap"). auto = on: the
        # three pipelining moves (threaded H2D block staging, no host
        # sync before the per-level collective, deferred final sweep)
        # only change where the HOST blocks — accumulation order,
        # reduce payloads and score arithmetic are untouched, so the
        # trees are bit-identical on/off by construction. "false" is
        # the synchronous A/B arm (attribution + escape hatch).
        self._overlap = str(config.tpu_stream_overlap) != "false"
        # per-rank in-flight sweep windows, PERSISTENT across level
        # sweeps, the final sweep, and round boundaries: an item is
        # (bins_upload, sweep_output); completing it host-blocks on
        # the output and frees the upload. depth=1 keeps the historic
        # 2-block transient bound (~512 MB/rank at the default block).
        # Under overlap the windows deliberately stay non-empty across
        # the level->find and final->next-round seams — that IS the
        # pipelining; export_train_state drains them first (the PR 13
        # contract; _drain_inflight below).
        def _complete_inflight(item):
            bins_blk, done = item
            jax.block_until_ready(done)
            bins_blk.delete()
        self._inflight = [InflightWindow(1, _complete_inflight)
                          for _ in self._ranks]
        # cyclic one-ahead upload prefetcher over the step-major block
        # schedule (built lazily: _block_schedule needs the rank
        # layout final). Every sweep consumes exactly one full cycle,
        # so the feed stays aligned at sweep boundaries; take(expect=)
        # makes any drift a loud error.
        self._feed = None

        # buffer donation for the streamed score slots (tpu_donate;
        # docs/perf.md "Iteration floor"): each block's [block_rows]
        # f32 score is a pure carry — the final sweep's output fully
        # replaces the slot and every reader (eval_set, checkpoints,
        # the stats prepass) sees only the reassigned reference
        from ..utils.debug import donation_enabled
        self._donate = donation_enabled(config)
        self._hist_rows_per_block = min(self.block_rows, 1 << 14)
        self._sweep = self._make_sweep()
        self._final = self._make_final()
        self._stats_fn = (jax.jit(self._stats_core())
                          if self._track_stats else None)
        self._find = self._make_find()
        self._find_sharded = (self._make_find_sharded()
                              if self.R > 1 else None)
        self._stats_reduce = (self._make_stats_reduce()
                              if self._track_stats and self.R > 1
                              else None)

        # device-resident per-row state, one slot per (rank, block):
        # score f32, leaf int16, label f32, weight f32 (if any) — ~10
        # bytes/row total, so state for a 32 GiB (1.1e9-row) bin matrix
        # fits v5e HBM while the 28x-larger bins stream. It also keeps
        # host traffic down: per sweep the ONLY transfers are the bins
        # block up and one packed [K,13] pull down (round-tripping leaf
        # ids per sweep was the first version's wall; the D2H rate is
        # to be re-measured on the chip).
        init = np.float32(self.init_scores[0])
        self._score_dev: List[list] = []
        self._leaf_dev: List[list] = []
        self._label_dev: List[list] = []
        self._weight_dev: List[list] = []
        self._zeros_leaf: List[jax.Array] = []
        for ri, rk in enumerate(self._ranks):
            dev = rk["dev"]
            zeros_leaf = self._put(
                np.zeros(self.block_rows, np.int16), dev)
            ones_w = (self._put(np.ones(self.block_rows, np.float32),
                                dev)
                      if self.weight is None else None)
            self._zeros_leaf.append(zeros_leaf)
            sc, lf, lb, wt = [], [], [], []
            for b, lo, hi in self._rank_blocks(ri):
                sc.append(self._put(
                    np.full(self.block_rows, init, np.float32), dev))
                lf.append(zeros_leaf)
                lb.append(self._put(
                    self._pad_block(self.label, lo, hi), dev))
                wt.append(self._put(
                    self._pad_block(self.weight, lo, hi), dev)
                    if self.weight is not None else ones_w)
            self._score_dev.append(sc)
            self._leaf_dev.append(lf)
            self._label_dev.append(lb)
            self._weight_dev.append(wt)
        # the f32 copies were only needed for the device upload; at
        # 1e9+ rows they are multiple GiB of host RAM. (The Dataset's
        # own float64 metadata.label stays — it backs the public
        # get_label() API and is owned by the Dataset, not the engine.)
        self.label = self.weight = None
        n_blocks_local = sum(rk["n_blocks"] for rk in self._ranks)
        self.n_blocks = n_blocks_local
        log.info(
            f"streaming engine: {self.n} rows x {F} features binned on "
            f"host ({self.binned.nbytes / 2**30:.2f} GiB), "
            f"{n_blocks_local} local blocks of {self.block_rows} rows"
            + (f", shard {[r['pos'] for r in self._ranks]} of "
               f"{self.R} ({self.n_global} global rows; one "
               f"{'psum_scatter' if self._scatter else 'psum'} per "
               f"level{', packed int32 wire' if self._packed_wire else ''})"
               if self.R > 1 else ""))

    # ------------------------------------------------------ rank layout
    def _put(self, arr, dev):
        """Device placement: committed to the rank's mesh device when
        sharded, the default device otherwise (matching the serial
        streaming path's uncommitted placement)."""
        if dev is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, dev)

    def _build_ranks(self):
        """Split this process's rows over its local mesh devices and
        learn every rank's GLOBAL row offset (the seed of the
        shard-invariant row hash). Single process: all ranks are local;
        a multi-process gang contributes its own shard (the launcher's
        ``data_fn`` row partition) and gathers the per-rank counts."""
        cfg = self.config
        R = self.R
        if R == 1:
            self._ranks = [{"pos": 0, "dev": None, "lo": 0,
                            "hi": self.n, "goff": 0}]
            self.n_global = self.n
            counts_all = np.asarray([self.n], np.int64)
        else:
            from ..parallel.mesh import local_mesh_positions
            flat = list(self.mesh.devices.flat)
            nproc = jax.process_count()
            if nproc > 1:
                my_pos, _ = local_mesh_positions(self.mesh)
                if not my_pos:
                    # a gang member outside the (possibly capped) mesh
                    # would silently drop its rows AND deadlock the
                    # in-mesh ranks' collectives — fatal like the
                    # zero-rows guard below
                    log.fatal(
                        f"streamed sharded training: process "
                        f"{jax.process_index()} owns no device of the "
                        f"{R}-shard mesh (tpu_mesh_shape smaller than "
                        f"the gang?) — its rows would be dropped; "
                        f"match the mesh size to the process count")
                sizes = _even_split(self.n, len(my_pos))
                counts = np.zeros(R, np.int64)
                for i, p in enumerate(my_pos):
                    counts[p] = sizes[i]
                from jax.experimental import multihost_utils
                g = np.asarray(
                    multihost_utils.process_allgather(counts)).reshape(
                        nproc, R)
                counts_all = g.sum(axis=0).astype(np.int64)
            else:
                my_pos = list(range(R))
                sizes = _even_split(self.n, R)
                counts_all = np.asarray(sizes, np.int64)
            goffs = np.concatenate(
                [[0], np.cumsum(counts_all)[:-1]]).astype(np.int64)
            self.n_global = int(counts_all.sum())
            lo = 0
            self._ranks = []
            for i, p in enumerate(my_pos):
                rows = int(counts_all[p]) if nproc > 1 else sizes[i]
                self._ranks.append({"pos": p, "dev": flat[p], "lo": lo,
                                    "hi": lo + rows,
                                    "goff": int(goffs[p])})
                lo += rows
        bad = ([int(p) for p in np.nonzero(counts_all <= 0)[0]]
               if R > 1 else [])
        if bad:
            # mirrors _cli_file_shard's early fatal: a rank that would
            # stream zero blocks deadlocks the per-level collective
            log.fatal(
                f"streamed sharded training would hand rank(s) "
                f"{bad[:8]} zero rows ({self.n_global} global rows "
                f"over {self.R} shards) — every rank must stream at "
                f"least one block; lower tpu_mesh_shape / the process "
                f"count, or feed more rows")

        # block size: bins block ~256 MB by default (PCIe-friendly, far
        # under any HBM), rounded to a lane multiple; per-RANK row
        # ranges cut into blocks of this size (the last block pads)
        rank_max = int(counts_all.max())
        blk = int(cfg.tpu_stream_block_rows)
        explicit = blk > 0
        if blk <= 0:
            blk = max(1 << 16, (256 << 20) // max(self.num_features, 1))
        blk = min(blk, max(rank_max, 8))
        # the hist kernel's internal row chunk must divide the block;
        # blocks >= 16 Ki rows round up to a 16 Ki multiple (the last
        # block pads), smaller ones use the block itself as the chunk
        self.block_rows = (_ceil_to(blk, 1 << 14) if blk >= (1 << 14)
                           else _ceil_to(blk, 8))
        if explicit and self.block_rows != blk:
            # warn only on a real ROUNDING of the requested size (the
            # histogram kernel's row chunk must divide the block) —
            # a value merely clamped to the per-rank row count is a
            # normal one-block configuration, not a mismatch
            log.warning(
                f"tpu_stream_block_rows={cfg.tpu_stream_block_rows} "
                f"does not divide cleanly against the per-rank row "
                f"range / histogram row chunk; rounded to "
                f"{self.block_rows}")
        for rk in self._ranks:
            rk["n_blocks"] = max(
                1, math.ceil((rk["hi"] - rk["lo"]) / self.block_rows))

    def _rank_blocks(self, ri: int):
        rk = self._ranks[ri]
        for b in range(rk["n_blocks"]):
            lo = rk["lo"] + b * self.block_rows
            hi = min(rk["hi"], lo + self.block_rows)
            yield b, lo, hi

    # --------------------------------------------------- jitted pieces
    def _make_sweep(self):
        """Build the jitted per-block level sweep. Only ``bins_blk``
        streams from host; score/label/weight/leaf are device-resident
        block slots and the valid-row count rides as one scalar.
        Bagging/GOSS masks are derived in-sweep from the block's GLOBAL
        row offset (``off``) + the per-round sampling scalars
        (``sampf``/``sampi``), so they cost zero host traffic and are
        invariant to the block/shard cut."""
        objective = self.objective
        num_bins = self.B
        rpb = self._hist_rows_per_block
        use_bag, posneg = self._use_bag, self._bag_posneg
        use_goss, amp = self._use_goss, self._goss_amp
        use_quant, use_sr = self._use_quant, self._use_sr
        c = self.config
        bag_frac = float(c.bagging_fraction)
        pos_frac = float(c.pos_bagging_fraction)
        neg_frac = float(c.neg_bagging_fraction)

        def masks(g, h, label_blk, cnt, idx_u32, sampf, sampi):
            if use_goss:
                metric = jnp.abs(g * h) * cnt
                live = cnt > 0
                is_top = (metric >= sampf[0]) & live
                u = _hash_u01(idx_u32, sampi[1])
                picked = live & ~is_top & (u < sampf[1])
                mask_gh = (is_top.astype(jnp.float32)
                           + picked.astype(jnp.float32)
                           * jnp.float32(amp))
                mask_cnt = (is_top | picked).astype(jnp.float32)
                return mask_gh, mask_cnt
            if use_bag:
                u = _hash_u01(idx_u32, sampi[0])
                if posneg:
                    keep = jnp.where(label_blk > 0, u < pos_frac,
                                     u < neg_frac)
                else:
                    keep = u < bag_frac
                m = cnt * keep.astype(jnp.float32)
                return m, m
            return cnt, cnt

        @jax.jit
        def sweep(bins_blk, score_blk, label_blk, weight_blk, n_valid,
                  leaf_blk, tbl, frontier, off, sampf, sampi):
            leaf_new = _apply_table(bins_blk, leaf_blk, tbl)
            ar = jnp.arange(leaf_blk.shape[0], dtype=jnp.int32)
            cnt = (ar < n_valid).astype(jnp.float32)
            idx_u32 = (off + ar).astype(jnp.uint32)
            g, h = objective.get_gradients(score_blk, label_blk,
                                           weight_blk)
            g = g.reshape(-1).astype(jnp.float32)
            h = h.reshape(-1).astype(jnp.float32)
            mask_gh, mask_cnt = masks(g, h, label_blk, cnt, idx_u32,
                                      sampf, sampi)
            gm = g * mask_gh
            hm = h * mask_gh
            if use_quant:
                # deterministic (or hash-seeded stochastic) rounding to
                # integer levels: exact in the bf16 histogram matmul,
                # exact under any summation order, and int16-packable
                # on the collective wire
                ng = ((_hash_u01(idx_u32, sampi[2]) - 0.5)
                      if use_sr else 0.0)
                nh = ((_hash_u01(idx_u32, sampi[3]) - 0.5)
                      if use_sr else 0.0)
                gq = jnp.round(gm / sampf[2] + ng)
                hq = jnp.round(hm / sampf[3] + nh)
                live = mask_cnt > 0
                gq = jnp.where(live, gq, 0.0)
                hq = jnp.where(live, hq, 0.0)
                vals = jnp.stack([gq, hq, mask_cnt], axis=1)
            else:
                vals = jnp.stack([gm, hm, mask_cnt], axis=1)
            hist = multi_leaf_histogram_xla(
                bins_blk, vals, leaf_new.astype(jnp.int32), frontier,
                num_bins=num_bins, rows_per_block=rpb)
            return leaf_new, hist

        return sweep

    def _stats_core(self):
        """Per-block round statistics from device-resident state ONLY
        (no bins traffic): unmasked |g|/h maxima (quantization scales)
        and, under GOSS, the 65536-bucket |g*h| float-bit histogram the
        global threshold is read from."""
        objective = self.objective
        use_goss = self._use_goss

        def core(score_blk, label_blk, weight_blk, n_valid):
            ar = jnp.arange(score_blk.shape[0], dtype=jnp.int32)
            cnt = (ar < n_valid).astype(jnp.float32)
            g, h = objective.get_gradients(score_blk, label_blk,
                                           weight_blk)
            g = g.reshape(-1).astype(jnp.float32)
            h = h.reshape(-1).astype(jnp.float32)
            ga = jnp.abs(g) * cnt
            hv = h * cnt
            maxs = jnp.stack([jnp.max(ga), jnp.max(hv)])
            if use_goss:
                metric = jnp.abs(g * h) * cnt
                b = (jax.lax.bitcast_convert_type(metric, jnp.int32)
                     >> 15)
                counts = jnp.zeros(_GOSS_BUCKETS, jnp.int32).at[b].add(
                    (cnt > 0).astype(jnp.int32))
            else:
                counts = jnp.zeros(1, jnp.int32)
            return maxs, counts

        return core

    def _make_final(self):
        """Jitted final sweep: apply the last split table, add leaf
        outputs to the device-resident score, and (under GOSS/quant)
        fold next round's statistics out of the NEW score — the stats
        prepass rides the sweep that was already touching every
        block."""
        lr = self.lr
        track = self._track_stats
        core = self._stats_core() if track else None

        def final(bins_blk, score_blk, label_blk, weight_blk, n_valid,
                  leaf_blk, tbl, leaf_out):
            leaf_new = _apply_table(bins_blk, leaf_blk, tbl)
            score_new = score_blk + lr * leaf_out[
                jnp.clip(leaf_new.astype(jnp.int32), 0,
                         leaf_out.shape[0] - 1)]
            if track:
                maxs, counts = core(score_new, label_blk, weight_blk,
                                    n_valid)
            else:
                maxs = jnp.zeros(2, jnp.float32)
                counts = jnp.zeros(1, jnp.int32)
            return leaf_new, score_new, maxs, counts

        # donate ONLY the score slot (argnum 1): the leaf slot cannot
        # donate — at round start every block's slot points at the
        # SHARED per-rank zeros block, and donating it on block 0's
        # dispatch would delete the buffer blocks 1..n still pass
        fn = jax.jit(final,
                     donate_argnums=(1,) if self._donate else ())
        if self._donate and self.config.tpu_debug_checks:
            from ..utils.debug import donation_guard
            fn = donation_guard(fn, "the streamed final sweep's "
                                    "donated score slot")
        return fn

    def _pack13(self, r, p):
        return jnp.concatenate([
            jnp.stack([r["gain"], r["feature"].astype(jnp.float32),
                       r["threshold_bin"].astype(jnp.float32),
                       r["default_left"].astype(jnp.float32)]),
            r["left_sums"].astype(jnp.float32),
            r["right_sums"].astype(jnp.float32),
            p.astype(jnp.float32)])

    def _make_find(self):
        """Jitted per-level split search over the frontier (single-
        shard path). Everything the host loop needs comes back PACKED
        into one [K, 13] f32 array (gain, feature, threshold_bin,
        default_left, left_sums[3], right_sums[3], parent_sums[3]) —
        every separate device->host pull is a sync of its own, and the
        unpacked dict was ~20 pulls per level. ``allowed`` is a TRACED
        argument (same [F] bool shape every call) so per-tree
        feature_fraction masks never recompile;
        ``scale`` rescales quantized integer level sums to real units
        (ones — an exact multiply — when quantization is off). With
        ``extra_trees``, per-(leaf, feature) uniforms ride a traced
        argument (drawn host-side from ``self._rng`` per level —
        mirroring learner/serial.py's per-round draws), so the
        one-random-threshold-per-node semantics actually bind instead
        of silently degrading to plain GBDT (find_best_split skips the
        extra_trees filter when extra_u is None)."""
        use_extra = bool(self._scfg.extra_trees)
        nb, hn = self.feat_num_bin, self.feat_has_nan
        scfg = self._scfg
        pack = self._pack13

        def one(h, p, allowed, eu):
            r = find_best_split(h, p, nb, hn, allowed, scfg,
                                extra_u=eu if use_extra else None)
            return pack(r, p)

        @jax.jit
        def find(hist, allowed, eu, scale):
            # leaf totals from the RAW histogram (integer-exact under
            # quantization, so identical on every shard/feature), then
            # rescale totals and histogram to real units together
            parent = jnp.sum(hist[:, 0, :, :], axis=1) * scale
            h = hist * scale
            return jax.vmap(one, in_axes=(0, 0, None,
                                          0 if use_extra else None))(
                h, parent, allowed, eu)

        return find

    def _make_find_sharded(self):
        """The sharded per-level program: ONE histogram collective
        (psum, or psum_scatter + best-split election under
        tpu_hist_reduce=scatter) of the accumulated [K, F, B, 3] level
        histogram through the shared packed-int32 wire
        (learner/collective.py), then the same packed [K, 13] split
        search — replicated output, identical on every rank."""
        from ..learner.collective import hist_allreduce
        from ..parallel.mesh import P, shard_map
        axis = self._axis
        R = self.R
        F = self.num_features
        scatter = self._scatter
        F_s = F // R if scatter else F
        packed_wire = self._packed_wire
        use_extra = bool(self._scfg.extra_trees)
        nb_full, hn_full = self.feat_num_bin, self.feat_has_nan
        scfg = self._scfg
        pack = self._pack13

        def impl(hist_blk, allowed, eu, scale):
            h = hist_allreduce(hist_blk[0], axis, scatter=scatter,
                               scatter_dim=1, packed=packed_wire)
            # leaf totals straight from the RAW reduced histogram: any
            # one owned feature's bins partition the leaf's rows, and
            # summing BEFORE the channel rescale keeps the totals
            # integer-exact under quantization — every shard derives
            # the identical [K, 3] no matter which feature it owns
            # (scaled sums differ in ULPs between features, which
            # would leak shard-dependent leaf values through the
            # elected record's parent slot)
            parent = jnp.sum(h[:, 0, :, :], axis=1) * scale
            h = h * scale
            if scatter:
                off = (jax.lax.axis_index(axis) * F_s).astype(jnp.int32)
                nb = jax.lax.dynamic_slice_in_dim(nb_full, off, F_s)
                hn = jax.lax.dynamic_slice_in_dim(hn_full, off, F_s)
                al = jax.lax.dynamic_slice_in_dim(allowed, off, F_s)
                eu_s = (jax.lax.dynamic_slice_in_dim(eu, off, F_s,
                                                     axis=1)
                        if use_extra else eu)
            else:
                off = jnp.zeros((), jnp.int32)
                nb, hn, al, eu_s = nb_full, hn_full, allowed, eu

            def one(hk, pk, euk):
                r = find_best_split(hk, pk, nb, hn, al, scfg,
                                    extra_u=euk if use_extra else None)
                r = dict(r)
                r["feature"] = r["feature"] + off
                return pack(r, pk)

            packed13 = jax.vmap(one, in_axes=(0, 0,
                                              0 if use_extra else None))(
                h, parent, eu_s)
            if scatter:
                # SyncUpGlobalBestSplit across feature owners: a small
                # [R, K, 13] all_gather + per-leaf max-gain election
                allp = jax.lax.all_gather(packed13, axis)
                win = jnp.argmax(allp[..., 0], axis=0)
                packed13 = jnp.take_along_axis(
                    allp, win[None, :, None].astype(jnp.int32),
                    axis=0)[0]
            return packed13

        return jax.jit(shard_map(
            impl, mesh=self.mesh,
            in_specs=(P(axis), P(), P(), P()),
            out_specs=P(), check_vma=False))

    def _make_stats_reduce(self):
        """Small per-round collective: pmax of the |g|/h maxima + psum
        of the GOSS bucket histogram (the 'tiny guard psum' pattern the
        serial packed wire uses)."""
        from ..parallel.mesh import P, shard_map
        axis = self._axis

        def impl(maxs, counts):
            return (jax.lax.pmax(maxs[0], axis),
                    jax.lax.psum(counts[0], axis))

        return jax.jit(shard_map(
            impl, mesh=self.mesh, in_specs=(P(axis), P(axis)),
            out_specs=(P(), P()), check_vma=False))

    def _global_of(self, parts):
        """Assemble per-rank device arrays (each ``[1, ...]`` on its
        mesh device) into one mesh-sharded global array — zero-copy;
        the collective program reads its shard in place."""
        from jax.sharding import NamedSharding
        from ..parallel.mesh import P
        shape = (self.R,) + tuple(parts[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, NamedSharding(self.mesh, P(self._axis)), parts)

    # ---------------------------------------------- per-round sampling
    @staticmethod
    def _salt32(seed_u32, tag: int, k: int) -> int:
        x = (int(seed_u32) ^ ((tag * 0x9E3779B9) & 0xFFFFFFFF)
             ^ ((int(k) * 0x85EBCA6B) & 0xFFFFFFFF)) & 0xFFFFFFFF
        return x

    def _collect_stats(self):
        """Reduce the pending per-rank round statistics (folded out of
        the previous final sweep, or computed by a standalone device-
        only prepass on round 0) into global (gmax, hmax, buckets)."""
        if self._pending_stats is None:
            pend = []
            for ri in range(len(self._ranks)):
                maxs = counts = None
                for b, lo, hi in self._rank_blocks(ri):
                    m, c = self._stats_fn(
                        self._score_dev[ri][b], self._label_dev[ri][b],
                        self._weight_dev[ri][b], np.int32(hi - lo))
                    maxs = m if maxs is None else jnp.maximum(maxs, m)
                    counts = c if counts is None else counts + c
                pend.append((maxs, counts))
            self._pending_stats = pend
        pend = self._pending_stats
        self._pending_stats = None     # consumed; the final sweep refills
        if self.R == 1:
            maxs = np.asarray(pend[0][0], np.float64)
            counts = np.asarray(pend[0][1], np.int64)
        else:
            m, c = self._stats_reduce(
                self._global_of([p[0][None] for p in pend]),
                self._global_of([p[1][None] for p in pend]))
            maxs = np.asarray(m, np.float64)
            counts = np.asarray(c, np.int64)
        return float(maxs[0]), float(maxs[1]), counts

    def _round_sampling(self):
        """Host-side per-round sampling/quantization scalars:
        ``sampf`` = [goss_thr, goss_p_pick, scale_g, scale_h] (f32),
        ``sampi`` = [bag_salt, goss_salt, sr_g_salt, sr_h_salt] (u32),
        plus the [3] channel rescale for split finding. Derived from
        GLOBAL statistics, so every rank computes identical values."""
        it = self.iter_
        sampf = np.zeros(4, np.float32)
        sampi = np.zeros(4, np.uint32)
        if self._use_bag:
            k = it // max(int(self.config.bagging_freq), 1)
            sampi[0] = self._salt32(self._bag_seed_u32, 0xBA66, k)
        if self._track_stats:
            gmax, hmax, counts = self._collect_stats()
            if self._use_goss:
                sampi[1] = self._salt32(self._seed_u32, 0x6055, it)
                total = int(counts.sum())
                k_top = max(1, int(total * self._top_rate))
                rev = np.cumsum(counts[::-1])
                j = min(int(np.searchsorted(rev, k_top)),
                        _GOSS_BUCKETS - 1)
                thr_bucket = (_GOSS_BUCKETS - 1) - j
                count_top = int(rev[j])
                sampf[0] = np.array([thr_bucket << 15],
                                    np.uint32).view(np.float32)[0]
                n_rest = max(total - count_top, 0)
                k_rand = int(total * self._other_rate)
                sampf[1] = (min(1.0, k_rand / n_rest)
                            if n_rest > 0 else 0.0)
            if self._use_quant:
                # unmasked maxima bound the masked values; GOSS
                # amplification widens the bound by (1-a)/b so levels
                # stay within +-glevels (a coarser grid than the
                # resident engine's masked max — documented)
                ampf = self._goss_amp if self._use_goss else 1.0
                sampf[2] = max(gmax * ampf, 1e-30) / self._glevels
                sampf[3] = max(hmax * ampf, 1e-30) / self._hlevels
                if self._use_sr:
                    sampi[2] = self._salt32(self._seed_u32, 0x56A1, it)
                    sampi[3] = self._salt32(self._seed_u32, 0x56A2, it)
        scale = (np.asarray([sampf[2], sampf[3], 1.0], np.float32)
                 if self._use_quant else np.ones(3, np.float32))
        return sampf, sampi, scale

    def _leaf_out_np(self, g: float, h: float) -> float:
        """calc_leaf_output (ops/split.py) in host numpy — leaf outputs
        are needed per split on the host path and a device round-trip
        each would be a dispatch plus a sync."""
        l1, l2 = self._scfg.lambda_l1, self._scfg.lambda_l2
        t = np.sign(g) * max(abs(g) - l1, 0.0) if l1 > 0.0 else g
        denom = h + l2
        out = -t / max(denom, 1e-30) if denom > 0.0 else 0.0
        md = self._scfg.max_delta_step
        if md > 0.0:
            out = float(np.clip(out, -md, md))
        return float(out)

    # ------------------------------------------------------------- API
    def can_fuse_iters(self) -> bool:
        return True

    def num_trees(self) -> int:
        return len(self.models)

    @property
    def current_iteration(self) -> int:
        return self.iter_

    def add_valid(self, data, name):
        """Valid sets evaluate via the host model over the RAW valid
        features (the streaming engine never bins or uploads them —
        a valid set large enough to matter should be subsampled).
        Multi-process gangs evaluate each process's LOCAL valid shard."""
        raw = getattr(data, "data", None)
        if raw is None or isinstance(raw, str):
            log.fatal(self._UNSUPPORTED_MSG.format(
                what="valid sets without in-memory raw features "
                     "(file-backed, or already constructed with the "
                     "raw matrix freed — pass a fresh Dataset)"))
        if not hasattr(raw, "shape"):
            # scipy sparse would also fail later (len() raises on
            # sparse, and the host-model traversal reads dense rows) —
            # reject anything non-array-like up front with the standard
            # message instead of crashing mid-eval
            log.fatal(self._UNSUPPORTED_MSG.format(
                what="valid sets whose raw features are not an array"))
        if hasattr(raw, "tocsr") and not isinstance(raw, np.ndarray):
            log.fatal(self._UNSUPPORTED_MSG.format(
                what="sparse raw valid features (densify with "
                     ".toarray() first)"))
        self.valid_data.append(data)
        self.valid_names.append(name)

    @property
    def valid_scores(self):
        log.fatal(self._UNSUPPORTED_MSG.format(
            what="custom feval over valid sets"))

    def eval_set(self, which: int):
        """(data_name, metric_name, value, higher_better) tuples —
        the resident engine's contract (GBDT.eval_set), via the shared
        metric helper so the two engines cannot drift.

        Training eval (which=-1) pulls the full device-resident score
        each call — 4 bytes/row of D2H; at 1e9-row scale through a
        slow pull path enable it sparingly (metric_freq). On a
        multi-process gang metrics cover this process's LOCAL rows,
        and rank 0's values are broadcast so early stopping cannot
        take rank-divergent decisions (a rank unwinding early would
        deadlock the others in the per-level collective)."""
        from ..metric import eval_metric_rows
        if which < 0:
            name = "training"
            raw = np.concatenate(
                [np.asarray(self._score_dev[ri][b])[:hi - lo]
                 for ri in range(len(self._ranks))
                 for b, lo, hi in self._rank_blocks(ri)])
            md = self.train_set.metadata
            label, weight, qb = md.label, md.weight, md.query_boundaries
        else:
            ds = self.valid_data[which]
            name = self.valid_names[which]
            # incremental raw cache: only the NEW trees since the last
            # eval traverse the valid matrix (the host model folds the
            # init score into tree 0, so increments sum exactly);
            # without this, per-iteration eval would rebuild and
            # re-traverse the whole forest — O(T^2) over training
            # shape[0], not len(): valid row count must not depend on
            # the raw container's __len__ (absent on scipy sparse)
            done, raw = self._valid_raw_cache.get(
                which, (0, np.zeros(int(ds.data.shape[0]), np.float64)))
            n_now = len(self.models)
            if n_now > done:
                raw = raw + self.predict(
                    ds.data, raw_score=True, start_iteration=done,
                    num_iteration=n_now - done)
                self._valid_raw_cache[which] = (n_now, raw)
            if ds.metadata.init_score is not None:
                # per-row valid init score (resident engine adds it in
                # _init_score_tile; the host model knows nothing of it)
                raw = raw + np.asarray(ds.metadata.init_score,
                                       np.float64)
            label = ds.metadata.label
            weight = ds.metadata.weight
            qb = ds.metadata.query_boundaries
        res = eval_metric_rows(self.objective, self.metrics, name,
                               raw, label, weight, qb, 1)
        if self.R > 1 and jax.process_count() > 1:
            # every rank must reach the SAME early-stop decision or the
            # survivors deadlock in the next per-level collective —
            # local-shard metrics diverge, so rank 0's values are
            # broadcast (one small allgather; the engine loop calls
            # eval_set in lockstep on every rank)
            from jax.experimental import multihost_utils
            vals = np.asarray([v for (_, _, v, _) in res], np.float64)
            g = np.asarray(
                multihost_utils.process_allgather(vals)).reshape(
                    jax.process_count(), -1)
            res = [(nm, mt, float(v0), hb)
                   for (nm, mt, _, hb), v0 in zip(res, g[0])]
        return res

    def rollback_one_iter(self):
        log.fatal(self._UNSUPPORTED_MSG.format(what="rollback"))

    def train_chunk(self, k: int):
        from .. import obs
        for _ in range(k):
            self.train_one_iter()
            # liveness on the fused (no-callback) path: the engine.py
            # round loop is bypassed here, so the watchdog's heartbeat
            # must ride the chunk loop itself (gbdt.train_chunk stamps
            # the same way)
            obs.heartbeat("train")

    # -------------------------------------------------------- training
    def _pad_block(self, arr, lo, hi, fill=0):
        out = arr[lo:hi]
        if hi - lo < self.block_rows:
            pad = np.full((self.block_rows - (hi - lo),) + out.shape[1:],
                          fill, dtype=out.dtype)
            out = np.concatenate([out, pad])
        return out

    def _empty_table(self) -> Dict[str, np.ndarray]:
        z = np.zeros(1, np.int32)
        return {"leaf": z - 1, "feat": z, "thr": z, "dl": z,
                "new_leaf": z, "nb": z, "hn": z}

    # --------------------------------------------- block upload staging
    def _block_schedule(self):
        """The step-major ``(ri, b, lo, hi)`` dispatch order EVERY
        streamed sweep iterates (level sweeps, the final sweep, the
        next round's sweeps — identical by construction), flattened
        for the cyclic upload prefetcher."""
        iters = [list(self._rank_blocks(ri))
                 for ri in range(len(self._ranks))]
        seq = []
        for step in range(max(len(it) for it in iters)):
            for ri in range(len(iters)):
                if step < len(iters[ri]):
                    b, lo, hi = iters[ri][step]
                    seq.append((ri, b, lo, hi))
        return seq

    def _stage_bins(self, item):
        """Stage one block's bins on its rank's device. Runs on the
        prefetch worker thread under overlap: slice + pad + device_put
        ONLY — never a collective (utils/prefetch.py's threading
        contract; the collective-safety checker pins it)."""
        ri, _b, lo, hi = item
        return self._put(self._pad_block(self.binned, lo, hi),
                         self._ranks[ri]["dev"])

    def _next_bins(self, ri, b, lo, hi):
        """The next scheduled block's padded bins upload: staged one
        step ahead on the worker thread under overlap (the host
        slices/pads/wires block i+1 while the device sweeps block i),
        staged inline — the historic order — when overlap is off."""
        if self._feed is None:
            self._feed = BlockPrefetcher(
                self._stage_bins, self._block_schedule(),
                threaded=self._overlap)
        return self._feed.take(expect=(ri, b, lo, hi))

    def _drain_inflight(self) -> None:
        """Complete every pending streamed dispatch: host-block on the
        in-flight sweep outputs and free their bins uploads. The PR 13
        checkpoint contract — ``export_train_state`` must only ever
        see fully materialized score slots — and the synchronous-mode
        sweep barrier both land here."""
        for win in self._inflight:
            win.drain()

    def _level_hists(self, table, frontier_np, sampf, sampi):
        """One streamed pass over every local rank's blocks: apply the
        pending split table, accumulate each rank's [K, F, B, 3] level
        histogram across its blocks — NO collective here; the single
        per-level reduction happens in the find program."""
        from .. import obs
        n_ranks = len(self._ranks)
        tbl_dev, frontier_dev, sampf_dev, sampi_dev = [], [], [], []
        for rk in self._ranks:
            dev = rk["dev"]
            frontier_dev.append(self._put(frontier_np, dev))
            tbl_dev.append({k: self._put(v, dev)
                            for k, v in table.items()})
            sampf_dev.append(self._put(sampf, dev))
            sampi_dev.append(self._put(sampi, dev))
        hists = [None] * n_ranks
        iters = [list(self._rank_blocks(ri)) for ri in range(n_ranks)]
        blocks = 0
        # BLOCK-STEP-MAJOR over the ranks: dispatch step s for every
        # rank before host-blocking on any rank's step s-1, so all
        # local devices compute concurrently (rank-major order would
        # serialize the devices to ~1/R utilization single-process);
        # each rank still accumulates ITS blocks in order, so the
        # partial sums are unchanged bit for bit.
        for step in range(max(len(it) for it in iters)):
            for ri, rk in enumerate(self._ranks):
                if step >= len(iters[ri]):
                    continue
                b, lo, hi = iters[ri][step]
                bins_blk = self._next_bins(ri, b, lo, hi)
                off = np.int32(rk["goff"] + (lo - rk["lo"]))
                leaf_new, h_blk = self._sweep(
                    bins_blk, self._score_dev[ri][b],
                    self._label_dev[ri][b], self._weight_dev[ri][b],
                    np.int32(hi - lo), self._leaf_dev[ri][b],
                    tbl_dev[ri], frontier_dev[ri], off, sampf_dev[ri],
                    sampi_dev[ri])
                self._leaf_dev[ri][b] = leaf_new    # stays on device
                hists[ri] = (h_blk if hists[ri] is None
                             else hists[ri] + h_blk)
                blocks += 1
                # throttle + free with the per-rank 2-block in-flight
                # window: unthrottled async dispatch would enqueue
                # EVERY block's ~256 MB device buffer before the
                # device drains one — at 128 blocks that is ~34 GB of
                # live transients and an OOM (observed at the 32 GiB
                # proof shape). Blocking on the rank's PREVIOUS block
                # keeps upload of block s+1 overlapped with compute of
                # block s while bounding transients to ~512 MB/rank.
                self._inflight[ri].push((bins_blk, hists[ri]))
        if not self._overlap:
            # synchronous mode: the historic pre-reduce barrier. Under
            # overlap the tail items stay pending — the find program's
            # own result pull waits on them through data dependencies,
            # so the collective dispatches WITHOUT a host sync and the
            # leftover bins uploads are freed by the next sweep's
            # pushes (<= depth block buffers per rank carry over).
            self._drain_inflight()
        self.comm_stats["blocks_scanned"] += blocks
        if obs.enabled():
            obs.inc("stream.blocks_scanned", blocks)
        return hists

    def _find_level(self, hists, allowed_dev, eu, scale):
        """The ONE per-level collective + split search: returns the
        packed [K_pad, 13] host array (identical on every rank).

        Under ``tpu_stream_overlap`` this is called with the level's
        tail sweeps still in flight: the collective program dispatches
        immediately (async, ordered behind the accumulations by data
        dependency) and the host blocks only on the packed result
        pull — the reduce overlaps the tail sweeps and the next
        blocks' staging instead of waiting for a host-side barrier."""
        from .. import obs
        self.comm_stats["levels"] += 1
        if self.R == 1:
            return np.asarray(self._find(hists[0], allowed_dev, eu,
                                         scale), np.float64)
        t0 = time.perf_counter()
        hist_g = self._global_of([h[None] for h in hists])
        bests = np.asarray(self._find_sharded(hist_g, allowed_dev, eu,
                                              scale), np.float64)
        dt_ms = (time.perf_counter() - t0) * 1e3
        K_pad = int(hists[0].shape[0])
        payload = K_pad * self.num_features * self.B * 4 \
            * (2 if self._packed_wire else 3)
        self.comm_stats["allreduce_calls"] += 1
        self.comm_stats["allreduce_bytes"] += payload
        if obs.enabled():
            obs.inc("comm.allreduce_calls")
            obs.inc("comm.allreduce_bytes", payload)
            obs.observe("comm.allreduce_ms", dt_ms)
        return bests

    def train_one_iter(self) -> None:
        L = int(self.config.num_leaves)
        max_depth = int(self.config.max_depth)
        F = self.num_features

        allowed = np.ones(F, bool)
        if self._ff < 1.0:
            k = max(1, int(F * self._ff))
            allowed[:] = False
            allowed[self._rng.choice(F, size=k, replace=False)] = True
        allowed_dev = jnp.asarray(allowed)
        sampf, sampi, scale = self._round_sampling()
        scale_dev = jnp.asarray(scale)

        for ri in range(len(self._ranks)):
            for b in range(self._ranks[ri]["n_blocks"]):
                self._leaf_dev[ri][b] = self._zeros_leaf[ri]
        nl = 1
        nn = 0
        # per-node host arrays (grown as splits land)
        sf, tb, dl, lc, rc, gains, ivals, icnts = \
            [], [], [], [], [], [], [], []
        leaf_parent_slot: Dict[int, tuple] = {}   # leaf -> (node, side)
        leaf_sums = np.zeros((L, 3), np.float64)
        frontier = [0]
        table = self._empty_table()
        depth = 0

        while frontier:
            K = len(frontier)
            # pad the frontier (and split table below) to powers of two:
            # -1 sentinel leaves match no rows, so the padding costs a
            # slice of wasted histogram width but caps the number of
            # distinct jit specializations at log2(L) — without it every
            # pruned-frontier shape recompiles (tens of seconds each,
            # dwarfing the sweep itself)
            K_pad = 1 << max(0, (K - 1)).bit_length()
            frontier_np = np.asarray(frontier + [-1] * (K_pad - K),
                                     np.int32)
            hists = self._level_hists(table, frontier_np, sampf, sampi)
            # per-level extra_trees uniforms (one random threshold per
            # (leaf, feature)); None when off — drawn from the shared
            # host rng, so every rank draws the same field
            eu = (jnp.asarray(self._rng.random((K_pad, F)), jnp.float32)
                  if self._scfg.extra_trees
                  else np.zeros((1, 1), np.float32))
            # ONE device->host pull per level (packed [K_pad, 13]),
            # and — sharded — ONE histogram collective per level
            bests = self._find_level(hists, allowed_dev, eu, scale_dev)
            for i, lf in enumerate(frontier):
                leaf_sums[lf] = bests[i, 10:13]
            table = self._empty_table()
            depth += 1
            if nl >= L or (0 < max_depth <= depth - 1):
                frontier = []
                break
            gains_k = bests[:K, 0]                   # drop pad lanes
            order = np.argsort(-gains_k)             # best-first within
            budget = L - nl                          # the level
            chosen = [i for i in order[:budget]
                      if np.isfinite(gains_k[i]) and gains_k[i] > -1e37]
            if not chosen:
                frontier = []
                break
            tl, tf, tt, tdl, tnew, tnb, thn = [], [], [], [], [], [], []
            new_frontier = []
            for i in chosen:
                lf = frontier[i]
                feat = int(bests[i, 1])
                node = nn
                nn += 1
                right_leaf = nl
                nl += 1
                if lf in leaf_parent_slot:
                    pn, side = leaf_parent_slot.pop(lf)
                    (lc if side == 0 else rc)[pn] = node
                sf.append(feat)
                tb.append(int(bests[i, 2]))
                dl.append(bool(bests[i, 3] > 0.5))
                lc.append(~lf)
                rc.append(~right_leaf)
                gains.append(float(bests[i, 0]))
                ivals.append(self._leaf_out_np(leaf_sums[lf][0],
                                               leaf_sums[lf][1]))
                icnts.append(int(round(leaf_sums[lf][2])))
                leaf_parent_slot[lf] = (node, 0)
                leaf_parent_slot[right_leaf] = (node, 1)
                leaf_sums[lf] = bests[i, 4:7]
                leaf_sums[right_leaf] = bests[i, 7:10]
                tl.append(lf)
                tf.append(feat)
                tt.append(int(bests[i, 2]))
                tdl.append(int(bests[i, 3] > 0.5))
                tnew.append(right_leaf)
                tnb.append(int(self._num_bin_np[feat]))
                thn.append(int(self._has_nan_np[feat]))
                new_frontier.extend([lf, right_leaf])
            S = len(tl)
            S_pad = 1 << max(0, (S - 1)).bit_length()
            pad = [0] * (S_pad - S)
            table = {"leaf": np.asarray(tl + [-1] * (S_pad - S), np.int32),
                     "feat": np.asarray(tf + pad, np.int32),
                     "thr": np.asarray(tt + pad, np.int32),
                     "dl": np.asarray(tdl + pad, np.int32),
                     "new_leaf": np.asarray(tnew + pad, np.int32),
                     "nb": np.asarray(tnb + pad, np.int32),
                     "hn": np.asarray(thn + pad, np.int32)}
            frontier = new_frontier if nl < L and not (
                0 < max_depth <= depth) else []
            if not frontier:
                break

        # ---- final sweep: last split table + score update ------------
        leaf_out = np.zeros(max(nl, 1), np.float32)
        for lf in range(nl):
            leaf_out[lf] = self._leaf_out_np(leaf_sums[lf][0],
                                             leaf_sums[lf][1])
        from .. import obs
        n_ranks = len(self._ranks)
        tbl_dev, leaf_out_dev = [], []
        for rk in self._ranks:
            tbl_dev.append({k: self._put(v, rk["dev"])
                            for k, v in table.items()})
            leaf_out_dev.append(self._put(leaf_out, rk["dev"]))
        maxs = [None] * n_ranks
        counts = [None] * n_ranks
        iters = [list(self._rank_blocks(ri)) for ri in range(n_ranks)]
        blocks = 0
        # block-step-major like _level_hists: keep every local device
        # busy while the per-rank 2-block window bounds transients
        for step in range(max(len(it) for it in iters)):
            for ri, rk in enumerate(self._ranks):
                if step >= len(iters[ri]):
                    continue
                b, lo, hi = iters[ri][step]
                bins_blk = self._next_bins(ri, b, lo, hi)
                leaf_new, score_new, m_blk, c_blk = self._final(
                    bins_blk, self._score_dev[ri][b],
                    self._label_dev[ri][b], self._weight_dev[ri][b],
                    np.int32(hi - lo), self._leaf_dev[ri][b],
                    tbl_dev[ri], leaf_out_dev[ri])
                self._leaf_dev[ri][b] = leaf_new
                self._score_dev[ri][b] = score_new
                blocks += 1
                if self._track_stats:
                    # next round's statistics fold out of this sweep
                    # (gradients of the NEW score) — no extra pass
                    maxs[ri] = (m_blk if maxs[ri] is None
                                else jnp.maximum(maxs[ri], m_blk))
                    counts[ri] = (c_blk if counts[ri] is None
                                  else counts[ri] + c_blk)
                self._inflight[ri].push((bins_blk, score_new))
        if not self._overlap:
            # synchronous mode: complete the round before returning.
            # Under overlap the final sweep's tail DEFERS — the next
            # round's first level-sweep pushes complete it (its sweeps
            # read score_new, so device data dependencies order the
            # two rounds; the host never stalls between them). The
            # next reader either blocks through a data dependency
            # (eval_set / _collect_stats pulls) or drains explicitly
            # (export_train_state — the PR 13 checkpoint contract).
            # Note GOSS/quantized configs host-block at the next
            # round's _collect_stats anyway (the sampling scalars need
            # the folded stats), which bounds how much of the final
            # sweep those configs can actually hide.
            self._drain_inflight()
        self.comm_stats["blocks_scanned"] += blocks
        if obs.enabled():
            obs.inc("stream.blocks_scanned", blocks)
        if self._track_stats:
            self._pending_stats = list(zip(maxs, counts))

        tree_arrays = {
            "num_leaves": nl,
            "split_feature": np.asarray(sf, np.int32),
            "threshold_bin": np.asarray(tb, np.int32),
            "default_left": np.asarray(dl, bool),
            "left_child": np.asarray(lc, np.int32),
            "right_child": np.asarray(rc, np.int32),
            "split_gain": np.asarray(gains, np.float32),
            "internal_value": np.asarray(ivals, np.float32),
            "internal_count": np.asarray(icnts, np.int64),
            "leaf_value": leaf_out[:nl].astype(np.float64),
            "leaf_count": leaf_sums[:nl, 2].round().astype(np.int64),
            "leaf_weight": leaf_sums[:nl, 1].astype(np.float64),
        }
        self.models.append(Tree.from_device(
            tree_arrays, self.lr, self.train_set.bin_mappers,
            list(self.train_set.used_features)))
        self.iter_ += 1

    # ------------------------------------------ checkpoint / resume
    # The streamed engine is the one training path where preemption is
    # the NORM (out-of-core runs are the longest runs), so it carries
    # the same durable-checkpoint contract as the resident engine:
    # export everything that evolves across rounds, and a resumed run
    # is bit-exact vs an uninterrupted one BY CONSTRUCTION — the
    # bagging/GOSS/stochastic-rounding draws are counter-hashes of the
    # GLOBAL row index + per-round salts derived from (seed, iter), so
    # they need no saved state; what must travel is the device-resident
    # scores, the host RNG (feature_fraction / extra_trees draws), the
    # pending next-round statistics the last final sweep folded out
    # (saving them beats recomputing: a standalone stats prepass could
    # fuse differently under XLA than the folded one), and the shard/
    # block layout the scores are cut by.
    def _layout_fingerprint(self) -> Dict:
        return {
            "R": int(self.R),
            "n": int(self.n),
            "n_global": int(self.n_global),
            "block_rows": int(self.block_rows),
            "ranks": [(int(rk["pos"]), int(rk["lo"]), int(rk["hi"]),
                       int(rk["goff"]), int(rk["n_blocks"]))
                      for rk in self._ranks],
        }

    def export_train_state(self) -> Dict:
        # the PR 13 contract under tpu_stream_overlap: a deferred
        # final sweep may still be in flight at a round boundary —
        # drain it (block on the sweep outputs, free the uploads) so
        # the np.asarray score pulls below export fully materialized
        # slots, never a snapshot raced against pending updates
        self._drain_inflight()
        state = {
            "engine": type(self).__name__,
            "iteration": int(self.iter_),
            # exact pickled trees (model TEXT rounds values through
            # "{:g}" — not bit-exact), same as the resident engine
            "models": list(self.models),
            "process_index": int(jax.process_index()),
            "process_count": int(jax.process_count()),
            "init_scores": self.init_scores.copy(),
            "rng": self._rng.bit_generator.state,
            "layout": self._layout_fingerprint(),
            # the device-resident per-(rank, block) score slots — THE
            # accumulated floats a resumed run must continue from
            # (padded to block_rows; the pad lanes are inert)
            "scores": [[np.asarray(s) for s in per_rank]
                       for per_rank in self._score_dev],
            # next round's GOSS/quantization statistics, folded out of
            # the final sweep that just ran (None when untracked or
            # already consumed — a standalone prepass recomputes then)
            "pending_stats": (
                None if self._pending_stats is None else
                [(np.asarray(m), np.asarray(c))
                 for (m, c) in self._pending_stats]),
            # incremental valid-set raw caches (host f64 accumulators;
            # rebuilding them from scratch re-sums trees in a different
            # association order — not bit-identical)
            "valid_raw_cache": {int(k): (int(done), raw.copy())
                                for k, (done, raw)
                                in self._valid_raw_cache.items()},
        }
        return state

    def import_train_state(self, state: Dict) -> bool:
        """Adopt :meth:`export_train_state` output into a freshly
        constructed engine. The checkpoint is TOPOLOGY-FREE: when the
        live shard/block layout matches the saved fingerprint the
        exact score slots are adopted as-is, and when it differs (a
        resumed fleet at R′ ≠ R ranks, a changed block size, a
        narrower gang after a degrade) the per-(rank, block) score
        slots are RE-CUT — reassembled by global row index from the
        saved slots (reading sibling ranks' checkpoint files when the
        rows span old processes), or recomputed from the pickled trees
        for any rows no saved slot covers (a bit-exact device replay
        of the final sweeps' score arithmetic). Eligibility for the
        re-cut is a capability-table verdict
        (``capabilities.stream_recut_verdict``): bit-exact under
        quantized gradients, opt-in (``tpu_elastic_recut=true``) on
        the exact-f32 path, and a hard error naming what moved for
        genuinely incompatible state (different data, engine, or tree
        count). Returns True."""
        # a fresh engine's windows are empty, but adopting state into
        # a live one must not leave stale sweeps pending against the
        # slots being replaced
        self._drain_inflight()
        saved_engine = state.get("engine")
        if saved_engine is not None \
                and saved_engine != type(self).__name__:
            log.fatal(
                f"checkpoint was written by a {saved_engine} engine but "
                f"resume constructed {type(self).__name__} — the "
                f"boosting/tree_learner/tpu_streaming params must match "
                f"the original run")
        models = state.get("models")
        if models is None:
            log.fatal("checkpoint state holds no model trees — corrupt "
                      "or incompatible checkpoint")
        self.models = list(models)
        self._models_version += 1
        self.iter_ = int(state["iteration"])
        if len(self.models) != self.iter_:
            log.fatal(
                f"checkpoint state is for iteration "
                f"{state['iteration']} but holds {len(self.models)} "
                f"trees — mismatched checkpoint contents")
        if state.get("init_scores") is not None:
            self.init_scores = np.asarray(state["init_scores"],
                                          np.float64)
        self._rng.bit_generator.state = state["rng"]
        saved_layout = state.get("layout") or {}
        layout = self._layout_fingerprint()
        same_process = (
            int(state.get("process_count", 1)) == jax.process_count()
            and int(state.get("process_index", 0))
            == jax.process_index())
        if saved_layout == layout and same_process \
                and state.get("scores") is not None:
            # fast path: identical topology — adopt the exact slots
            scores = state["scores"]
            for ri, rk in enumerate(self._ranks):
                for b in range(rk["n_blocks"]):
                    self._score_dev[ri][b] = self._put(
                        np.asarray(scores[ri][b], np.float32),
                        rk["dev"])
            pend = state.get("pending_stats")
            if pend is not None and self._track_stats:
                self._pending_stats = [
                    (self._put(np.asarray(m, np.float32), rk["dev"]),
                     self._put(np.asarray(c, np.int32), rk["dev"]))
                    for (m, c), rk in zip(pend, self._ranks)]
            else:
                self._pending_stats = None
        else:
            self._import_recut(state, saved_layout, layout)
        for ri, rk in enumerate(self._ranks):
            # leaf slots are per-tree transients (reset at every round
            # start); point them back at the shared zero block
            for b in range(rk["n_blocks"]):
                self._leaf_dev[ri][b] = self._zeros_leaf[ri]
        self._valid_raw_cache = {
            int(k): (int(done), np.asarray(raw, np.float64))
            for k, (done, raw)
            in (state.get("valid_raw_cache") or {}).items()}
        self._hm_cache = (None, None)
        return True

    # ------------------------------------------- elastic re-cut (resume)
    def _import_recut(self, state: Dict, saved_layout: Dict,
                      layout: Dict) -> None:
        """Re-cut a checkpoint written under a DIFFERENT shard/block
        layout onto the live one. Streamed score slots are a
        deterministic function of trees × global rows, so the slots
        reassemble by global row index from whatever saved slots are
        reachable (this state's own, plus sibling old-rank checkpoint
        files) and any uncovered rows replay from the pickled trees —
        both bit-exact reconstructions of the per-row floats. Pending
        GOSS/quant round statistics re-reduce exactly (max / integer
        sum are grouping-invariant); when incomplete they are dropped
        and the round-0-style standalone prepass recomputes them."""
        from .. import capabilities, obs
        if not saved_layout:
            log.fatal("streamed checkpoint carries no shard/block "
                      "layout fingerprint — corrupt or incompatible "
                      "checkpoint")
        saved_nglobal = int(saved_layout.get("n_global", -1))
        if saved_nglobal != self.n_global:
            log.fatal(
                f"streamed resume cannot re-cut this checkpoint: the "
                f"GLOBAL row count moved ({saved_nglobal} saved, "
                f"{self.n_global} now) — scores are per-row state, so "
                f"a changed dataset is genuinely incompatible (elastic "
                f"resume re-cuts the same rows across a different "
                f"shard/block topology only)")
        if saved_layout != layout \
                or int(state.get("process_count", 1)) \
                != jax.process_count():
            # a REAL topology change: the re-cut continuation's
            # bit-equality is a capability-table verdict. (Same-layout
            # states that merely lack score slots skip this — the tree
            # replay below is bit-exact for any numerics.)
            diff = sorted(set(
                [k for k in layout
                 if saved_layout.get(k) != layout.get(k)]
                + ([] if int(state.get("process_count", 1))
                   == jax.process_count() else ["process_count"])))
            moved = ", ".join(
                f"{k}: {saved_layout.get(k)!r} -> {layout.get(k)!r}"
                for k in diff if k not in ("ranks", "process_count")
            ) or f"process topology ({state.get('process_count')} -> " \
                f"{jax.process_count()} rank(s))"
            verdict, why = capabilities.stream_recut_verdict(
                self.config)
            if verdict == capabilities.FATAL:
                log.fatal(
                    f"streamed resume found a changed shard/block "
                    f"layout ({moved}) and refused to re-cut: {why}")
            elif verdict == capabilities.DEMOTE:
                log.warning(f"streamed resume re-cutting a changed "
                            f"shard/block layout ({moved}): {why}")
            else:
                log.info(f"streamed resume re-cutting a changed "
                         f"shard/block layout ({moved}): {why}")
            obs.inc("train.topology_changes", force=True)
        else:
            log.warning("streamed resume: checkpoint layout matches "
                        "but carries no score slots; recomputing them "
                        "from the pickled trees")

        # ---- gather every reachable saved slot by GLOBAL row --------
        glob = np.zeros(self.n_global, np.float32)
        cov = np.zeros(self.n_global, bool)
        pend_by_pos: Dict[int, tuple] = {}
        for eng_state in [state] + self._peer_states(state):
            lay = eng_state.get("layout") or {}
            scores = eng_state.get("scores")
            pend = eng_state.get("pending_stats")
            sb = int(lay.get("block_rows", 0) or 0)
            for ri, rk in enumerate(lay.get("ranks") or []):
                pos, lo, hi, goff = (int(rk[0]), int(rk[1]),
                                     int(rk[2]), int(rk[3]))
                rows = hi - lo
                if scores is not None and sb > 0 \
                        and ri < len(scores):
                    for b, blk in enumerate(scores[ri]):
                        blo = b * sb
                        take = min(sb, rows - blo)
                        if take <= 0:
                            continue
                        s = np.asarray(blk, np.float32)
                        glob[goff + blo:goff + blo + take] = s[:take]
                        cov[goff + blo:goff + blo + take] = True
                if pend is not None and ri < len(pend):
                    pend_by_pos[pos] = pend[ri]

        # ---- fill the live slots (reshard; replay uncovered) --------
        init = np.float32(self.init_scores[0])
        replay_blocks = []
        for ri, rk in enumerate(self._ranks):
            for b, lo, hi in self._rank_blocks(ri):
                g0 = rk["goff"] + (lo - rk["lo"])
                if not cov[g0:g0 + (hi - lo)].all():
                    replay_blocks.append((ri, b, lo, hi))
                    continue
                slot = np.full(self.block_rows, init, np.float32)
                slot[:hi - lo] = glob[g0:g0 + (hi - lo)]
                self._score_dev[ri][b] = self._put(slot, rk["dev"])
        if replay_blocks:
            log.warning(
                f"elastic resume: {len(replay_blocks)} streamed score "
                f"block(s) had no reachable saved slot (missing or "
                f"unreadable old-rank checkpoint file); recomputing "
                f"them from the {len(self.models)} pickled trees — a "
                f"bit-exact device replay of the final-sweep score "
                f"arithmetic")
            self._replay_score_blocks(replay_blocks)

        # ---- pending round statistics -------------------------------
        R_saved = int(saved_layout.get("R", 1))
        if self._track_stats and pend_by_pos \
                and len(pend_by_pos) == R_saved:
            # grouping-invariant re-reduction: elementwise MAX of the
            # per-old-rank maxima, integer SUM of the bucket counts —
            # handed to mesh position 0 with zero-contributions
            # elsewhere, so the live pmax/psum reproduce the exact
            # global values the old topology would have reduced to
            maxs = np.max(np.stack(
                [np.asarray(m, np.float32)
                 for m, _c in pend_by_pos.values()]), axis=0)
            counts = np.sum(np.stack(
                [np.asarray(c, np.int64)
                 for _m, c in pend_by_pos.values()]),
                axis=0).astype(np.int32)
            self._pending_stats = [
                ((self._put(maxs, rk["dev"]),
                  self._put(counts, rk["dev"]))
                 if rk["pos"] == 0 else
                 (self._put(np.zeros_like(maxs), rk["dev"]),
                  self._put(np.zeros_like(counts), rk["dev"])))
                for rk in self._ranks]
        else:
            if self._track_stats and pend_by_pos:
                log.warning(
                    f"elastic resume: pending round statistics "
                    f"reachable for {len(pend_by_pos)} of {R_saved} "
                    f"old rank(s); dropping them — the standalone "
                    f"device prepass recomputes the same "
                    f"grouping-invariant maxima/counts at round start")
            self._pending_stats = None

    def _peer_states(self, state: Dict) -> List[Dict]:
        """Sibling OLD processes' engine states at this iteration,
        read from the shared checkpoint directory (multi-process
        elastic resume: a new rank's rows can span several old ranks'
        per-process score shards). Unreachable or incompatible peer
        files are skipped with a warning — their rows fall back to the
        tree replay."""
        P = int(state.get("process_count", 1))
        me = int(state.get("process_index", 0))
        d = str(state.get("_checkpoint_dir") or "")
        if P <= 1 or not d:
            return []
        from ..recovery.checkpoint import (CheckpointError,
                                           CheckpointManager)
        out = []
        for q in range(P):
            if q == me:
                continue
            try:
                st = CheckpointManager(d, rank=q).load(
                    iteration=self.iter_)
            except CheckpointError as e:
                log.warning(
                    f"elastic resume: old rank {q}'s checkpoint at "
                    f"iteration {self.iter_} is unreadable ({e}); its "
                    f"rows will be recomputed from the pickled trees")
                continue
            eng = (st or {}).get("engine") or {}
            lay = eng.get("layout") or {}
            if eng.get("engine") != type(self).__name__ \
                    or int(eng.get("iteration", -1)) != self.iter_ \
                    or int(lay.get("n_global", -1)) != self.n_global:
                log.warning(
                    f"elastic resume: old rank {q}'s checkpoint at "
                    f"iteration {self.iter_} is incompatible (engine/"
                    f"iteration/row-count mismatch); skipping it")
                continue
            out.append(eng)
        return out

    def _replay_fns(self):
        """Jitted tree-replay pieces mirroring the final sweep's score
        arithmetic EXACTLY (the same ``_apply_table`` routing, the
        same one ``lr * leaf_out[leaf]`` f32 add per tree) — what
        makes the recompute path a bit-exact reconstruction of the
        saved slots rather than a close one."""
        cached = getattr(self, "_replay_cache", None)
        if cached is not None:
            return cached
        lr = self.lr

        @jax.jit
        def apply_j(bins_blk, leaf_blk, tbl):
            return _apply_table(bins_blk, leaf_blk, tbl)

        @jax.jit
        def add_j(score_blk, leaf_blk, leaf_out):
            return score_blk + lr * leaf_out[
                jnp.clip(leaf_blk.astype(jnp.int32), 0,
                         leaf_out.shape[0] - 1)]

        self._replay_cache = (apply_j, add_j)
        return self._replay_cache

    def _tree_tables(self, tree) -> List[Dict[str, np.ndarray]]:
        """Reconstruct a pickled tree's per-level split tables — the
        exact shape ``train_one_iter`` fed ``_apply_table``. The
        construction invariants make this derivable from child
        topology alone: node j's right branch minted leaf j+1, its
        left branch kept the split leaf's id, and a leaf splits only
        at its own depth (an unchosen frontier leaf never re-enters
        the frontier)."""
        nn = int(tree.num_leaves) - 1
        if nn <= 0:
            return []
        leaf_of = np.zeros(nn, np.int32)
        depth_of = np.zeros(nn, np.int32)
        for i in range(nn):
            for side, child in ((0, int(tree.left_child[i])),
                                (1, int(tree.right_child[i]))):
                if child >= 0:
                    leaf_of[child] = leaf_of[i] if side == 0 \
                        else np.int32(i + 1)
                    depth_of[child] = depth_of[i] + 1
        tables = []
        for d in range(int(depth_of.max()) + 1):
            idx = np.flatnonzero(depth_of == d).astype(np.int32)
            S = len(idx)
            S_pad = 1 << max(0, (S - 1)).bit_length()
            zpad = np.zeros(S_pad - S, np.int32)
            feats = np.asarray(tree.split_feature)[idx].astype(np.int32)
            tables.append({
                "leaf": np.concatenate(
                    [leaf_of[idx], np.full(S_pad - S, -1, np.int32)]),
                "feat": np.concatenate([feats, zpad]),
                "thr": np.concatenate(
                    [np.asarray(tree.threshold_bin)[idx]
                     .astype(np.int32), zpad]),
                "dl": np.concatenate(
                    [np.asarray(tree.default_left)[idx]
                     .astype(np.int32), zpad]),
                "new_leaf": np.concatenate(
                    [(idx + 1).astype(np.int32), zpad]),
                "nb": np.concatenate([self._num_bin_np[feats], zpad]),
                "hn": np.concatenate(
                    [self._has_nan_np[feats].astype(np.int32), zpad]),
            })
        return tables

    def _replay_score_blocks(self, replay_blocks) -> None:
        """Recompute ``(ri, b, lo, hi)`` score slots from the pickled
        trees: route every tree's per-level split tables over the
        block's bins, add its ``lr * leaf_out`` — the identical f32
        accumulation order training ran, so the result is bit-equal to
        the slot the lost checkpoint held."""
        apply_j, add_j = self._replay_fns()
        init = np.float32(self.init_scores[0])
        prog = [(self._tree_tables(t),
                 (np.asarray(t.leaf_value, np.float64)
                  / self.lr).astype(np.float32))
                for t in self.models]
        dev_cache: Dict[int, list] = {}
        for ri, b, lo, hi in replay_blocks:
            rk = self._ranks[ri]
            if ri not in dev_cache:
                dev_cache[ri] = [
                    ([{k: self._put(v, rk["dev"])
                       for k, v in tbl.items()} for tbl in tables],
                     self._put(lo_np, rk["dev"]))
                    for tables, lo_np in prog]
            bins_blk = self._put(
                self._pad_block(self.binned, lo, hi), rk["dev"])
            score = self._put(
                np.full(self.block_rows, init, np.float32), rk["dev"])
            for tables_dev, leaf_out_dev in dev_cache[ri]:
                leaf = self._zeros_leaf[ri]
                for tbl_dev in tables_dev:
                    leaf = apply_j(bins_blk, leaf, tbl_dev)
                score = add_j(score, leaf, leaf_out_dev)
            jax.block_until_ready(score)
            bins_blk.delete()
            self._score_dev[ri][b] = score

    # ------------------------------------------------------- predict
    def predict(self, X, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                pred_leaf: bool = False, **_overrides) -> np.ndarray:
        # _overrides: tpu_predict_* serving knobs (resident-engine
        # traversal only; the host-model path here ignores them)
        from ..io.model_text import HostModel
        cache = getattr(self, "_hm_cache", (None, None))
        if cache[0] != len(self.models):
            cache = (len(self.models),
                     HostModel.from_engine(self, self.config))
            self._hm_cache = cache
        return cache[1].predict(X, raw_score=raw_score,
                                start_iteration=start_iteration,
                                num_iteration=num_iteration,
                                pred_leaf=pred_leaf)
