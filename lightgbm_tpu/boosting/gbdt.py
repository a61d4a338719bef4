"""GBDT boosting engine: the per-iteration training loop.

Reference: ``GBDT::TrainOneIter`` (src/boosting/gbdt.cpp, UNVERIFIED —
empty mount, see SURVEY.md banner): gradients from the objective →
(bagging subset) → train one tree per class → shrinkage → update train +
valid scores → metrics.

TPU-first: one jitted ``step`` fuses gradient computation, the whole
leaf-wise tree growth, and train/valid score updates; the host loop only
orchestrates iterations, callbacks, and model bookkeeping (mirroring the
reference where everything inside an iteration is C++/CUDA and Python owns
the callback loop). Scores and the binned matrix stay device-resident
across iterations; per-iteration host traffic is just the finished tree's
flat arrays (the reference's CUDA learner syncs the same per-tree state,
cuda_single_gpu_tree_learner.cpp).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import capabilities, obs
from ..config import Config
from ..io.dataset import Dataset
from ..learner.serial import GrowConfig, grow_tree
from ..metric import Metric, metrics_for_config
from ..objective import Objective, create_objective
from ..ops.histogram import pad_rows
from ..ops.predict import forest_predict_binned, tree_predict_binned
from ..ops.select import kth_largest, kth_smallest
from ..tree import Tree
from ..utils import log
from ..utils.prefetch import InflightWindow

# once-per-process marker for the tpu_hist_partition=auto stand-down
# warning (every train() builds a fresh GBDT; correct default behavior
# must not warn repeatedly)
_WARNED_PART_AUTO: list = []




def _cegb_u_fold(U, leaf_used, leaf_id, in_sample):
    """U |= path-features of each IN-SAMPLE row's leaf for one tree
    (cost_effective_gradient_boosting.hpp marks feature-used-in-data on
    split application, over the bagged/GOSS partition only): one-hot
    [n, L] x [L, F] matmul (0/1 exact in bf16, f32 accumulation).
    Runs inside the jitted step so the GOSS sample mask — computed
    device-side — governs acquisition exactly."""
    L = leaf_used.shape[0]
    oh = ((leaf_id[:, None]
           == jnp.arange(L, dtype=jnp.int32)[None, :])
          & in_sample[:, None]).astype(jnp.bfloat16)
    hit = jax.lax.dot_general(
        oh, leaf_used.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return U | (hit > 0.5)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def predict_pad_rows(n_rows: int, chunk_rows, buckets) -> int:
    """Total rows the predict chunk plan allocates for an ``n_rows``
    request — THE serving pad policy (pow2 bucket under the chunk
    floor, whole same-shape chunks above it), shared between
    ``_run_forest_chunks``'s plan and serve/service.py's
    ``serve.batch_fill_ratio`` denominator so the gauge can never
    drift from the dispatched shape."""
    from ..config import coerce_bool
    chunk = max(int(chunk_rows), 1024)
    n = max(int(n_rows), 1)
    if n > chunk:
        return -(-n // chunk) * chunk
    return _predict_row_bucket(n, chunk) if coerce_bool(buckets) else n


# smallest pow2 row bucket a predict pads to; serve/service.py's
# warmup walk starts here so it visits exactly the engine's bucket set
PREDICT_ROW_BUCKET_FLOOR = 128


def _predict_row_bucket(n: int, cap: int) -> int:
    """Pad a predict batch up to the nearest power-of-two row bucket
    (floor 128), capped at the chunk size — arbitrary request sizes then
    hit a BOUNDED traversal compile cache (<= log2(cap/128) programs)
    instead of one program per distinct n."""
    b = max(_next_pow2(max(n, 1)), PREDICT_ROW_BUCKET_FLOOR)
    return b if b <= cap else cap

# rows a histogram block holds (the padded row count is a multiple; a
# small table takes its own row count rounded up to 256);
# learner/serial.py::grow_tree says what the chip read for other sizes
ROWS_PER_BLOCK = 4096

# stacked-forest cache entries kept per engine (distinct (start, num,
# pad) tree ranges in flight at once — full model + a few early-stop
# slices; each entry is only T * Ln * ~10 ints of HBM)
_STACK_CACHE_ENTRIES = 8


class _DeviceData:
    """Device-resident binned data + metadata for one dataset.

    With a mesh, rows are sharded over the DATA axis (the reference's
    per-machine row shards, dataset_loader.cpp rank-aware loading); padding
    rounds up so every shard holds whole histogram blocks.
    """

    def __init__(self, ds: Dataset, rows_per_block: int, mesh=None,
                 transposed: bool = False, shard_features: bool = False,
                 n_feature_pad: int = 0, binned_override=None,
                 n_layout: int = None):
        ds.construct()
        self.n = ds.num_data
        # feature-parallel replicates rows; data/voting shard them
        row_shards = (mesh.devices.size
                      if mesh is not None and not shard_features else 1)
        # multi-host placement requires every process to contribute the
        # SAME padded chunk shape (make_array_from_process_local_data);
        # with uneven shards (e.g. the distributed CLI's remainder on
        # the last rank) the pad target must be the LARGEST local shard,
        # agreed via a host-side counts allgather — otherwise shapes
        # (and thus the traced SPMD programs) diverge across processes.
        # The caller passes n_layout when it already gathered the max
        # (GBDT.__init__ does, for rows_per_block); valid sets gather
        # their own here.
        if n_layout is None:
            n_layout = self.n
            if (mesh is not None and not shard_features
                    and jax.process_count() > 1):
                from jax.experimental import multihost_utils
                g = np.asarray(multihost_utils.process_allgather(
                    np.asarray([self.n], np.int64)))
                n_layout = int(g.max())
        self.n_pad = pad_rows(max(n_layout, self.n),
                              rows_per_block * row_shards)
        # device-resident ingest (ops/ingest.py): the binned matrix was
        # PRODUCED on the accelerator — adopt it directly (row/column
        # padding happens on device) instead of round-tripping through
        # host. Meshes and the EFB bundled matrix keep the host upload
        # path (sharded placement consumes host numpy).
        dev = (ds.device_ingested() if binned_override is None else None)
        use_dev = dev is not None and mesh is None
        if use_dev:
            binned = None
            bins_width = int(dev.bins.shape[1])
            bins_itemsize = np.dtype(dev.bins.dtype).itemsize
        else:
            binned = (ds.binned if binned_override is None
                      else binned_override)   # EFB physical matrix
            if ds.device_ingested() is not None \
                    and getattr(ds, "_binned", None) is not None:
                # host fallback (mesh / EFB): the host copy is now
                # authoritative — drop the device-resident ingest
                # arrays instead of leaving them orphaned in HBM next
                # to the sharded uploads
                ds._ingest = None
            if n_feature_pad and binned.shape[1] < n_feature_pad:
                # pad feature columns so every device owns an equal slice
                # (scatter/feature-parallel); padded features never split
                # (num_bin=1, allowed=False in the engine's metadata)
                binned = np.concatenate(
                    [binned, np.zeros((binned.shape[0],
                                       n_feature_pad - binned.shape[1]),
                                      binned.dtype)], axis=1)
            if self.n_pad > self.n:
                pad = np.zeros((self.n_pad - self.n, binned.shape[1]),
                               dtype=binned.dtype)
                binned = np.concatenate([binned, pad], axis=0)
            bins_width = binned.shape[1]
            bins_itemsize = binned.itemsize

        from ..parallel.mesh import P, put, shard_rows
        axis = mesh.axis_names[0] if mesh is not None else None

        # HBM capacity guard: the dominant device residents are the
        # row-major bins and (Pallas path) the feature-major bins_t;
        # per-device share divides by the row shard count. Fail with an
        # actionable message instead of an opaque device OOM.
        from ..utils.hbm import (ENGINE_HBM_FRACTION, binned_device_bytes,
                                 hbm_bytes_limit)
        hbm_limit = hbm_bytes_limit()
        if hbm_limit:
            need = binned_device_bytes(self.n_pad, bins_width,
                                       bins_itemsize, transposed)
            # rows (data/voting) or columns (feature-parallel) shard
            # over every mesh device either way
            n_dev = mesh.devices.size if mesh is not None else 1
            per_dev = need // n_dev
            if obs.enabled():
                # the capacity-guard estimate as a gauge: HBM creep
                # shows as hbm.binned_estimate_bytes vs hbm.bytes_limit
                # trending together, not as a surprise fatal
                obs.set_gauge("hbm.binned_estimate_bytes", per_dev)
                obs.set_gauge("hbm.bytes_limit", hbm_limit)
            if per_dev > ENGINE_HBM_FRACTION * hbm_limit:
                from ..utils import log as _log
                _log.fatal(
                    f"binned data needs ~{per_dev / 2**30:.1f} GiB per "
                    f"device but HBM is {hbm_limit / 2**30:.1f} GiB. "
                    f"Shard rows over more devices "
                    f"(tree_learner=data), lower max_bin, or drop "
                    f"features")

        def place(a, extra_dims=1):
            if mesh is None:
                return jnp.asarray(a)
            if shard_features:
                # rows replicated under feature-parallel
                return put(mesh, np.asarray(a), P())
            return shard_rows(mesh, np.asarray(a), extra_dims)

        if use_dev:
            # no feature-column padding here: use_dev implies mesh is
            # None, and F_pad == F without a mesh (need_fpad is a
            # sharded-layout concern) — only rows can need padding
            bins = dev.bins
            assert not n_feature_pad or bins.shape[1] == n_feature_pad
            if bins.shape[0] < self.n_pad:
                bins = jnp.concatenate(
                    [bins, jnp.zeros((self.n_pad - bins.shape[0],
                                      bins.shape[1]), bins.dtype)])
            elif bins.shape[0] > self.n_pad:
                # a previous engine padded further (bigger block size);
                # pad rows are zeros, so trimming is exact
                bins = bins[:self.n_pad]
            self.bins = bins
            # swap the padded array back into the ingest result: the
            # UNPADDED original's HBM is released (host_binned slices
            # to n_rows, so Dataset consumers are unaffected) — without
            # this the dataset would hold a second full-size copy for
            # its whole lifetime
            dev.bins = bins
            self.bins_t = None
            if transposed:
                # feature-major int8 tile: the ingest kernel already
                # emitted it fused with the row-major pass; derive
                # on-device (bitcast transpose) when it did not — the
                # HOST transpose is gone either way
                bt = dev.bins_t
                if bt is None:
                    bt = jax.lax.bitcast_convert_type(
                        bins.T.astype(jnp.uint8), jnp.int8)
                if bt.shape[1] < self.n_pad:
                    bt = jnp.concatenate(
                        [bt, jnp.zeros((bt.shape[0],
                                        self.n_pad - bt.shape[1]),
                                       jnp.int8)], axis=1)
                elif bt.shape[1] > self.n_pad:
                    bt = bt[:, :self.n_pad]
                self.bins_t = bt
                dev.bins_t = bt
            elif dev.bins_t is not None:
                # this engine never reads the tile (non-Pallas config on
                # a dataset whose construct-time params emitted it) —
                # release its HBM instead of keeping a dead same-size
                # copy alive via the ingest result
                dev.bins_t = None
        else:
            if mesh is not None and shard_features:
                self.bins = put(mesh, binned, P(None, axis))
            else:
                self.bins = place(binned, extra_dims=2)
            self.bins_t = None
            if transposed:
                # feature-major int8 copy for the Pallas histogram kernel
                bt = np.ascontiguousarray(binned.T).astype(np.int8)
                if mesh is None:
                    self.bins_t = jnp.asarray(bt)
                elif shard_features:
                    self.bins_t = put(mesh, bt, P(axis, None))
                else:
                    self.bins_t = put(mesh, bt, P(None, axis))
        self._place = place
        md = ds.metadata

        def _pad1(a, fill=0.0):
            if a is None:
                return None
            a = np.asarray(a, dtype=np.float32)
            if a.ndim == 1 and len(a) < self.n_pad:
                a = np.concatenate(
                    [a, np.full(self.n_pad - len(a), fill, np.float32)])
            return place(a)

        self.label = _pad1(md.label)
        self.weight = _pad1(md.weight)
        self.init_score = (None if md.init_score is None
                           else np.asarray(md.init_score, np.float64))
        self.query_boundaries = md.query_boundaries
        self.valid_mask = place(
            (np.arange(self.n_pad) < self.n).astype(np.float32))


# tpu_auto_quantize only engages at the scale the A/B validated
# (docs/perf.md): below this, exact f32 gradients are the default.
# Policy constants live in the capability table (capabilities.py);
# this module-level alias stays monkeypatchable for tests.
AUTO_QUANT_MIN_ROWS = capabilities.AUTO_QUANT_MIN_ROWS


def _cols_needed(host: Dict[str, np.ndarray]) -> float:
    """Columns the histograms of these trees (any leading dims) had to
    read, from the trees' own counts: a tree's root rows once and the
    smaller child's rows at each split (benchmark/lib/work.py's rule);
    a tree that never split counts 0."""
    lc, rc = host["left_child"], host["right_child"]
    icount, lcount = host["internal_count"], host["leaf_count"]

    def count(child):
        inner = np.take_along_axis(icount, np.maximum(child, 0), axis=-1)
        leaf = np.take_along_axis(lcount, np.maximum(~child, 0), axis=-1)
        return np.where(child >= 0, inner, leaf).astype(np.int64)

    n_nodes = host["num_leaves"][..., None] - 1
    live = np.arange(lc.shape[-1]) < n_nodes
    smaller = np.where(live, np.minimum(count(lc), count(rc)), 0)
    roots = np.where(n_nodes[..., 0] > 0, icount[..., 0], 0)
    return float(np.sum(smaller) + np.sum(roots.astype(np.int64)))


def goss_shard_valid_counts(n_local: int, n_pad_local: int,
                            n_global_devices: int, n_processes: int,
                            allgather=None):
    """Per-global-shard valid row counts for GOSS's exact subset sizes.

    Single-process: this process's rows span the whole mesh, so the
    counts fall out of the local block layout. Multi-host: each process
    computes its LOCAL devices' counts (its chunk is placed on its own
    addressable devices in mesh order by
    ``make_array_from_process_local_data``) and one host-side counts
    allgather concatenates them in process order — the same order the
    mesh's ``axis_index`` enumerates global shards. ``allgather`` is
    injectable for single-process tests.
    """
    if n_processes <= 1:
        blk = n_pad_local // n_global_devices
        return [max(0, min(n_local - s * blk, blk))
                for s in range(n_global_devices)]
    n_local_dev = max(1, n_global_devices // n_processes)
    blk = n_pad_local // n_local_dev
    loc = np.asarray([max(0, min(n_local - s * blk, blk))
                      for s in range(n_local_dev)], np.int64)
    if allgather is None:
        from jax.experimental import multihost_utils
        allgather = multihost_utils.process_allgather
    return [int(v) for v in np.asarray(allgather(loc)).reshape(-1)]


class GBDT:
    """Boosting engine (reference: GBDT class, src/boosting/gbdt.cpp)."""

    # score/valid-score carries may donate under tpu_donate (the step
    # outputs fully replace the inputs, nothing host-side re-reads the
    # pre-step buffers). DART re-reads score_pre/valid_pre to rescale
    # the new tree against the dropped set, and RF folds the step
    # output against held base/pred-sum buffers — both override False.
    _donate_carries = True

    def __init__(self, config: Config, train_set: Dataset,
                 fobj: Optional[Callable] = None, mesh=None,
                 init_forest=None):
        self.config = config
        self.train_set = train_set.construct()
        self.fobj = fobj
        # distributed learner selection (TreeLearner factory seam,
        # src/treelearner/tree_learner.cpp): serial runs single-device;
        # data/voting shard rows, feature shards columns over a mesh
        self.mesh = mesh
        if (self.mesh is None and config.tree_learner != "serial"
                and jax.device_count() > 1):
            from ..parallel.mesh import (create_data_mesh,
                                         create_feature_mesh)
            # tpu_mesh_shape: cap the mesh to the first N devices
            # ("" = all visible devices)
            nd = (int(config.tpu_mesh_shape)
                  if str(config.tpu_mesh_shape).strip() else None)
            self.mesh = (create_feature_mesh(nd)
                         if config.tree_learner == "feature"
                         else create_data_mesh(nd))
        if self.mesh is not None and config.tree_learner == "serial":
            self.mesh = None
        if self.mesh is None and config.tree_learner != "serial":
            log.warning(
                f"tree_learner={config.tree_learner} needs more than "
                f"one device and {jax.device_count()} is visible; the "
                f"serial learner runs")
        self.learner_type = config.tree_learner if self.mesh is not None \
            else "serial"
        self._shard_features = self.learner_type == "feature"
        if self._shard_features and jax.process_count() > 1:
            # feature-sharded placement has no process-local chunk
            # semantics (every process binned ALL columns); the
            # row-sharded learners are the multi-host story
            log.fatal("tree_learner=feature is not supported multi-host;"
                      " use data or voting")
        self.axis = (self.mesh.axis_names[0]
                     if self.mesh is not None else "")
        # measured-default quantized training (tpu_auto_quantize,
        # VERDICT r4 item 2): in the A/B's validated regime — >= 500k
        # rows, gbdt boosting, a level-sum-safe objective, no custom
        # fobj — int8 histograms were +18-36% throughput at
        # equal-or-better equal-round AUC (docs/perf.md). Explicit
        # use_quantized_grad settings always win; smaller data keeps
        # the exact-f32 default for reference bit-compatibility.
        if (bool(config.tpu_auto_quantize)
                and "use_quantized_grad" not in config.raw_params
                and not config.use_quantized_grad
                and config.boosting == "gbdt" and fobj is None
                and self.train_set.num_data >= AUTO_QUANT_MIN_ROWS
                and str(config.objective)
                in capabilities.AUTO_QUANTIZE_OBJECTIVES):
            config.use_quantized_grad = True
            config._quantize_auto = True
            log.info("tpu_auto_quantize: enabling quantized gradients "
                     "(int8 histograms) for this training — measured "
                     "equal-AUC and faster at this scale; set "
                     "use_quantized_grad=false to keep f32")
        self.objective: Objective = create_objective(config)
        if hasattr(self.objective, "prepare") and \
                self.train_set.metadata.label is not None:
            self.objective.prepare(self.train_set.metadata.label,
                                   self.train_set.metadata.weight)
        if self.objective.is_ranking:
            self.objective.setup_queries(
                self.train_set.metadata.query_boundaries,
                self.train_set.num_data,
                position=self.train_set.metadata.position)
        # stateful objectives (lambdarank_unbiased): per-rank propensity
        # state threads through the boosting step and updates host-side
        # each iteration (not rolled back by rollback_one_iter)
        self._pos_state = None
        if getattr(self.objective, "has_pos_state", False):
            if self.mesh is not None:
                log.fatal("position debiasing (a `position` field, or "
                          "lambdarank_unbiased=true) is not supported "
                          "with distributed tree_learner yet; drop the "
                          "position field / flag or use the serial "
                          "learner")
            self._pos_state = self.objective.init_pos_state()
        self.metrics: List[Metric] = metrics_for_config(config)
        self.num_class = config.num_tree_per_iteration
        self.models: List[Tree] = []
        self.iter_ = 0
        self.average_output = False  # RF subclass sets True
        # stacked-forest device cache bookkeeping: _models_version bumps
        # on ANY model mutation (growth, rollback, state import, DART/RF
        # leaf rescales) so cached device stacks can never serve stale
        # leaf values (_stack_model_list)
        self._models_version = 0
        self._stack_cache: Optional[Tuple[Tuple[int, int], Dict]] = None
        # device-resident SHAP path-table cache (predict_contrib):
        # same (len, version) key + LRU shape as _stack_cache, entries
        # keyed by (start_tree, n_trees, dtype) slice
        self._shap_cache: Optional[Tuple[Tuple[int, int], Dict]] = None
        # tree-sharded predict (serve/shard.py enable_tree_sharding):
        # when set, stacked forests are placed with the [T] axis
        # NamedSharding-split over this mesh and predicts take the
        # sharded traversal; _shard_consts caches the replicated
        # feat_num_bin/feat_has_nan copies so warm predicts re-place
        # nothing
        self._predict_mesh = None
        self._shard_consts: Optional[Tuple] = None

        n_shards = self.mesh.devices.size if self.mesh is not None else 1
        n_rows_layout = self.train_set.num_data
        if self.mesh is not None and jax.process_count() > 1:
            # uneven multi-host shards: every process must derive the
            # SAME block size or the traced SPMD programs diverge
            from jax.experimental import multihost_utils
            n_rows_layout = int(np.asarray(
                multihost_utils.process_allgather(
                    np.asarray([n_rows_layout], np.int64))).max())
        rows_per_block = min(
            ROWS_PER_BLOCK,
            pad_rows(max(1, n_rows_layout // n_shards), 256))
        self.rows_per_block = rows_per_block

        F = len(self.train_set.used_features)
        self.num_features = F

        # ---- EFB (dataset_loader.cpp FindGroups/FastFeatureBundling) --
        # bundle mutually-exclusive sparse features into shared physical
        # columns; the learner scans F_phys columns and expands
        # histograms back to logical features (io/bundling.py). Composes
        # with serial / data-psum / voting (scatter and feature-parallel
        # keep their own feature-ownership layouts instead).
        self.has_bundles = False
        self.bundle_plan = None
        self._bundle_dev = None
        self._bundled_binned = None
        # under device-resident ingest the bundle probe would force a
        # full-matrix D2H materialization (Dataset.binned) during the
        # exact window ttfi_s exists to shrink — and dense accelerator
        # datasets essentially never bundle. Probe only when the host
        # copy exists anyway; tpu_ingest_device=false restores EFB.
        _host_bins_free = (self.train_set.device_ingested() is None
                           or getattr(self.train_set, "_binned", None)
                           is not None)
        if (config.enable_bundle and F >= 2 and not self._shard_features
                and not _host_bins_free):
            log.info("EFB bundle probe skipped: dataset is "
                     "device-resident (tpu_ingest_device); set "
                     "tpu_ingest_device=false to restore EFB")
        if (config.enable_bundle and F >= 2 and not self._shard_features
                and _host_bins_free):
            mappers = [self.train_set.bin_mappers[f]
                       for f in self.train_set.used_features]
            eligible = np.array(
                [(m.bin_type != "categorical")
                 and m.missing_type == "none" for m in mappers],
                dtype=bool)
            default_bins = np.array(
                [m.value_to_bin(0.0) if eligible[i] else 0
                 for i, m in enumerate(mappers)], dtype=np.int32)
            if int(eligible.sum()) >= 2:
                from ..io.bundling import find_bundles, plan_bundles
                nb_logical = self.train_set.feature_num_bins()
                multi = find_bundles(
                    self.train_set.binned, nb_logical, eligible,
                    default_bins,
                    max_conflict_rate=config.max_conflict_rate,
                    seed=config.data_random_seed)
                if multi:
                    self.bundle_plan = plan_bundles(nb_logical,
                                                    default_bins, multi)
                    self.has_bundles = True
                    obs.inc("bundle.groups", len(multi), force=True)
                    obs.inc("bundle.features_bundled",
                            sum(len(b) for b in multi), force=True)
                    log.info(
                        f"EFB: bundled {sum(len(b) for b in multi)} "
                        f"features into {len(multi)} bundles "
                        f"({F} -> {self.bundle_plan.n_phys} columns)")

        # pad feature count to a multiple of the shard count so scatter /
        # feature-parallel slices are equal-width (padded features carry
        # num_bin=1 + allowed=False, so they never win a split)
        need_fpad = self.mesh is not None and not self.has_bundles and (
            self._shard_features
            or (self.learner_type == "data"
                and config.tpu_hist_reduce == "scatter"))
        self.F_pad = (_ceil_to(max(F, 1), n_shards) if need_fpad else F)
        fpad = self.F_pad - F
        num_bin = self.train_set.feature_num_bins()
        self.max_num_bin = int(num_bin.max()) if F else 2
        if self.has_bundles:
            # one shared width covers both the physical scan and the
            # logical expansion
            self.max_num_bin = max(
                self.max_num_bin, int(self.bundle_plan.phys_num_bin.max()))
        # static histogram width: pad to a lane-friendly multiple
        self.B = max(8, _ceil_to(self.max_num_bin, 8))
        is_cat = np.array(
            [self.train_set.bin_mappers[f].bin_type == "categorical"
             for f in self.train_set.used_features], dtype=bool)
        # categorical NaN/unseen is bin 0 and routes via bitset-miss, not
        # the numerical last-bin NaN convention
        has_nan = np.array(
            [self.train_set.bin_mappers[f].missing_type == "nan"
             for f in self.train_set.used_features], dtype=bool) & ~is_cat
        if fpad:
            num_bin = np.concatenate([num_bin, np.ones(fpad, num_bin.dtype)])
            has_nan = np.concatenate([has_nan, np.zeros(fpad, bool)])
            is_cat = np.concatenate([is_cat, np.zeros(fpad, bool)])
        self.feat_num_bin = jnp.asarray(num_bin.astype(np.int32))
        # bin count of each column of the kernel's source (bins_t): the
        # bundled physical matrix under EFB; under feature-parallel one
        # program serves every shard's column slice, so a position takes
        # the largest count any shard has there
        col_bins = (self.bundle_plan.phys_num_bin if self.has_bundles
                    else num_bin)
        if self._shard_features:
            col_bins = col_bins.reshape(n_shards, -1).max(axis=0)
        self._hist_col_bins = tuple(int(b) for b in col_bins)
        self.feat_has_nan = jnp.asarray(has_nan)
        self.has_categorical = bool(is_cat.any())
        self.feat_is_cat = jnp.asarray(is_cat)
        # static categorical positions for the sliced split-search fast
        # path (ops/split.py cat_positions); scatter/feature-parallel
        # shards search dynamic slices, so they fall back to the masked
        # full-width scan
        self._cat_positions = tuple(int(i) for i in np.nonzero(is_cat)[0])

        # monotone constraints ([F_pad] int8 by used-feature index;
        # categorical features are never direction-constrained)
        mc = list(config.monotone_constraints or [])
        mono = np.zeros(self.F_pad, dtype=np.int8)
        if mc:
            for i, f in enumerate(self.train_set.used_features):
                if f < len(mc):
                    mono[i] = int(mc[f])
            mono[is_cat] = 0
        self.has_monotone = bool(np.any(mono != 0))
        self.feat_mono = jnp.asarray(mono) if self.has_monotone else None

        # feature_contri (config_auto.cpp feature_contri, the "fp"
        # feature-penalty aliases): per-feature split-gain multipliers,
        # given by ORIGINAL feature index, remapped to used features
        fc = list(config.feature_contri or [])
        self.has_contri = bool(fc) and any(float(c) != 1.0 for c in fc)
        self.feat_contri = None
        if self.has_contri:
            arr = np.ones(self.F_pad, dtype=np.float32)
            for i, f in enumerate(self.train_set.used_features):
                if f < len(fc):
                    arr[i] = float(fc[f])
            self.feat_contri = jnp.asarray(arr)

        # interaction constraints ([G, F_pad] bool over used features)
        from ..config import parse_interaction_constraints
        groups_spec = parse_interaction_constraints(
            config.interaction_constraints)
        self.has_interaction = bool(groups_spec)
        self.interaction_groups = None
        if self.has_interaction:
            orig_to_used = {f: i for i, f in
                            enumerate(self.train_set.used_features)}
            gm = np.zeros((len(groups_spec), self.F_pad), dtype=bool)
            for gi, grp in enumerate(groups_spec):
                for f in grp:
                    u = orig_to_used.get(int(f))
                    if u is not None:
                        gm[gi, u] = True
            self.interaction_groups = jnp.asarray(gm)

        if self.has_bundles:
            from ..io.bundling import apply_bundles, build_expand_maps
            self._bundled_binned = apply_bundles(self.train_set.binned,
                                                 self.bundle_plan)
            mpf, mpb, mvalid, mdef = build_expand_maps(
                self.bundle_plan, num_bin[:F], self.B)
            self._bundle_dev = (
                jnp.asarray(mpf), jnp.asarray(mpb), jnp.asarray(mvalid),
                jnp.asarray(mdef),
                jnp.asarray(self.bundle_plan.bundled),
                jnp.asarray(self.bundle_plan.phys_col),
                jnp.asarray(self.bundle_plan.start),
                jnp.asarray(self.bundle_plan.default_bin))

        # CEGB (cost_effective_gradient_boosting.hpp): split penalty +
        # coupled per-feature penalty charged until a feature first
        # enters the model (host-tracked, device array refreshed on
        # use) + LAZY per-row penalty (round 4): splitting leaf l on f
        # costs lazy[f] x (#rows in l that never met f on a tree path
        # yet) — the per-row feature-acquisition model. Acquisition
        # state is a device [n_pad, F_pad] matrix updated after each
        # tree from the per-leaf path-feature sets.
        coupled = list(config.cegb_penalty_feature_coupled or [])
        lazy = list(config.cegb_penalty_feature_lazy or [])
        self.has_cegb = bool(
            config.cegb_penalty_split > 0 or any(coupled) or any(lazy))
        self._cegb_coupled = None
        self._cegb_used = None
        self._cegb_pen_cache = None
        self._cegb_lazy = None
        self._cegb_U = None     # device [n_pad, F_pad] bool, lazy init
        if self.has_cegb and coupled:
            arr = np.zeros(self.F_pad, dtype=np.float32)
            for i, f in enumerate(self.train_set.used_features):
                if f < len(coupled):
                    arr[i] = float(coupled[f])
            self._cegb_coupled = arr * float(config.cegb_tradeoff)
            self._cegb_used = np.zeros(self.F_pad, dtype=bool)
        if self.has_cegb and any(lazy):
            if (self.mesh is not None or self.has_bundles
                    or getattr(self.objective, "has_pos_state", False)):
                log.fatal("cegb_penalty_feature_lazy requires the "
                          "serial single-device learner without EFB "
                          "bundling or position-state objectives")
            arr = np.zeros(self.F_pad, dtype=np.float32)
            for i, f in enumerate(self.train_set.used_features):
                if f < len(lazy):
                    arr[i] = float(lazy[f])
            self._cegb_lazy = jnp.asarray(
                arr * float(config.cegb_tradeoff))

        # ---- forced splits (forcedsplits_filename; ForceSplits in
        # serial_tree_learner.cpp — UNVERIFIED): JSON tree flattened
        # into a preorder table applied one entry per growth round ----
        self._forced_dev = None
        self._n_forced = 0
        fs_path = str(config.forcedsplits_filename or "").strip()
        if fs_path:
            if self.mesh is not None or self.has_bundles:
                log.warning("forcedsplits_filename requires the serial "
                            "learner and no EFB bundles; ignoring "
                            "forced splits")
            else:
                self._load_forced_splits(fs_path)

        self.use_pallas = F > 0 and capabilities.pallas_histogram_runs(
            self.B, config.tpu_double_precision_hist)
        self.data = _DeviceData(self.train_set, rows_per_block, self.mesh,
                                transposed=self.use_pallas,
                                shard_features=self._shard_features,
                                # the bundled matrix is NARROWER than F —
                                # never pad it back to logical width
                                n_feature_pad=(0 if self.has_bundles
                                               else self.F_pad),
                                binned_override=self._bundled_binned,
                                n_layout=n_rows_layout)

        # ---- leaf-ordered device row partition (tpu_hist_partition;
        # ops/partition.py): rows ride the grow-loop carry grouped by
        # leaf so each round's histogram scans only the elected
        # children's spans (siblings by pool subtraction). Trees are
        # structurally identical to the masked path (bit-exact under
        # quantized gradients). The per-round
        # repartition move costs ~2 compaction passes (docs/perf.md
        # "Partitioned histograms"), so AUTO only engages where the
        # cost model wins: the Pallas pool path over a large
        # un-compacted source, where per-round scan time is dominated
        # by its row-linear VPU one-hot term. Explicit "true" engages
        # anywhere the move machinery exists (CPU/XLA uses an exact
        # scatter move), "false" never.
        import math as _m
        self.part_rpb = _m.gcd(1024, rows_per_block)
        part_mode = str(config.tpu_hist_partition)
        # TPU without the Pallas kernels has no fast move (computed
        # scatters serialize, docs/perf.md) — partition never engages
        can_part = F > 0 and (self.use_pallas
                              or jax.default_backend() != "tpu")
        if part_mode == "true":
            if not can_part:
                log.warning(
                    "tpu_hist_partition=true needs the Pallas path on "
                    "TPU (max_bin<=255, no "
                    "tpu_double_precision_hist) or a non-TPU backend; "
                    "keeping the masked full-scan histograms")
            self.hist_partition = can_part
        elif part_mode == "false":
            self.hist_partition = False
        else:
            # the auto cost model lives in the capability table
            # (capabilities.hist_partition_auto); this block only owns
            # the warning etiquette
            engage, reason = capabilities.hist_partition_auto(
                config, self.use_pallas, self.data.n_pad)
            self.hist_partition = can_part and engage
            if can_part and not engage and reason is not None:
                big = (self.data.n_pad
                       >= capabilities.HIST_PARTITION_MIN_ROWS)
                msg = (f"tpu_hist_partition=auto: staying on masked "
                       f"histograms ({reason}); set "
                       f"tpu_hist_partition=true to force")
                # the stand-down is WARNING-visible only where the
                # partition plausibly applied (flagship-scale runs) and
                # once per process — default small/GOSS configs must
                # not pay a warning per train() for correct behavior
                if big and not _WARNED_PART_AUTO:
                    _WARNED_PART_AUTO.append(True)
                    log.warning(msg)
                else:
                    log.info(msg)
        if self.hist_partition:
            log.info("leaf-ordered row partition enabled: histograms "
                     "scan only the elected children's row spans")

        self.grow_cfg = self._make_grow_cfg()

        # ---- initial scores (BoostFromAverage, gbdt.cpp) ------------------
        # Under continuation (init_model, gbdt.cpp::ResetTrainingData with
        # existing models) the loaded forest carries the original init
        # bias in its first trees, so boost-from-average is skipped —
        # EXCEPT for RF, where every tree independently carries the bias
        # and gradients are always evaluated at the init score (rf.hpp
        # computes BoostFromAverage regardless of existing models).
        label_np = self.train_set.metadata.label
        self.init_scores = np.zeros(self.num_class, dtype=np.float64)
        if label_np is not None and self.fobj is None \
                and (init_forest is None or config.boosting == "rf"):
            if self.num_class == 1:
                w_np = self.train_set.metadata.weight
                if jax.process_count() > 1 and config.boost_from_average:
                    # multi-host: each process holds only its row shard;
                    # sync the mean statistic across processes (the
                    # reference's Network::GlobalSyncUpByMean)
                    self.init_scores[0] = \
                        self.objective.init_score_all_processes(
                            label_np, w_np)
                else:
                    self.init_scores[0] = self.objective.init_score(
                        label_np, w_np)
        self.score = self._init_score_tile(self.data)
        if init_forest is not None:
            self._load_forest(init_forest)

        # valid sets registered later via add_valid
        self.valid_data: List[_DeviceData] = []
        self.valid_scores: List[jnp.ndarray] = []
        self.valid_names: List[str] = []
        self._valid_ds: List[Dataset] = []

        # linear trees (linear_tree_learner.cpp): structures grown by the
        # standard jitted learner, leaves refined by host-side per-leaf
        # weighted ridge (learner/linear.py)
        self.linear_tree = bool(config.linear_tree)
        if self.linear_tree and self.train_set._raw_for_linear is None:
            log.fatal("linear_tree=True requires the Dataset to be "
                      "constructed with linear_tree in its params "
                      "(raw feature values must be retained)")

        self._rng_feature = np.random.RandomState(
            config.feature_fraction_seed)
        self._rng_bagging = np.random.RandomState(config.bagging_seed)
        self._bag_mask = None  # device [n_pad] or None when no bagging
        self._train_metric_names: List[str] = [m.name for m in self.metrics]
        self._build_step()

    # ------------------------------------------------------------------
    def _init_score_tile(self, dd: "_DeviceData") -> jnp.ndarray:
        """Device [n_pad, K] tile of init scores + dataset init_score."""
        s0 = np.tile(self.init_scores.astype(np.float32), (dd.n_pad, 1))
        if dd.init_score is not None:
            m = dd.init_score.size
            if m not in (dd.n, dd.n * self.num_class):
                log.fatal(f"Length of init_score ({m}) does not match "
                          f"number of data ({dd.n}) or number of data * "
                          f"num_class ({dd.n * self.num_class})")
            s0[:dd.n] += dd.init_score.reshape(dd.n, -1).astype(np.float32)
        return dd._place(s0, extra_dims=2)

    def _logical_bins(self) -> jnp.ndarray:
        """The LOGICAL binned train matrix for tree traversal (score
        rebuilds, DART dropped-tree recomputation). Under EFB the
        resident matrix is the bundled physical one, so the logical
        layout is rebuilt on first use and cached — DART needs it every
        iteration, so under EFB+DART both layouts stay resident."""
        if not self.has_bundles:
            return self.data.bins
        if getattr(self, "_logical_bins_cache", None) is None:
            binned = self.train_set.binned
            if self.data.n_pad > binned.shape[0]:
                binned = np.concatenate(
                    [binned, np.zeros((self.data.n_pad - binned.shape[0],
                                       binned.shape[1]), binned.dtype)])
            self._logical_bins_cache = self.data._place(binned,
                                                        extra_dims=2)
        return self._logical_bins_cache

    def _load_forest(self, init_forest) -> None:
        """Continuation: adopt a loaded HostModel's trees and fold their
        predictions into the training score."""
        if init_forest.num_tree_per_iteration != self.num_class:
            log.fatal(
                f"Cannot continue training: the loaded model has "
                f"{init_forest.num_tree_per_iteration} trees per iteration"
                f", the new config {self.num_class}")
        # NB: compare against the config, not self.average_output — the
        # RF subclass sets that flag only after super().__init__ returns
        if bool(init_forest.average_output) != (self.config.boosting
                                                == "rf"):
            kind = "averaged (rf)" if init_forest.average_output \
                else "additive (gbdt/dart)"
            log.fatal(
                f"Cannot continue training: the loaded model is {kind} "
                f"but boosting={self.config.boosting} — the ensemble "
                f"semantics don't compose")
        for ht in init_forest.trees:
            self.models.append(Tree.rebin(
                ht, self.train_set.bin_mappers,
                self.train_set.used_features))
        self.iter_ = len(self.models) // self.num_class
        if self.models:
            if any(getattr(t, "is_linear", False) for t in self.models):
                # linear leaves need raw features: host-side rebuild
                if self.train_set._raw_for_linear is None:
                    log.fatal("Continuing from a linear-tree model "
                              "requires linear_tree=True params")
                Xu = self.train_set._raw_for_linear
                raw_np = np.zeros((self.data.n_pad, self.num_class),
                                  dtype=np.float32)
                for i, t in enumerate(self.models):
                    raw_np[:self.data.n, i % self.num_class] += \
                        t.predict_raw(Xu)
                self.score = self.score + self.data._place(
                    raw_np, extra_dims=2)
                return
            stacked, class_idx = self._stack_models(0, len(self.models))
            raw, _ = forest_predict_binned(
                stacked, self._logical_bins(), self.feat_num_bin,
                self.feat_has_nan, class_idx, self.num_class)
            self.score = self.score + raw

    def add_valid(self, ds: Dataset, name: str) -> None:
        # feature-parallel keeps valid sets unsharded (prediction needs
        # every column); data/voting shard valid rows like train rows
        if self.linear_tree and not ds._constructed:
            ds.params.setdefault("linear_tree", True)
        self._valid_ds.append(ds)
        dd = _DeviceData(ds.construct(), self.rows_per_block,
                         None if self._shard_features else self.mesh)
        score0 = self._init_score_tile(dd)
        if self.models:
            stacked, class_idx = self._stack_models(0, len(self.models))
            raw, _ = forest_predict_binned(
                stacked, dd.bins, self.feat_num_bin, self.feat_has_nan,
                class_idx, self.num_class)
            score0 = score0 + raw
        self.valid_data.append(dd)
        self.valid_scores.append(score0)
        self.valid_names.append(name)
        # valid-set count changed: the valid_update jit closure must see it
        self._build_step()

    def _learning_rate(self) -> float:
        """Per-tree shrinkage; RF overrides to 1.0 (rf.hpp stores raw)."""
        return float(self.config.learning_rate)

    def _load_forced_splits(self, path: str) -> None:
        """Parse a forcedsplits_filename JSON tree ({"feature",
        "threshold", nested "left"/"right"}) into the preorder table
        grow_tree consumes. Numerical thresholds map to bin ids;
        CATEGORICAL entries (round 4) take "threshold" as a category
        value or list of values, binned into a goes-left bitset.
        Entries on unused features are skipped with their subtrees,
        like the reference's validity checks."""
        import json
        from ..io.binning import BIN_TYPE_CATEGORICAL
        with open(path) as f:
            spec = json.load(f)
        orig_to_used = {f: i for i, f in
                        enumerate(self.train_set.used_features)}
        W = (self.B + 31) // 32
        parents, lefts, feats, tbins, iscat, bitsets = \
            [], [], [], [], [], []

        def walk(node, parent_idx, is_left):
            if not isinstance(node, dict) or "feature" not in node:
                return
            fo = int(node["feature"])
            u = orig_to_used.get(fo)
            mapper = (self.train_set.bin_mappers[fo]
                      if fo < len(self.train_set.bin_mappers) else None)
            if u is None or mapper is None:
                log.warning(f"forced split on unused feature {fo} "
                            f"skipped (with its subtree)")
                return
            if len(parents) >= self.config.num_leaves - 1:
                log.warning("more forced splits than num_leaves-1; "
                            "extra entries ignored")
                return
            bits = np.zeros(W, np.uint32)
            if mapper.bin_type == BIN_TYPE_CATEGORICAL:
                thr = node["threshold"]
                cats = thr if isinstance(thr, (list, tuple)) else [thr]
                hit = 0
                for cv in cats:
                    b = (mapper.cat_to_bin or {}).get(int(cv))
                    if b is None:
                        log.warning(f"forced categorical split: "
                                    f"category {cv} of feature {fo} "
                                    f"was not seen at bin time; "
                                    f"ignored")
                        continue
                    bits[b >> 5] |= np.uint32(1) << np.uint32(b & 31)
                    hit += 1
                if hit == 0:
                    log.warning(f"forced categorical split on feature "
                                f"{fo} matched no known category; "
                                f"skipped (with its subtree)")
                    return
                tb = 0
                cat = True
            else:
                tb = mapper.value_to_bin(float(node["threshold"]))
                cat = False
            idx = len(parents)
            parents.append(parent_idx)
            lefts.append(bool(is_left))
            feats.append(u)
            tbins.append(tb)
            iscat.append(cat)
            bitsets.append(bits)
            walk(node.get("left"), idx, True)
            walk(node.get("right"), idx, False)

        walk(spec, -1, False)
        if parents:
            if any(iscat) and not self.has_categorical:
                # cannot happen via normal construction (cat mappers
                # imply has_categorical), but guard the invariant the
                # learner's bitset lanes rely on
                log.fatal("forced categorical splits require a dataset "
                          "with categorical features")
            self._n_forced = len(parents)
            self._forced_dev = (
                jnp.asarray(np.asarray(parents, np.int32)),
                jnp.asarray(np.asarray(lefts, bool)),
                jnp.asarray(np.asarray(feats, np.int32)),
                jnp.asarray(np.asarray(tbins, np.int32)),
                jnp.asarray(np.asarray(iscat, bool)),
                jnp.asarray(np.stack(bitsets)))
            log.info(f"applying {self._n_forced} forced split(s) at "
                     f"the top of every tree")

    def _make_grow_cfg(self) -> GrowConfig:
        config = self.config
        _hist_scatter = (self.learner_type == "data"
                         and config.tpu_hist_reduce == "scatter"
                         and not self.has_bundles)
        return GrowConfig(
            num_leaves=config.num_leaves,
            max_depth=config.max_depth,
            lambda_l1=config.lambda_l1,
            lambda_l2=config.lambda_l2,
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            max_delta_step=config.max_delta_step,
            num_bins=self.B,
            rows_per_block=self.rows_per_block,
            precise_histogram=config.tpu_double_precision_hist,
            leaf_batch=max(1, config.tpu_leaf_batch),
            use_pallas=self.use_pallas,
            # int8 histogram path: stochastic rounding can push a level
            # to qbins, so int8 needs num_grad_quant_bins <= 127; the
            # int32 accumulator must also hold qbins * n_rows without
            # wrapping (the bf16 path degrades gracefully there instead)
            int_hist=(self.use_pallas
                      and bool(config.use_quantized_grad)
                      and int(config.num_grad_quant_bins) <= 127
                      and self.data.n_pad
                      * int(config.num_grad_quant_bins) < 2**31),
            hist_col_bins=self._hist_col_bins,
            axis_name=(self.axis if self.mesh is not None
                       and not self._shard_features else ""),
            has_categorical=self.has_categorical,
            cat_positions=(self._cat_positions
                           if not (self._shard_features or _hist_scatter)
                           else ()),
            max_cat_threshold=config.max_cat_threshold,
            cat_smooth=config.cat_smooth,
            cat_l2=config.cat_l2,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_per_group=config.min_data_per_group,
            hist_scatter=_hist_scatter,
            packed_wire=bool(config.tpu_hist_packed_wire),
            num_shards=(self.mesh.devices.size
                        if self.mesh is not None else 1),
            voting=self.learner_type == "voting",
            top_k=config.top_k,
            feature_axis=(self.axis if self._shard_features else ""),
            has_monotone=self.has_monotone,
            monotone_intermediate=(
                str(config.monotone_constraints_method).lower()
                in ("intermediate", "advanced")),
            monotone_advanced=(
                str(config.monotone_constraints_method).lower()
                == "advanced"),
            monotone_penalty=config.monotone_penalty,
            has_interaction=self.has_interaction,
            has_bundles=self.has_bundles,
            partition=self.hist_partition,
            part_rpb=self.part_rpb,
            feature_fraction_bynode=config.feature_fraction_bynode,
            has_cegb=self.has_cegb,
            cegb_tradeoff=config.cegb_tradeoff,
            cegb_penalty_split=config.cegb_penalty_split,
            has_cegb_lazy=self._cegb_lazy is not None,
            path_smooth=config.path_smooth,
            extra_trees=config.extra_trees,
            extra_seed=config.extra_seed,
            has_contri=self.has_contri,
            n_forced=self._n_forced,
        )

    # ------------------------------------------------------------------
    def _build_step(self) -> None:
        obj = self.objective
        K = self.num_class
        # re-derive growth config so reset_parameter takes effect
        self.grow_cfg = self._make_grow_cfg()
        gcfg = self.grow_cfg
        lr = self._learning_rate()
        mesh = self.mesh

        needs_rng = getattr(obj, "needs_rng", False)
        self._step_state = self._step_goss_state = None
        # hist.onehot_elems a column scanned (_count_work): the rows of
        # the kernel's one-hot, from the layout the kernel itself uses;
        # the XLA fallback builds every bin of every column
        if self.use_pallas:
            from ..ops.pallas_histogram import onehot_layout
            self._hist_onehot_per_col = onehot_layout(
                gcfg.hist_col_bins, gcfg.num_bins).onehot_rows
        else:
            self._hist_onehot_per_col = \
                self.data.bins.shape[1] * gcfg.num_bins

        @obs.scope("engine/gradients")
        def gradients(score, label, weight, key):
            s = score[:, 0] if K == 1 else score
            if needs_rng:
                return obj.get_gradients(s, label, weight, key=key)
            return obj.get_gradients(s, label, weight)

        # gradient quantization (use_quantized_grad; reference:
        # cuda_gradient_discretizer.cu): grad/hess become small integer
        # levels — EXACT in the bf16 histogram matmul and int-valued on
        # the reduction wire — with stochastic rounding for unbiasedness
        use_quant = bool(self.config.use_quantized_grad)
        qbins = max(2, int(self.config.num_grad_quant_bins))
        renew_quant = bool(self.config.quant_train_renew_leaf)
        use_sr = bool(self.config.stochastic_rounding)
        glevels = max(qbins // 2, 1)
        hlevels = max(qbins - 1, 1)

        @obs.scope("engine/gradients")
        def quantize(gk_m, hk_m, mask_count, qkey):
            gmax = jnp.max(jnp.abs(gk_m))
            hmax = jnp.max(hk_m)
            if gcfg.axis_name:
                gmax = jax.lax.pmax(gmax, gcfg.axis_name)
                hmax = jax.lax.pmax(hmax, gcfg.axis_name)
            scale_g = jnp.maximum(gmax / glevels, 1e-30)
            scale_h = jnp.maximum(hmax / hlevels, 1e-30)
            if qkey is not None and use_sr:
                # stochastic_rounding=false -> deterministic nearest
                # rounding (gradient_discretizer semantics)
                kg, kh = jax.random.split(qkey)
                ng = jax.random.uniform(kg, gk_m.shape,
                                        minval=-0.5, maxval=0.5)
                nh = jax.random.uniform(kh, hk_m.shape,
                                        minval=-0.5, maxval=0.5)
            else:
                ng = nh = 0.0
            gq = jnp.round(gk_m / scale_g + ng)
            hq = jnp.round(hk_m / scale_h + nh)
            # stochastic rounding must not resurrect masked-out rows
            live = mask_count > 0
            gq = jnp.where(live, gq, 0.0)
            hq = jnp.where(live, hq, 0.0)
            scale = jnp.stack([scale_g, scale_h,
                               jnp.asarray(1.0, jnp.float32)])
            return gq, hq, scale

        @obs.scope("engine/score_update")
        def leaf_contrib(tree, leaf_id):
            """Per-row leaf_value[leaf_id] * lr. As a one-hot matmul: a
            per-row gather into a [L] table runs on the TPU scalar unit
            (~9ms/Mrow); the masked contraction is ~free on the MXU. The
            one-hot operand is O(n*L), so fall back to the gather for
            very wide trees where it would dominate HBM."""
            Lq = tree["leaf_value"].shape[0]
            if Lq <= 512:
                onehot = (leaf_id[:, None]
                          == jnp.arange(Lq, dtype=jnp.int32)[None, :])
                return jax.lax.dot_general(
                    onehot.astype(jnp.float32),
                    tree["leaf_value"][:, None],
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST)[:, 0] * lr
            return tree["leaf_value"][leaf_id] * lr

        @obs.scope("grower/leaf_values")
        def renew_leaves(tree, leaf_id, gk_m, hk_m):
            """Re-derive leaf outputs from FULL-precision sums
            (quant_train_renew_leaf)."""
            from ..ops.split import calc_leaf_output
            Lq = tree["leaf_value"].shape[0]
            oh = (leaf_id[:, None]
                  == jnp.arange(Lq, dtype=jnp.int32)[None, :])
            sums = jax.lax.dot_general(
                oh.astype(jnp.float32),
                jnp.stack([gk_m, hk_m], axis=1),
                dimension_numbers=(((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)   # [L, 2]
            if gcfg.axis_name:
                sums = jax.lax.psum(sums, gcfg.axis_name)
            renewed = calc_leaf_output(
                sums[:, 0], sums[:, 1], gcfg.lambda_l1,
                gcfg.lambda_l2, gcfg.max_delta_step)
            tree = dict(tree)
            tree["leaf_value"] = jnp.where(
                tree["leaf_count"] > 0, renewed, tree["leaf_value"])
            return tree

        @obs.scope("engine/score_update")
        def add_contrib(score, k, tree, leaf_id):
            return score.at[:, k].add(leaf_contrib(tree, leaf_id))

        def grow_all(bins, bins_t, score, g, h, mask_gh, mask_count,
                     allowed, qkey=None, cegb_pen=None, cegb_U=None):
            trees, leaf_ids = [], []
            new_score = score
            U_new = cegb_U
            if cegb_U is not None:
                # reference parity: the lazy penalty counts rows of the
                # SAMPLED partition (bagging/GOSS) — out-of-sample rows
                # are treated as fully acquired so they carry no mass
                in_sample = mask_count > 0
                U_eff = cegb_U | ~in_sample[:, None]
            for k in range(K):
                gk = g if K == 1 else g[:, k]
                hk = h if K == 1 else h[:, k]
                gk_m = gk * mask_gh
                hk_m = hk * mask_gh
                chan_scale = None
                if use_quant:
                    kq = (None if qkey is None
                          else jax.random.fold_in(qkey, k))
                    gk_q, hk_q, chan_scale = quantize(
                        gk_m, hk_m, mask_count, kq)
                    vals = jnp.stack([gk_q, hk_q, mask_count], axis=1)
                else:
                    vals = jnp.stack([gk_m, hk_m, mask_count], axis=1)
                tree, leaf_id = grow_tree(
                    bins, vals, self.feat_num_bin, self.feat_has_nan,
                    allowed, gcfg, bins_t=bins_t,
                    is_cat=self.feat_is_cat, mono=self.feat_mono,
                    groups=self.interaction_groups,
                    bundle=self._bundle_dev, chan_scale=chan_scale,
                    node_key=(None if qkey is None
                              else jax.random.fold_in(qkey, 0xB14D + k)),
                    cegb_pen=cegb_pen, contri=self.feat_contri,
                    forced=self._forced_dev,
                    lazy=(None if cegb_U is None
                          else (U_eff, self._cegb_lazy)))
                if cegb_U is not None:
                    # class-k+1's tree sees class-k's acquisitions
                    # (the reference trains per-class trees serially
                    # and marks on split application)
                    U_new = _cegb_u_fold(U_new, tree["leaf_used"],
                                         leaf_id, in_sample)
                    U_eff = U_new | ~in_sample[:, None]
                    tree = {kk: v for kk, v in tree.items()
                            if kk != "leaf_used"}
                if use_quant and renew_quant:
                    tree = renew_leaves(tree, leaf_id, gk_m, hk_m)
                new_score = add_contrib(new_score, k, tree, leaf_id)
                trees.append(tree)
                leaf_ids.append(leaf_id)
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
            return stacked, jnp.stack(leaf_ids), new_score, U_new

        def step_impl(bins, bins_t, label, weight, score, mask_gh,
                      mask_count, allowed, cegb_pen, key, cegb_U=None):
            g, h = gradients(score, label, weight, key)
            return grow_all(bins, bins_t, score, g, h, mask_gh, mask_count,
                            allowed, qkey=jax.random.fold_in(key, 0x9e37),
                            cegb_pen=cegb_pen, cegb_U=cegb_U)

        # ---- tpu_debug: checkify validation pass (SURVEY.md §5) --------
        # a separate jitted checkify program (cheap: gradients only, no
        # tree growth) so the hot step stays checkify-free
        self._debug_check = None
        if bool(self.config.tpu_debug):
            from jax.experimental import checkify

            def _dbg_impl(score, label, weight, key, pos_state):
                n_bad_s = jnp.sum(~jnp.isfinite(score))
                checkify.check(
                    n_bad_s == 0,
                    "model scores contain {n} non-finite value(s) — "
                    "non-finite labels/init_score, or a previous "
                    "iteration diverged (try a lower learning_rate)",
                    n=n_bad_s)
                if getattr(obj, "has_pos_state", False):
                    s = score[:, 0] if K == 1 else score
                    g, h, _ = obj.get_gradients(s, label, weight,
                                                pos_state=pos_state)
                else:
                    g, h = gradients(score, label, weight, key)
                n_bad_g = jnp.sum(~jnp.isfinite(g))
                n_bad_h = jnp.sum(~jnp.isfinite(h))
                n_neg_h = jnp.sum(h < 0)
                checkify.check(
                    n_bad_g == 0,
                    "objective produced {n} non-finite gradient "
                    "value(s) — check labels/init_score/custom fobj",
                    n=n_bad_g)
                checkify.check(
                    n_bad_h == 0,
                    "objective produced {n} non-finite hessian "
                    "value(s) — check labels/init_score/custom fobj",
                    n=n_bad_h)
                checkify.check(
                    n_neg_h == 0,
                    "objective produced {n} negative hessian value(s) "
                    "— leaf outputs would be unbounded", n=n_neg_h)
                return n_bad_g

            self._debug_check = jax.jit(
                checkify.checkify(_dbg_impl,
                                  errors=checkify.user_checks))
            # oob-bin audit (host-side, once): every stored bin id must
            # be < the feature's bin count. (Skipped under EFB — the
            # physical bundle columns use offset bin spaces that the
            # logical feat_num_bin does not describe.)
            _ing = self.train_set.device_ingested()
            if not self.has_bundles and (
                    _ing.n_rows if _ing is not None
                    else len(self.train_set.binned)):
                nb_host = np.asarray(self.feat_num_bin)
                if _ing is not None and getattr(
                        self.train_set, "_binned", None) is None:
                    # device-resident dataset: audit the device array
                    # (pad rows are bin 0 — never the max) instead of
                    # D2H-materializing and permanently caching a full
                    # host copy just for a check
                    F_chk = min(_ing.bins.shape[1], len(nb_host))
                    col_max = np.asarray(
                        jnp.max(_ing.bins[:, :F_chk], axis=0))
                else:
                    binned_chk = self.train_set.binned
                    F_chk = min(binned_chk.shape[1], len(nb_host))
                    col_max = binned_chk[:, :F_chk].max(axis=0)
                bad = np.nonzero(col_max >= nb_host[:F_chk])[0]
                if len(bad):
                    log.fatal(f"tpu_debug: out-of-bounds bin ids in "
                              f"feature column(s) {bad.tolist()[:8]} "
                              f"(max bin {col_max[bad[0]]} >= num_bin "
                              f"{int(nb_host[bad[0]])}) — corrupt "
                              f"binned data or mismatched bin mappers")

        top_rate = float(self.config.top_rate)
        other_rate = float(self.config.other_rate)
        # goss.hpp truncates the DOUBLE product (static_cast<data_size_t>
        # of rate * cnt); an f32 floor on device can differ by one when
        # the product lands within an f32 ulp of an integer (e.g.
        # 0.35*180). The per-shard valid counts are static (padding mask
        # only — GOSS replaces bagging), so the exact counts are
        # precomputed host-side in double and closed over as constants.
        _rows_sharded = self.mesh is not None and not self._shard_features
        # Exact counts at ANY process count (VERDICT r4 item 7): the
        # per-global-shard valid row counts are assembled host-side at
        # init — single-host directly, multi-host via one counts
        # allgather (each process contributes its local devices' counts
        # in mesh order, mirroring make_array_from_process_local_data's
        # process-contiguous chunk placement) — so the double-precision
        # truncation of goss.hpp's subset sizes holds on every shard.
        if _rows_sharded:
            _local_valid = goss_shard_valid_counts(
                self.data.n, self.data.n_pad, self.mesh.devices.size,
                jax.process_count())
        else:
            _local_valid = [self.data.n]
        goss_axis = self.axis if _rows_sharded else None
        # goss.hpp floors top_k at 1 (std::max(1, top_k)); a shard with
        # zero valid rows still selects nothing because is_top is masked
        # by the valid mask
        _k_top_list = [max(1, int(v * top_rate)) for v in _local_valid]
        _k_rand_list = [int(v * other_rate) for v in _local_valid]
        goss_k_top_tbl = jnp.asarray(_k_top_list, jnp.int32)
        goss_k_rand_tbl = jnp.asarray(_k_rand_list, jnp.int32)
        # goss.rows_in / goss.rows_kept an iteration (_count_work): the
        # rows the thresholds rank and the exact count they keep
        self._goss_rows = (
            sum(_local_valid),
            sum(min(kt, v) + min(kr, max(v - kt, 0)) for v, kt, kr
                in zip(_local_valid, _k_top_list, _k_rand_list)))

        @obs.scope("engine/goss_sample")
        def goss_masks(g, h, valid_mask, key):
            """GOSS (goss.hpp): keep top-a by |g*h|, sample b of the rest,
            amplify the sampled rest by (1-a)/b. Per-shard under the mesh,
            matching the reference's per-machine local bagging."""
            metric = jnp.abs(g * h)
            if K > 1:
                metric = jnp.sum(metric, axis=1)
            metric = metric * valid_mask
            n_local = metric.shape[0]
            n_valid = jnp.sum(valid_mask)
            sid = (jax.lax.axis_index(goss_axis)
                   if goss_axis is not None else 0)
            k_top = goss_k_top_tbl[sid]
            k_rand = goss_k_rand_tbl[sid].astype(jnp.float32)
            k_rest = jnp.maximum(n_valid - k_top, 1.0)
            # the k_top-th largest metric, == sort(metric)[n_local -
            # k_top], by a counting select: no row is ordered
            thresh = kth_largest(metric, k_top)
            # EXACT top-k (goss.hpp partitions exactly k rows): ties at
            # the threshold break by row index via a cumulative count,
            # so the selected count is deterministic — required both for
            # reference parity and so the compact path's fixed buffer
            # (tpu_goss_compact) can never truncate
            valid = valid_mask > 0
            above = (metric > thresh) & valid
            k_need = k_top - jnp.sum(above).astype(jnp.int32)
            tie = (metric == thresh) & valid
            tie_rank = jnp.cumsum(tie.astype(jnp.int32))
            is_top = above | (tie & (tie_rank <= k_need))
            rest = valid & ~is_top
            # EXACT-size uniform sample of the rest (goss.hpp samples a
            # fixed-size subset): keep the k_cap smallest uniform draws
            # among rest rows — unbiased in row position, unlike a
            # Bernoulli draw truncated by prefix. Ties in the k-th draw
            # break by row index via the same cumulative-count trick as
            # the top-k side.
            k_cap = jnp.minimum(k_rand, k_rest).astype(jnp.int32)
            u = jnp.where(rest, jax.random.uniform(key, (n_local,)),
                          jnp.inf)
            # the k_cap-th SMALLEST draw; k_cap = 0 reads the minimum
            # (picked is force-emptied by the k_cap > 0 mask below)
            u_thresh = kth_smallest(u, k_cap)
            strictly = rest & (u < u_thresh)
            at_t = rest & (u == u_thresh)
            need = k_cap - jnp.sum(strictly).astype(jnp.int32)
            at_rank = jnp.cumsum(at_t.astype(jnp.int32))
            picked = (strictly | (at_t & (at_rank <= need))) & (k_cap > 0)
            amp = (1.0 - top_rate) / max(other_rate, 1e-12)
            mask_gh = (is_top.astype(jnp.float32)
                       + picked.astype(jnp.float32) * amp)
            mask_count = (is_top | picked).astype(jnp.float32)
            return mask_gh, mask_count

        self._goss_masks = goss_masks

        def step_goss_impl(bins, bins_t, label, weight, score, valid_mask,
                           allowed, cegb_pen, key, cegb_U=None):
            kg, km = jax.random.split(key)
            g, h = gradients(score, label, weight, kg)
            mask_gh, mask_count = goss_masks(g, h, valid_mask, km)
            return grow_all(bins, bins_t, score, g, h, mask_gh, mask_count,
                            allowed, qkey=jax.random.fold_in(key, 0x9e37),
                            cegb_pen=cegb_pen, cegb_U=cegb_U)

        def step_custom_impl(bins, bins_t, score, g, h, mask_gh,
                             mask_count, allowed, cegb_pen, key,
                             cegb_U=None):
            return grow_all(bins, bins_t, score, g, h, mask_gh, mask_count,
                            allowed, qkey=key, cegb_pen=cegb_pen,
                            cegb_U=cegb_U)

        # ---- GOSS histogram-only compaction (tpu_goss_compact) ---------
        # The masked formulation scans ALL rows with zero weights; the
        # reference's GOSS scans only the sampled subset
        # (goss.hpp bag_data_indices_). Here: ONE lax.sort moves the
        # sampled rows into a fixed-size front buffer (static n_sub >=
        # worst-case sample), HISTOGRAMS scan only that buffer, and the
        # one-hot score update stays exactly as in the masked path; the
        # full-row leaf ids it reads are routed once a tree, after the
        # grower's loop (learner/serial.py `defer_full`, PERF.md §6 PR
        # 32: routing them at every loop trip was a quarter of an
        # iteration). Sample choice is bit-identical to the
        # masked path (same RNG stream); histogram float sums may
        # differ only in accumulation order (exact in quantized mode).
        renews_obj = (type(obj).renew_tree_output
                      is not Objective.renew_tree_output)
        # Round 3 compacted via ONE multi-operand lax.sort, whose
        # superlinear compile cost gated it to F <= ~32 packed columns.
        # Round 4 replaced the sort with the Pallas row-compaction
        # kernel (ops/compact.py): per-block permutation matmuls at any
        # width (~5 ms vs 13 ms at 1M x 28, and Bosch F=200 / Criteo /
        # MSLR widths now compact too — docs/perf.md "Row compaction
        # kernel").
        import math as _math
        from ..ops.compact import (compact_rows, compact_rows_xla,
                                   compaction_out_cols, plan_compaction)
        # compaction block size: 1024 where it divides n_pad (the
        # kernel's best on the chip, PERF.md §6 PR 35; n_pad is a
        # rows_per_block multiple, itself a multiple of 256: R_c is
        # 256, 512 or 1024)
        R_c = _math.gcd(1024, gcfg.rows_per_block)
        frac = top_rate + other_rate
        n_sub = compaction_out_cols(
            int(np.ceil(self.data.n_pad * frac)) + 8192,
            R_c, gcfg.rows_per_block)
        use_goss_compact = (bool(self.config.tpu_goss_compact)
                           and self.config.data_sample_strategy == "goss"
                           and mesh is None and not self.has_bundles
                           and not self.linear_tree and not renews_obj
                           and not (use_quant and renew_quant)
                           and not getattr(obj, "has_pos_state", False)
                           and top_rate + other_rate < 1.0
                           # the compacted buffer (sampled rows + write
                           # slack) must genuinely shrink the scan; tiny
                           # datasets / near-1.0 fractions keep the
                           # masked path (also guarantees the kernel's
                           # write windows never clamp = never drop a
                           # sampled row)
                           and n_sub < self.data.n_pad
                           # the XLA scatter fallback serializes ON TPU
                           # (docs/perf.md) — without the Pallas path
                           # (max_bin>256 / tpu_double_precision_hist)
                           # keep the masked scan
                           and (self.use_pallas
                                or jax.default_backend() != "tpu"))
        self._use_goss_compact = use_goss_compact

        # ---- buffer donation (tpu_donate; docs/perf.md "Iteration
        # floor"): the r5 trace pins ~9% of device busy on loop-state
        # %copy — donate the carries so XLA aliases them in place.
        # The [n_pad, K] score carry is donation-safe only when no
        # host path re-reads the PRE-step buffer after dispatch:
        # leaf-output renewal reads the old score for its percentile
        # refit, linear leaves read score_pre in _apply_linear_fit,
        # and DART/RF blend with held pre-step score/valid buffers
        # (those engines set _donate_carries=False).
        from ..utils.debug import donation_enabled, donation_guard
        _donate = donation_enabled(self.config)
        _donate_score = (_donate and self._donate_carries
                         and not renews_obj and not self.linear_tree)
        _donate_valid = _donate and self._donate_carries
        _dbg_checks = bool(self.config.tpu_debug_checks)

        def _jit_don(fn, don, site):
            # jit with donation; tpu_debug_checks wraps DONATING jits
            # in the use-after-donate guard — a jit that donates
            # nothing cannot use-after-donate, and wrapping it would
            # only misattribute an unrelated deleted-array error to
            # this site (plus pay a per-call leaf scan for nothing)
            j = jax.jit(fn, donate_argnums=don)
            return donation_guard(j, site) if (don and _dbg_checks) \
                else j
        if use_goss_compact:
            dd = self.data
            n_full = dd.n_pad

            def step_goss_compact_impl(bins, bins_t, label, weight,
                                       valid_mask, score, allowed,
                                       cegb_pen, key, cegb_U=None):
                kg, km = jax.random.split(key)
                g, h = gradients(score, label, weight, kg)
                mask_gh, mask_count = goss_masks(g, h, valid_mask, km)
                sel = mask_count > 0
                # TPU note: jnp.nonzero / gathers at computed indices
                # lower to serialized scatter/slice loops (~1s at 1M
                # rows). The compaction kernel moves the sampled rows
                # into a fixed-size front buffer with per-block one-hot
                # permutation matmuls instead; grad/hess/masks ride as
                # value channels of the same kernel call.
                with obs.scope("engine/goss_compact"):
                    g2 = g if K > 1 else g[:, None]
                    h2 = h if K > 1 else h[:, None]
                    vals_all = jnp.concatenate(
                        [g2.T, h2.T, mask_gh[None], mask_count[None]],
                        axis=0).astype(jnp.float32)       # [2K+2, n]
                    dest, algn, rem, nch = plan_compaction(sel, R_c,
                                                           n_sub)
                    if bins_t is not None:
                        bins_t_c, vc = compact_rows(
                            bins_t, vals_all, dest, algn, rem, nch,
                            out_cols=n_sub, rows_per_block=R_c)
                        # int8 -> uint8 reinterpret restores bin values
                        # for the row-major partition path
                        bins_c = bins_t_c.T.astype(bins.dtype)
                    else:
                        bt_any, vc = compact_rows_xla(
                            bins.T, vals_all, dest, algn, rem,
                            out_cols=n_sub, rows_per_block=R_c)
                        bins_c = bt_any.T
                        bins_t_c = None
                    # one-hot destination rows built, blocks handed
                    compact_work = {
                        "compact_onehot_rows": 128 * jnp.sum(nch),
                        "compact_blocks": jnp.array(nch.shape[0], jnp.int32)}
                    g_c = vc[:K].T
                    h_c = vc[K:2 * K].T
                    mgh_c = vc[2 * K]
                    mc_c = vc[2 * K + 1]
                qkey = jax.random.fold_in(key, 0x9e37)
                import dataclasses as _dc
                gcfg_c = _dc.replace(gcfg, hist_compact=True)
                trees, leaf_ids = [], []
                new_score = score
                U_new = cegb_U
                if cegb_U is not None:
                    in_sample = sel
                    U_eff = cegb_U | ~in_sample[:, None]
                for k in range(K):
                    with obs.scope("engine/goss_compact"):
                        gk = g_c[:, k] * mgh_c
                        hk = h_c[:, k] * mgh_c
                    chan_scale = None
                    if use_quant:
                        kq = jax.random.fold_in(qkey, k)
                        gk, hk, chan_scale = quantize(gk, hk, mc_c, kq)
                    vals_c = jnp.stack([gk, hk, mc_c], axis=1)
                    tree, leaf_id = grow_tree(
                        bins, vals_c, self.feat_num_bin,
                        self.feat_has_nan, allowed, gcfg_c,
                        bins_t=bins_t, is_cat=self.feat_is_cat,
                        mono=self.feat_mono,
                        groups=self.interaction_groups,
                        chan_scale=chan_scale,
                        node_key=jax.random.fold_in(qkey, 0xB14D + k),
                        cegb_pen=cegb_pen, contri=self.feat_contri,
                        compact=(bins_c, bins_t_c, vals_c),
                        forced=self._forced_dev,
                        lazy=(None if cegb_U is None
                              else (U_eff, self._cegb_lazy)))
                    if cegb_U is not None:
                        U_new = _cegb_u_fold(U_new, tree["leaf_used"],
                                             leaf_id, in_sample)
                        U_eff = U_new | ~in_sample[:, None]
                        tree = {kk: v for kk, v in tree.items()
                                if kk != "leaf_used"}
                    # FULL leaf ids: routed once after the loop, through
                    # the finished tree (in the loop under lazy CEGB); the
                    # score update is the same one-hot matmul as the
                    # masked path
                    new_score = add_contrib(new_score, k, tree, leaf_id)
                    # the iteration's one compaction, by the plan's own
                    # account (the compact.* counters), on class 0's tree
                    tree = dict(tree, **(
                        compact_work if k == 0
                        else jax.tree.map(jnp.zeros_like, compact_work)))
                    trees.append(tree)
                    leaf_ids.append(leaf_id)
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
                return stacked, jnp.stack(leaf_ids), new_score, U_new

            # donate cegb_U so the lazy-acquisition matrix updates in
            # place ([n_pad, F_pad] bool — 2.5 GB at 10M x 256) instead
            # of holding two copies across the step, plus the score
            # carry when nothing re-reads it (tpu_donate)
            _don_c = (((9,) if _donate else ())
                      + ((5,) if _donate_score else ()))
            _compact_j = _jit_don(step_goss_compact_impl, _don_c,
                                  "the GOSS-compact step's donated "
                                  "score")

            def _step_goss_compact(score, allowed, cegb_pen, key):
                return _compact_j(dd.bins, dd.bins_t, dd.label,
                                  dd.weight, dd.valid_mask, score,
                                  allowed, cegb_pen, key,
                                  self._cegb_U_arg())

            self._step_goss_compact = _step_goss_compact
        else:
            self._step_goss_compact = None

        @obs.scope("engine/valid_update")
        def valid_update_impl(valid_bins_scores, stacked_trees):
            # apply this iteration's K trees to each valid set's raw scores
            out = []
            for bins, vscore in valid_bins_scores:
                new = vscore
                for k in range(K):
                    tree_k = jax.tree.map(lambda a, k=k: a[k],
                                          stacked_trees)
                    vals, _ = tree_predict_binned(
                        tree_k, bins, self.feat_num_bin, self.feat_has_nan)
                    new = new.at[:, k].add(vals * lr)
                out.append(new)
            return out

        # NOTE on jit boundaries: device arrays CLOSED OVER by a jitted
        # function are embedded into the lowered HLO as constants, so the
        # (remote) compile payload grows with the dataset. Every step jit
        # below therefore takes the big arrays as ARGUMENTS; thin Python
        # wrappers supply them per call (no transfer cost — they are
        # device-resident).
        # valid scores are a pure carry on the engines that donate
        # (every reader sees only the reassigned list): donate them so
        # each per-iteration valid update aliases in place too
        _valid_update_j = _jit_don(
            lambda vbins, valid_scores, stacked_trees: valid_update_impl(
                list(zip(vbins, valid_scores)), stacked_trees),
            (1,) if _donate_valid else (),
            "the valid-update's donated scores")

        def plain_valid_update(valid_scores, stacked_trees):
            vbins = tuple(self.valid_data[i].bins
                          for i in range(len(valid_scores)))
            return _valid_update_j(vbins, tuple(valid_scores),
                                   stacked_trees)

        if mesh is None:
            d = self.data
            _step_j = _jit_don(
                step_impl,
                (((10,) if _donate else ())
                 + ((4,) if _donate_score else ())),
                "the step's donated score")
            _goss_j = _jit_don(
                step_goss_impl,
                (((9,) if _donate else ())
                 + ((4,) if _donate_score else ())),
                "the GOSS step's donated score")
            _custom_j = _jit_don(
                step_custom_impl,
                (((10,) if _donate else ())
                 + ((2,) if _donate_score else ())),
                "the custom-fobj step's donated score")

            def step(score, mask_gh, mask_count, allowed, cegb_pen, key):
                return _step_j(d.bins, d.bins_t, d.label, d.weight, score,
                               mask_gh, mask_count, allowed, cegb_pen,
                               key, self._cegb_U_arg())

            def step_goss(score, allowed, cegb_pen, key):
                return _goss_j(d.bins, d.bins_t, d.label, d.weight,
                               score, d.valid_mask, allowed, cegb_pen,
                               key, self._cegb_U_arg())

            def step_custom(score, g, h, mask_gh, mask_count, allowed,
                            cegb_pen, key):
                return _custom_j(d.bins, d.bins_t, score, g, h,
                                 mask_gh, mask_count, allowed, cegb_pen,
                                 key, self._cegb_U_arg())

            if getattr(obj, "has_pos_state", False):
                # stateful objective: gradients also return updated
                # position-bias state, threaded by train_one_iter
                def grads_state(score, label, weight, pos_state):
                    s = score[:, 0] if K == 1 else score
                    return obj.get_gradients(s, label, weight,
                                             pos_state=pos_state)

                def _state_impl(bins, bins_t, label, weight, score,
                                mask_gh, mask_count, allowed, cegb_pen,
                                key, pos_state):
                    g, h, new_state = grads_state(score, label, weight,
                                                  pos_state)
                    stacked, lids, ns, _ = grow_all(
                        bins, bins_t, score, g, h, mask_gh,
                        mask_count, allowed,
                        qkey=jax.random.fold_in(key, 0x9e37),
                        cegb_pen=cegb_pen)
                    return stacked, lids, ns, new_state

                def _goss_state_impl(bins, bins_t, label, weight, score,
                                     valid_mask, allowed, cegb_pen, key,
                                     pos_state):
                    kg, km = jax.random.split(key)
                    g, h, new_state = grads_state(score, label, weight,
                                                  pos_state)
                    mask_gh, mask_count = goss_masks(g, h, valid_mask,
                                                     km)
                    stacked, lids, ns, _ = grow_all(
                        bins, bins_t, score, g, h, mask_gh,
                        mask_count, allowed,
                        qkey=jax.random.fold_in(key, 0x9e37),
                        cegb_pen=cegb_pen)
                    return stacked, lids, ns, new_state

                _don_st = (4,) if _donate_score else ()
                _state_j = _jit_don(_state_impl, _don_st,
                                    "the stateful step's donated score")
                _goss_state_j = _jit_don(
                    _goss_state_impl, _don_st,
                    "the stateful GOSS step's donated score")

                def step_state(score, mask_gh, mask_count, allowed,
                               cegb_pen, key, pos_state):
                    return _state_j(d.bins, d.bins_t, d.label, d.weight,
                                    score, mask_gh, mask_count, allowed,
                                    cegb_pen, key, pos_state)

                def step_goss_state(score, allowed, cegb_pen, key,
                                    pos_state):
                    return _goss_state_j(d.bins, d.bins_t, d.label,
                                         d.weight, score, d.valid_mask,
                                         allowed, cegb_pen, key,
                                         pos_state)

                self._step_state = step_state
                self._step_goss_state = step_goss_state

            valid_update = plain_valid_update
        else:
            # SPMD distributed: data/voting shard rows over the mesh axis
            # (histograms psum / psum_scatter / vote-reduce inside
            # grow_tree per GrowConfig); feature-parallel shards COLUMNS,
            # replicating rows, with the split search sliced per device
            # and the winner elected by all_gather. Tree decisions end up
            # replicated either way — mirroring the reference parallel
            # learners' global sync (SURVEY.md §3.4) without any
            # per-split host round-trip.
            # check_vma=False: the varying-manual-axes checker cannot
            # trace through grow_tree's nested jit + Pallas call (tested:
            # TypeError in the histogram scan); replication correctness
            # is covered instead by the serial-equivalence tests at
            # rtol=1e-4 under precise histograms
            # (tests/test_distributed.py).
            from ..parallel.mesh import P, shard_map
            d = self.data
            ax = self.axis
            rep = P()
            if self._shard_features:
                row2 = rep          # rows replicated
                row1 = rep
                bins_spec = P(None, ax)     # [n, F] columns sharded
                bt_spec = P(ax, None)       # [F, n]
                leaf_id_spec = rep
            else:
                row2 = P(ax, None)
                row1 = P(ax)
                bins_spec = row2
                bt_spec = P(None, ax)       # [F, n] sharded over rows
                leaf_id_spec = P(None, ax)
            tree_keys = ["num_leaves", "split_feature", "threshold_bin",
                         "default_left", "left_child", "right_child",
                         "split_gain", "internal_value", "internal_count",
                         "leaf_value", "leaf_count", "leaf_weight",
                         # the grower's work counts: rows scanned are
                         # psum'd inside grow_tree, calls and slots are
                         # the same on every shard
                         "hist_rows", "hist_calls", "hist_slots_filled",
                         "route_rows"]
            if self.has_categorical:
                tree_keys += ["is_cat", "cat_bitset"]
            if self.hist_partition:
                tree_keys += ["move_calls", "move_rows", "full_scans"]
            tree_specs = {k: rep for k in tree_keys}
            # 4th output = cegb_U (always None under mesh — lazy CEGB
            # requires the serial learner; the spec matches structure
            # only, None carries no leaves)
            out_specs = (tree_specs, leaf_id_spec, row2, None)

            w_spec = rep if d.weight is None else row1
            sharded_step = shard_map(
                step_impl, mesh=mesh,
                in_specs=(bins_spec, bt_spec, row1, w_spec, row2, row1,
                          row1, rep, rep, rep),
                out_specs=out_specs, check_vma=False)
            sharded_goss = shard_map(
                step_goss_impl, mesh=mesh,
                in_specs=(bins_spec, bt_spec, row1, w_spec, row2, row1,
                          rep, rep, rep),
                out_specs=out_specs, check_vma=False)
            grad_spec = row2 if K > 1 else row1
            sharded_custom = shard_map(
                step_custom_impl, mesh=mesh,
                in_specs=(bins_spec, bt_spec, row2, grad_spec, grad_spec,
                          row1, row1, rep, rep, rep),
                out_specs=out_specs, check_vma=False)

            # the sharded score carry donates like the serial one: the
            # mesh-sharded [n_pad, K] global array aliases shard-wise
            _sh_step_j = _jit_don(
                sharded_step, (4,) if _donate_score else (),
                "the sharded step's donated score")
            _sh_goss_j = _jit_don(
                sharded_goss, (4,) if _donate_score else (),
                "the sharded GOSS step's donated score")
            _sh_custom_j = _jit_don(
                sharded_custom, (2,) if _donate_score else (),
                "the sharded custom-fobj step's donated score")

            def step(score, mask_gh, mask_count, allowed, cegb_pen, key):
                return _sh_step_j(d.bins, d.bins_t, d.label, d.weight,
                                  score, mask_gh, mask_count, allowed,
                                  cegb_pen, key)

            def step_goss(score, allowed, cegb_pen, key):
                return _sh_goss_j(d.bins, d.bins_t, d.label, d.weight,
                                  score, d.valid_mask, allowed,
                                  cegb_pen, key)

            def step_custom(score, g, h, mask_gh, mask_count, allowed,
                            cegb_pen, key):
                return _sh_custom_j(d.bins, d.bins_t, score, g, h,
                                    mask_gh, mask_count, allowed,
                                    cegb_pen, key)

            if self._shard_features:
                # feature-parallel valid sets are replicated (prediction
                # needs all columns); plain jit, no shard_map
                valid_update = plain_valid_update
            else:
                def _sh_valid_impl(valid_scores, stacked_trees):
                    n_valid = len(valid_scores)
                    fn = shard_map(
                        lambda bins_scores, trees: tuple(valid_update_impl(
                            list(bins_scores), trees)),
                        mesh=mesh,
                        in_specs=(tuple((row2, row2)
                                        for _ in range(n_valid)),
                                  tree_specs),
                        out_specs=tuple(row2 for _ in range(n_valid)),
                        check_vma=False)
                    pairs = tuple((self.valid_data[i].bins, s)
                                  for i, s in enumerate(valid_scores))
                    return list(fn(pairs, stacked_trees))

                valid_update = _jit_don(
                    _sh_valid_impl, (0,) if _donate_valid else (),
                    "the sharded valid-update's donated scores")

        @jax.jit
        @obs.scope("engine/score_update")
        def apply_renewed(score, leaf_ids, renewed_leaf_values):
            # re-apply renewed leaf outputs: score = score + lr * renewed
            for k in range(K):
                contrib = renewed_leaf_values[k][leaf_ids[k]] * lr
                score = score.at[:, k].add(contrib)
            return score

        # ---- fused multi-iteration chunk (one dispatch per n iters) ----
        # Every jit dispatch costs host time and a host<->device sync
        # before the next iteration's arguments exist; scanning the whole
        # boosting step pays that once per chunk (how much it buys on
        # today's chip is to be re-measured). Only the pure-jit path
        # qualifies (checked in train_chunk). Keyed by the bare goss_now
        # bool train_chunk looks up.
        self._chunk_cache: Dict[bool, Callable] = {}
        F = self.num_features

        def make_chunk(goss: bool):
            allowed_all = jnp.asarray(np.arange(self.F_pad) < F)
            d_ = self.data

            def chunk_impl(bins, bins_t, label, weight, score, valid_mask,
                           keys):
                def body(sc, bkey):
                    # lazy CEGB is chunk-ineligible (can_fuse_iters),
                    # so the steps' cegb_U output is always None here
                    if goss and use_goss_compact:
                        stacked, _lid, ns, _ = step_goss_compact_impl(
                            bins, bins_t, label, weight, valid_mask,
                            sc, allowed_all, None, bkey)
                    elif goss:
                        stacked, _lid, ns, _ = step_goss_impl(
                            bins, bins_t, label, weight, sc, valid_mask,
                            allowed_all, None, bkey)
                    else:
                        stacked, _lid, ns, _ = step_impl(
                            bins, bins_t, label, weight, sc, valid_mask,
                            valid_mask, allowed_all, None, bkey)
                    return ns, stacked
                return jax.lax.scan(body, score, keys)

            # the chunk carry donates whenever the per-step score does
            # (can_fuse_iters already excludes every host re-reader):
            # without it the [n_pad, K] score rides an H2H copy through
            # EVERY chunk even though the per-step jits alias theirs
            if mesh is None:
                _chunk_j = _jit_don(
                    chunk_impl, (4,) if _donate_score else (),
                    "the fused chunk's donated score")

                def chunk(score, keys):
                    return _chunk_j(d_.bins, d_.bins_t, d_.label,
                                    d_.weight, score, d_.valid_mask,
                                    keys)
                return chunk

            sharded_chunk = shard_map(
                chunk_impl, mesh=mesh,
                in_specs=(bins_spec, bt_spec, row1, w_spec, row2, row1,
                          rep),
                out_specs=(row2, tree_specs), check_vma=False)

            _sh_chunk_j = _jit_don(
                sharded_chunk, (4,) if _donate_score else (),
                "the sharded fused chunk's donated score")

            def chunk(score, keys):
                return _sh_chunk_j(d_.bins, d_.bins_t, d_.label,
                                   d_.weight, score, d_.valid_mask,
                                   keys)
            return chunk

        self._make_chunk = make_chunk

        self._step = step
        self._step_goss = step_goss
        self._step_custom = step_custom
        self._valid_update = valid_update
        self._apply_renewed = apply_renewed

    # ------------------------------------------------------------------
    def _cegb_U_arg(self) -> Optional[jnp.ndarray]:
        """Device [n_pad, F_pad] per-row feature-acquisition matrix for
        the lazy CEGB penalty; padding rows start fully acquired so
        they never contribute penalty mass."""
        if self._cegb_lazy is None:
            return None
        if self._cegb_U is None:
            m = np.zeros((self.data.n_pad, self.F_pad), bool)
            m[self.data.n:] = True
            self._cegb_U = jnp.asarray(m)
        return self._cegb_U

    def _cegb_pen(self) -> Optional[jnp.ndarray]:
        """Per-feature coupled CEGB penalty ([F_pad]); zero for features
        the model already uses. None when CEGB is off (the split-cost
        part is static in GrowConfig)."""
        if self._cegb_coupled is None:
            return None
        if self._cegb_pen_cache is None:
            self._cegb_pen_cache = jnp.asarray(
                np.where(self._cegb_used, 0.0, self._cegb_coupled)
                .astype(np.float32))
        return self._cegb_pen_cache

    def _feature_mask(self) -> jnp.ndarray:
        F = self.num_features
        frac = self.config.feature_fraction
        mask = np.zeros(self.F_pad, dtype=bool)
        if frac >= 1.0 or F == 0:
            mask[:F] = True
        else:
            k = max(1, int(np.ceil(F * frac)))
            chosen = self._rng_feature.choice(F, size=k, replace=False)
            mask[chosen] = True
        return jnp.asarray(mask)

    def _bagging_masks(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (mask_gh, mask_count) incorporating row validity."""
        c = self.config
        d = self.data
        use_bagging = (c.bagging_freq > 0
                       and (c.bagging_fraction < 1.0
                            or c.pos_bagging_fraction < 1.0
                            or c.neg_bagging_fraction < 1.0))
        if not use_bagging:
            return d.valid_mask, d.valid_mask
        if self._bag_mask is None or self.iter_ % c.bagging_freq == 0:
            n = d.n
            label = None
            if (c.pos_bagging_fraction < 1.0
                    or c.neg_bagging_fraction < 1.0):
                label = np.asarray(self.train_set.metadata.label)
                pos = label > 0
                keep = np.zeros(n, dtype=np.float32)
                keep[pos] = (self._rng_bagging.rand(int(pos.sum()))
                             < c.pos_bagging_fraction)
                keep[~pos] = (self._rng_bagging.rand(int((~pos).sum()))
                              < c.neg_bagging_fraction)
            else:
                keep = (self._rng_bagging.rand(n)
                        < c.bagging_fraction).astype(np.float32)
            full = np.zeros(d.n_pad, dtype=np.float32)
            full[:n] = keep
            self._bag_mask = d._place(full)
        return self._bag_mask, self._bag_mask

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> None:
        """One boosting iteration (optionally with custom fobj grads)."""
        score_pre = self.score       # gradient point (linear-leaf refit)
        allowed = self._feature_mask()
        key = jax.random.PRNGKey(self.config.objective_seed + self.iter_)
        # GOSS kicks in after 1/learning_rate iterations (goss.hpp keeps
        # the first iterations un-subsampled)
        goss_active = (
            self.config.data_sample_strategy == "goss" and grad is None
            and self.iter_ >= int(1.0 / max(self.config.learning_rate,
                                            1e-6)))
        if self._debug_check is not None:
            from jax.experimental import checkify as _checkify
            if grad is not None:
                # custom-fobj grads arrive host-side: validate directly
                for nm, a in (("gradient", grad), ("hessian", hess)):
                    bad = int(np.sum(~np.isfinite(np.asarray(a))))
                    if bad:
                        log.fatal(
                            f"tpu_debug at iteration {self.iter_}: "
                            f"custom fobj produced {bad} non-finite "
                            f"{nm} value(s)")
            else:
                err, _ = self._debug_check(
                    self.score, self.data.label, self.data.weight, key,
                    self._pos_state)
                try:
                    err.throw()
                except _checkify.JaxRuntimeError as e:
                    log.fatal(f"tpu_debug at iteration {self.iter_}: "
                              f"{e}")
        # the fused XLA step dispatch (gradients + grow + split + score
        # apply run as ONE device program, so the host can only time
        # the dispatch boundary, train/dispatch; completion lands in
        # train/fetch_trees where the tree arrays materialize)
        with obs.span("train/step", iteration=self.iter_):
            self._train_step(grad, hess, goss_active, allowed, key,
                             score_pre)
        self.iter_ += 1

    def _train_step(self, grad, hess, goss_active: bool, allowed, key,
                    score_pre) -> None:
        """The body of the ``train/step`` span: dispatch, valid update,
        tree fetch and the host's bookkeeping of one iteration."""
        cegb_U_new = None
        with obs.span("train/dispatch"):
            if grad is not None:
                mask_gh, mask_count = self._bagging_masks()
                g = self._pad_custom(grad)
                h = self._pad_custom(hess)
                stacked, leaf_ids, new_score, cegb_U_new = \
                    self._step_custom(
                        self.score, g, h, mask_gh, mask_count, allowed,
                        self._cegb_pen(), key)
            elif goss_active:
                if self._pos_state is not None:
                    stacked, leaf_ids, new_score, self._pos_state = \
                        self._step_goss_state(self.score, allowed,
                                              self._cegb_pen(), key,
                                              self._pos_state)
                elif self._step_goss_compact is not None:
                    stacked, leaf_ids, new_score, cegb_U_new = \
                        self._step_goss_compact(
                            self.score, allowed, self._cegb_pen(), key)
                else:
                    stacked, leaf_ids, new_score, cegb_U_new = \
                        self._step_goss(
                            self.score, allowed, self._cegb_pen(), key)
            else:
                mask_gh, mask_count = self._bagging_masks()
                if self._pos_state is not None:
                    stacked, leaf_ids, new_score, self._pos_state = \
                        self._step_state(self.score, mask_gh, mask_count,
                                         allowed, self._cegb_pen(), key,
                                         self._pos_state)
                else:
                    stacked, leaf_ids, new_score, cegb_U_new = self._step(
                        self.score, mask_gh, mask_count, allowed,
                        self._cegb_pen(), key)
        # start device->host copies of the (tiny) tree arrays immediately:
        # each synchronous transfer stalls the host until the device has
        # caught up, so issue them all async and overlap with the step
        for leaf in jax.tree.leaves(stacked):
            leaf.copy_to_host_async()
        # leaf-output renewal (L1/quantile/MAPE percentile re-fit,
        # ObjectiveFunction::RenewTreeOutput): recompute leaf values from
        # per-leaf residual percentiles of the PRE-update score, then
        # redo the score update with the renewed values
        renews = (grad is None
                  and type(self.objective).renew_tree_output
                  is not Objective.renew_tree_output)
        if renews:
            label = np.asarray(self.train_set.metadata.label)
            weight = self.train_set.metadata.weight
            old = np.asarray(self.score)[:self.data.n]
            lid = np.asarray(leaf_ids)[:, :self.data.n]
            renewed = np.stack([
                self.objective.renew_tree_output(
                    old[:, k], label, weight, lid[k],
                    self.config.num_leaves)
                for k in range(self.num_class)]).astype(np.float32)
            renewed_dev = jnp.asarray(renewed)
            stacked = dict(stacked)
            stacked["leaf_value"] = renewed_dev
            new_score = self._apply_renewed(self.score, leaf_ids,
                                            renewed_dev)
        self.score = new_score
        if self.valid_scores:
            with obs.span("train/valid_update"):
                self.valid_scores = self._valid_update(self.valid_scores,
                                                       stacked)
            # rows of the validation sets this round's trees scored
            obs.inc("valid.rows_scored",
                    float(sum(dd.n for dd in self.valid_data)), force=True)
        with obs.span("train/fetch_trees"):
            host_trees = self._fetch_tree_arrays(stacked)
        with obs.span("train/count_work"):
            host_trees = self._count_work(host_trees, goss_active)
        with obs.span("train/append_trees"):
            self._append_host_trees(host_trees)
        obs.inc("train.iterations")
        obs.heartbeat("train")
        if cegb_U_new is not None:
            # device-side acquisition fold already ran inside the step
            # (_cegb_u_fold): in-sample rows acquired their leaf-path
            # features for each class tree
            self._cegb_U = cegb_U_new
        if self.linear_tree and grad is None:
            self._apply_linear_fit(leaf_ids, score_pre)
            self._invalidate_forest_cache()   # leaves refined in place
        if self.config.tpu_debug_checks:
            # NaN/inf guard (aux failure-detection subsystem): catch
            # divergence at the iteration that produced it
            for t in self.models[-self.num_class:]:
                if not np.isfinite(t.leaf_value).all():
                    log.fatal(f"Non-finite leaf values at iteration "
                              f"{self.iter_} — check learning_rate/"
                              f"objective inputs")
            if not np.isfinite(np.asarray(self.score)).all():
                log.fatal(f"Non-finite training scores at iteration "
                          f"{self.iter_}")

    def _apply_linear_fit(self, leaf_ids, score_pre) -> None:
        """Refine the just-grown trees' leaves with per-leaf weighted
        ridge models and patch the train/valid scores with the delta
        (LinearTreeLearner semantics; learner/linear.py)."""
        from ..learner.linear import fit_linear_leaves, predict_linear
        K = self.num_class
        n = self.data.n
        Xu = self.train_set._raw_for_linear
        old = np.asarray(score_pre)[:n]
        lid = np.asarray(leaf_ids)[:, :n]
        sc = jnp.asarray(old[:, 0] if K == 1 else old)
        label = jnp.asarray(self.train_set.metadata.label)
        w = self.train_set.metadata.weight
        w = None if w is None else jnp.asarray(w)
        if getattr(self.objective, "has_pos_state", False):
            # post-update state (the pre-update state is gone by now);
            # the propensity drift between two iterations is negligible
            # for the leaf refit
            g, h, _ = self.objective.get_gradients(
                sc, label, w, pos_state=self._pos_state)
        elif getattr(self.objective, "needs_rng", False):
            # the SAME key the grown tree's gradients used
            g, h = self.objective.get_gradients(
                sc, label, w, key=jax.random.PRNGKey(
                    self.config.objective_seed + self.iter_))
        else:
            g, h = self.objective.get_gradients(sc, label, w)
        g = np.asarray(g).reshape(n, -1)
        h = np.asarray(h).reshape(n, -1)
        bag = None
        if self._bag_mask is not None:
            bag = np.asarray(self._bag_mask)[:n]
        deltas = np.zeros((self.data.n_pad, K), dtype=np.float32)
        for k in range(K):
            t = self.models[-K + k]
            # mask BOTH g and h so out-of-bag rows drop out of both
            # sides of the normal equations
            hk = h[:, k] if bag is None else h[:, k] * bag
            gk = g[:, k] if bag is None else g[:, k] * bag
            delta = fit_linear_leaves(
                t, lid[k], Xu, gk, hk, self.config.lambda_l2,
                self.config.linear_lambda, self._learning_rate())
            deltas[:n, k] = delta
        self.score = self.score + self.data._place(deltas, extra_dims=2)
        for vi, dd in enumerate(self.valid_data):
            Xv = getattr(self._valid_ds[vi], "_raw_for_linear", None)
            if Xv is None:
                if not getattr(self, "_warned_valid_linear", False):
                    log.warning(
                        "valid set was constructed without linear_tree "
                        "params; its eval metrics track constant leaves,"
                        " not the linear model")
                    self._warned_valid_linear = True
                continue
            vdeltas = np.zeros((dd.n_pad, K), dtype=np.float32)
            for k in range(K):
                t = self.models[-K + k]
                if not getattr(t, "is_linear", False):
                    continue
                leaf = t.predict_leaf_raw(Xv)
                dv = predict_linear(t, Xv, leaf) - t.leaf_value[leaf]
                vdeltas[:dd.n, k] = dv
            self.valid_scores[vi] = (self.valid_scores[vi]
                                     + dd._place(vdeltas, extra_dims=2))

    def _fetch_tree_arrays(self, stacked) -> Dict[str, np.ndarray]:
        """Device->host transfer of the stacked tree arrays: issue every
        copy async first (a synchronous transfer per array would pay
        the host<->device sync once each), then materialize."""
        for leaf in jax.tree.leaves(stacked):
            leaf.copy_to_host_async()
        return jax.tree.map(np.asarray, stacked)

    def _count_work(self, host: Dict[str, np.ndarray],
                    sampled: bool) -> Dict[str, np.ndarray]:
        """Feed the grower's own work counts of a step's or a chunk's
        trees (any leading dims) to the ``hist.*`` / ``goss.*``
        counters, labelled by the program that grew them, and return
        the tree arrays without them. Always kept (``force``): once a
        step or a chunk, a few dict lookups and adds."""
        host = dict(host)
        total = {k: float(np.sum(host.pop(k), dtype=np.float64))
                 for k in ("hist_rows", "hist_calls", "hist_slots_filled",
                           "route_rows")}
        # only the step that compacts GOSS's sample carries the first
        # two, only a tree grown under the leaf-ordered partition the rest
        compact = {k: float(np.sum(host.pop(k, 0.0), dtype=np.float64))
                   for k in ("compact_onehot_rows", "compact_blocks",
                             "move_rows", "move_calls", "full_scans")}
        cols = total["hist_rows"]
        label = int(bool(sampled))
        n_trees = host["num_leaves"].size
        for name, value in (
                # columns the calls were handed: the static buffer
                # length, or the elected spans under hist_partition
                ("hist.cols_scanned", cols),
                ("hist.cols_needed", _cols_needed(host)),
                ("hist.calls", total["hist_calls"]),
                ("hist.leaf_slots_filled", total["hist_slots_filled"]),
                # the kernel's VPU and MXU work by its own account: the
                # one-hot rows it builds for a column scanned
                ("hist.onehot_elems", cols * self._hist_onehot_per_col),
                # rows the grower's row -> leaf passes were handed, over
                # the table's rows a tree: how often the table is walked
                ("partition.rows_routed", total["route_rows"]),
                ("partition.rows_table",
                 float(self.data.n_pad) * n_trees)):
            obs.inc(name, value, force=True, sampled=label)
        # splits these trees made, and how many of them are set-splits
        n_nodes = host["num_leaves"][..., None] - 1
        obs.inc("split.chosen", float(np.sum(n_nodes)), force=True,
                sampled=label)
        if "is_cat" in host:
            live = np.arange(host["is_cat"].shape[-1]) < n_nodes
            obs.inc("split.chosen_cat",
                    float(np.sum(host["is_cat"].astype(bool) & live)),
                    force=True, sampled=label)
        if compact["compact_blocks"]:
            # one-hot destination rows compact_rows built (128 a group
            # a block fills) and the blocks it was handed
            obs.inc("compact.onehot_rows", compact["compact_onehot_rows"],
                    force=True, sampled=label)
            obs.inc("compact.blocks", compact["compact_blocks"],
                    force=True, sampled=label)
        if compact["move_calls"]:
            # rows the partition's mover was handed (the whole histogram
            # source a loop trip) and its moves (two kernel passes each)
            obs.inc("partition.rows_moved", compact["move_rows"],
                    force=True, sampled=label)
            obs.inc("partition.move_calls", compact["move_calls"],
                    force=True, sampled=label)
            # the loop trips whose children did not fit the block table
            obs.inc("partition.full_scans", compact["full_scans"],
                    force=True, sampled=label)
        if sampled:
            n_iters = n_trees // self.num_class
            rows_in, rows_kept = self._goss_rows
            obs.inc("goss.rows_in", float(rows_in * n_iters), force=True)
            obs.inc("goss.rows_kept", float(rows_kept * n_iters),
                    force=True)
        return host

    def _append_host_trees(self, host: Dict[str, np.ndarray]) -> None:
        """Append one iteration's K per-class trees (host arrays with a
        leading class dim) to the model list."""
        for k in range(self.num_class):
            arrays = {key: v[k] for key, v in host.items()}
            t = Tree.from_device(
                arrays, self._learning_rate(),
                self.train_set.bin_mappers, self.train_set.used_features)
            if self._cegb_used is not None and t.num_nodes:
                newly = np.setdiff1d(t.split_feature[:t.num_nodes],
                                     np.flatnonzero(self._cegb_used))
                if len(newly):
                    self._cegb_used[newly] = True
                    self._cegb_pen_cache = None   # refresh on next step
            if t.cat_threshold is not None:
                # uint32 words of the model's category-VALUE bitsets
                obs.inc("tree.cat_bitset_words", len(t.cat_threshold),
                        force=True)
            self.models.append(t)
        self._invalidate_forest_cache()

    def _invalidate_forest_cache(self) -> None:
        """The model list changed (or trees mutated in place): drop the
        stacked-forest device cache and bump the version every consumer
        keys on (engine predict, Booster._to_host_model)."""
        self._models_version = getattr(self, "_models_version", 0) + 1
        self._stack_cache = None
        self._shap_cache = None

    def can_fuse_iters(self) -> bool:
        """True when boosting iterations are expressible as one scanned
        device program: no custom fobj, no host-side leaf renewal, no
        host-RNG bagging, no per-tree feature sampling, no valid-set score
        carries."""
        c = self.config
        renews = (type(self.objective).renew_tree_output
                  is not Objective.renew_tree_output)
        use_bagging = (c.bagging_freq > 0
                       and (c.bagging_fraction < 1.0
                            or c.pos_bagging_fraction < 1.0
                            or c.neg_bagging_fraction < 1.0))
        return (self.fobj is None and not renews and not use_bagging
                and c.feature_fraction >= 1.0 and not self.valid_data
                and self._cegb_coupled is None
                and self._cegb_lazy is None and not self.linear_tree
                and not c.tpu_debug_checks and not c.tpu_debug
                and self._pos_state is None)

    def train_chunk(self, n_iters: int) -> None:
        """Run ``n_iters`` boosting iterations in one device dispatch
        (``lax.scan`` over the fused step). Produces the same models as
        ``n_iters`` calls of train_one_iter (same per-iter RNG keys);
        falls back to the per-iter loop when ineligible."""
        if n_iters <= 0:
            return
        c = self.config
        if n_iters == 1 or c.tpu_fuse_iters <= 1 \
                or not self.can_fuse_iters():
            for _ in range(n_iters):
                self.train_one_iter()
            return
        is_goss = c.data_sample_strategy == "goss"
        goss_start = (int(1.0 / max(c.learning_rate, 1e-6))
                      if is_goss else None)
        # fixed scan length: every distinct length is a separate XLA
        # compile (trip count is static), so run whole chunks of D and
        # finish the remainder per-iter
        D = max(2, c.tpu_fuse_iters)
        done = 0
        while done < n_iters:
            it0 = self.iter_
            goss_now = is_goss and it0 >= goss_start
            avail = n_iters - done
            if is_goss and not goss_now:
                avail = min(avail, goss_start - it0)
            if avail < D:
                for _ in range(avail):
                    self.train_one_iter()
                done += avail
                continue
            n = D
            if goss_now not in self._chunk_cache:
                self._chunk_cache[goss_now] = self._make_chunk(goss_now)
            # identical keys to train_one_iter's PRNGKey(seed + iter):
            # pack the threefry hi/lo uint32 halves explicitly, matching
            # PRNGKey's truncation behavior (hi word only under x64)
            seeds64 = (np.arange(it0, it0 + n, dtype=np.int64)
                       + np.int64(c.objective_seed)).astype(np.uint64)
            hi = ((seeds64 >> np.uint64(32)).astype(np.uint32)
                  if jax.config.jax_enable_x64
                  else np.zeros(n, np.uint32))
            keys = jnp.asarray(np.stack(
                [hi, (seeds64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)],
                axis=1))
            with obs.span("train/fused_chunk", iterations=n,
                          start=it0):
                # the call of the chunk program returns at enqueue
                with obs.span("train/dispatch"):
                    new_score, stacked = self._chunk_cache[goss_now](
                        self.score, keys)
                self.score = new_score
                with obs.span("train/fetch_trees"):
                    host = self._fetch_tree_arrays(stacked)
                with obs.span("train/count_work"):
                    host = self._count_work(host, goss_now)
                with obs.span("train/append_trees"):
                    for i in range(n):
                        self._append_host_trees(
                            {kk: v[i] for kk, v in host.items()})
            obs.inc("train.iterations", n)
            obs.heartbeat("train")
            self.iter_ += n
            done += n

    def _pad_custom(self, arr: np.ndarray) -> jnp.ndarray:
        arr = np.asarray(arr, dtype=np.float32)
        if self.num_class > 1:
            arr = arr.reshape(self.num_class, -1).T \
                if arr.ndim == 1 else arr
            out = np.zeros((self.data.n_pad, self.num_class), np.float32)
            out[:self.data.n] = arr
        else:
            out = np.zeros(self.data.n_pad, np.float32)
            out[:self.data.n] = arr.ravel()
        return jnp.asarray(out)

    # ------------------------------------------------------------------
    # fault-tolerant training state (recovery subsystem). The model
    # trees travel separately as model text; this is everything ELSE
    # that evolves across iterations and that init_model continuation
    # loses: host RNG streams, the exact score arrays, the current
    # bagging mask, CEGB acquisition state, position-bias state.
    def _rows_to_host(self, arr) -> Optional[np.ndarray]:
        """Host copy of a per-row device array: the process-LOCAL row
        chunk under a multi-process mesh (each process checkpoints its
        own shard), the full array otherwise."""
        if arr is None:
            return None
        if self.mesh is not None and jax.process_count() > 1:
            shards = {(s.index[0].start or 0): s
                      for s in arr.addressable_shards}
            return np.concatenate(
                [np.asarray(shards[k].data) for k in sorted(shards)],
                axis=0)
        return np.asarray(arr)

    def export_train_state(self) -> Dict[str, Any]:
        """Complete training state for a durable checkpoint (the model
        itself is serialized separately as model text)."""
        return {
            "engine": type(self).__name__,
            "iteration": int(self.iter_),
            # the engine's host trees travel as exact pickled copies
            # (model TEXT rounds internal_value/leaf_weight through
            # "{:g}", which would break bit-exact DART drop traversal)
            "models": list(self.models),
            "process_index": int(jax.process_index()),
            "process_count": int(jax.process_count()),
            "init_scores": self.init_scores.copy(),
            "rng_feature": self._rng_feature.get_state(),
            "rng_bagging": self._rng_bagging.get_state(),
            "bag_mask": self._rows_to_host(self._bag_mask),
            "score": self._rows_to_host(self.score),
            "valid_scores": [self._rows_to_host(s)
                             for s in self.valid_scores],
            "cegb_used": (None if self._cegb_used is None
                          else np.asarray(self._cegb_used).copy()),
            "cegb_U": (None if self._cegb_U is None
                       else np.asarray(self._cegb_U)),
            "pos_state": (None if self._pos_state is None
                          else jax.tree.map(np.asarray, self._pos_state)),
        }

    def import_train_state(self, state: Dict[str, Any]) -> bool:
        """Restore :meth:`export_train_state` output into a freshly
        constructed engine (no init_forest — the checkpoint's pickled
        trees are adopted directly). Returns True when the exact score
        arrays were restored (bit-exact resume); False when they were
        rebuilt from the restored forest (topology/shape mismatch —
        training stays correct but is no longer bit-exact vs an
        uninterrupted run)."""
        saved_engine = state.get("engine")
        if saved_engine is not None \
                and saved_engine != type(self).__name__:
            log.fatal(
                f"checkpoint was written by a {saved_engine} engine but "
                f"resume constructed {type(self).__name__} — the "
                f"boosting/tree_learner params must match the original "
                f"run")
        models = state.get("models")
        if models is None:
            log.fatal("checkpoint state holds no model trees — corrupt "
                      "or incompatible checkpoint")
        self.models = list(models)
        self._invalidate_forest_cache()
        self.iter_ = len(self.models) // self.num_class
        if int(state["iteration"]) != self.iter_:
            log.fatal(
                f"checkpoint state is for iteration "
                f"{state['iteration']} but holds "
                f"{self.iter_} iterations of trees — mismatched "
                f"checkpoint contents")
        self._rng_feature.set_state(state["rng_feature"])
        self._rng_bagging.set_state(state["rng_bagging"])
        if state.get("init_scores") is not None:
            # the checkpoint's model text is UNBIASED (no AddBias fold);
            # the bias lives here and is re-folded at the next save
            self.init_scores = np.asarray(state["init_scores"],
                                          dtype=np.float64)
        same_topo = (
            int(state.get("process_count", 1)) == jax.process_count()
            and int(state.get("process_index", 0)) == jax.process_index())
        cur = self._rows_to_host(self.score)
        sc = state.get("score")
        saved_valid = state.get("valid_scores") or []
        # valid sets are guarded like the train score: a changed valid
        # set (count or padded shape) must not silently adopt the old
        # set's accumulated predictions into this run's eval state
        valid_ok = (len(saved_valid) == len(self.valid_scores)
                    and all(v is not None and v.shape
                            == self._rows_to_host(
                                self.valid_scores[i]).shape
                            for i, v in enumerate(saved_valid)))
        restored = bool(same_topo and sc is not None
                        and sc.shape == cur.shape and valid_ok)
        if restored:
            self.score = self.data._place(sc, extra_dims=2)
            bm = state.get("bag_mask")
            self._bag_mask = (None if bm is None
                              else self.data._place(bm))
            for i, vs in enumerate(saved_valid):
                self.valid_scores[i] = self.valid_data[i]._place(
                    vs, extra_dims=2)
        else:
            log.warning(
                "checkpoint scores were saved under a different process "
                "topology, data shape, or valid-set layout; rebuilding "
                "scores from the restored model (training continues "
                "correctly but is not bit-exact vs an uninterrupted "
                "run)")
            # rebuild with the RESTORED init_scores (the checkpoint's
            # model text carries no bias of its own)
            self._recompute_scores()
        if state.get("cegb_used") is not None \
                and self._cegb_used is not None:
            self._cegb_used[:] = state["cegb_used"]
            self._cegb_pen_cache = None
        if state.get("cegb_U") is not None and self._cegb_lazy is not None:
            self._cegb_U = jnp.asarray(state["cegb_U"])
        if state.get("pos_state") is not None \
                and self._pos_state is not None:
            self._pos_state = jax.tree.map(jnp.asarray,
                                           state["pos_state"])
        return restored

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """GBDT::RollbackOneIter — drop the last iteration's trees."""
        if self.iter_ == 0:
            return
        self.models = self.models[:-self.num_class]
        self._invalidate_forest_cache()
        self.iter_ -= 1
        self._recompute_scores()

    def _recompute_scores(self) -> None:
        score = self._init_score_tile(self.data)
        if self.models:
            stacked, class_idx = self._stack_models(0, len(self.models))
            raw, _ = forest_predict_binned(
                stacked, self._logical_bins(), self.feat_num_bin,
                self.feat_has_nan, class_idx, self.num_class)
            score = score + raw
        self.score = score
        for vi, dd in enumerate(self.valid_data):
            v = self._init_score_tile(dd)
            if self.models:
                raw, _ = forest_predict_binned(
                    stacked, dd.bins, self.feat_num_bin, self.feat_has_nan,
                    class_idx, self.num_class)
                v = v + raw
            self.valid_scores[vi] = v

    # ------------------------------------------------------------------
    def _stack_models(self, start: int, num: int):
        """Stack host trees [start, start+num) into device arrays."""
        return self._stack_model_list(list(range(start, start + num)))

    def _stack_model_list(self, indices: List[int], pad_count: int = 0,
                          pad_leaves: int = 0, use_cache=None):
        """Stack an arbitrary subset of host trees into device arrays
        (DART needs non-contiguous dropped-tree subsets).

        ``pad_count``/``pad_leaves`` stabilize the stacked SHAPES so the
        consumer jit does not recompile per distinct subset: the stack is
        padded to ``pad_count`` single-leaf zero-value trees (inert under
        traversal) and every per-tree array to ``pad_leaves`` slots.

        Contiguous index ranges are memoized on the engine (the
        stacked-forest device cache, keyed by (model count+version,
        start, num, pad shape)): repeat ``predict`` calls on an
        unchanged model reuse the device-resident stack — zero host
        re-stacking, zero HBM re-upload. ``_invalidate_forest_cache``
        drops it on any model mutation; DART's random drop subsets are
        non-contiguous and bypass it."""
        if use_cache is None:
            use_cache = bool(getattr(self.config, "tpu_predict_cache",
                                     True))
        key = None
        if (use_cache and indices
                and list(indices) == list(range(indices[0],
                                                indices[0] + len(indices)))):
            key = (indices[0], len(indices), int(pad_count),
                   int(pad_leaves))
            ver = (len(self.models), self._models_version)
            cache = self._stack_cache
            if cache is not None and cache[0] == ver:
                hit = cache[1].get(key)
                if hit is not None:
                    # LRU refresh: re-insert so slice-shape churn can
                    # never evict the hot full-model entry (tolerate a
                    # concurrent pop — threaded serving must not crash)
                    try:
                        cache[1][key] = cache[1].pop(key)
                    except KeyError:
                        pass
                    obs.inc("predict.stack_cache_hits")
                    return hit
        # observable for the zero-restack serving guarantee (tests pin
        # that warm predicts never reach this point)
        self._stack_builds = getattr(self, "_stack_builds", 0) + 1
        obs.inc("predict.stack_cache_misses")
        trees = [self.models[i] for i in indices]
        n_real = len(trees)
        n_pad = max(pad_count, n_real)
        L = max(max((t.num_leaves for t in trees), default=1), pad_leaves)
        Ln = max(L - 1, 1)

        def padded(getter, size, dtype, fill=0):
            out = np.full((n_pad, size), fill, dtype=dtype)
            for i, t in enumerate(trees):
                a = getter(t)
                out[i, :len(a)] = a
            return jnp.asarray(out)

        stacked = {
            "num_leaves": jnp.asarray(np.array(
                [t.num_leaves for t in trees] + [1] * (n_pad - n_real),
                np.int32)),
            "split_feature": padded(lambda t: t.split_feature, Ln, np.int32),
            "threshold_bin": padded(lambda t: t.threshold_bin, Ln, np.int32),
            "default_left": padded(lambda t: t.default_left, Ln, bool),
            "left_child": padded(lambda t: t.left_child, Ln, np.int32),
            "right_child": padded(lambda t: t.right_child, Ln, np.int32),
            "leaf_value": padded(
                lambda t: t.leaf_value.astype(np.float32), L, np.float32),
        }
        force_cat = pad_count > 0 and self.has_categorical
        if force_cat or any(t.cat_bitset_bins is not None for t in trees):
            # under shape-stabilizing padding, the bitset width and the
            # presence of the cat keys must not depend on WHICH trees
            # were drawn, or the consumer jit recompiles per drop set
            W = ((self.B + 31) // 32 if force_cat else
                 max(t.cat_bitset_bins.shape[1] for t in trees
                     if t.cat_bitset_bins is not None))
            bs = np.zeros((n_pad, Ln, W), dtype=np.uint32)
            for i, t in enumerate(trees):
                if t.cat_bitset_bins is not None:
                    a = t.cat_bitset_bins
                    bs[i, :a.shape[0], :a.shape[1]] = a
            stacked["is_cat"] = padded(
                lambda t: (t.is_categorical if t.is_categorical is not None
                           else np.zeros(t.num_nodes, bool)), Ln, bool)
            stacked["cat_bitset"] = jnp.asarray(bs)
        class_idx = jnp.asarray(np.asarray(
            list(indices) + [0] * (n_pad - n_real),
            dtype=np.int32) % self.num_class)
        if getattr(self, "_predict_mesh", None) is not None:
            # tree-sharded serving: commit the stack with its [T] axis
            # split over the mesh BEFORE caching, so every warm predict
            # reuses the sharded placement (re-placing per call would
            # re-upload the forest per request)
            from ..serve.shard import place_tree_sharded
            stacked, class_idx = place_tree_sharded(
                stacked, class_idx, self._predict_mesh)
        if key is not None:
            cache = self._stack_cache
            if cache is None or cache[0] != ver:
                cache = (ver, {})
                self._stack_cache = cache
            if len(cache[1]) >= _STACK_CACHE_ENTRIES:
                cache[1].pop(next(iter(cache[1])))
            cache[1][key] = (stacked, class_idx)
        return stacked, class_idx

    # ------------------------------------------------------------------
    def eval_set(self, which: int) -> List[Tuple[str, str, float, bool]]:
        """Evaluate metrics: which=-1 train, else valid index.

        Returns list of (data_name, metric_name, value, higher_better).
        """
        from ..metric import eval_metric_rows
        obs.inc("eval.calls", force=True)
        if which < 0:
            dd, name, score = self.data, "training", self.score
        else:
            dd = self.valid_data[which]
            name = self.valid_names[which]
            score = self.valid_scores[which]
        # the host waits here for the device to finish the scores, then
        # copies them over
        with obs.span("eval/scores"):
            raw = np.asarray(score)[:dd.n]
        label = np.asarray(dd.label)[:dd.n] if dd.label is not None else None
        weight = (np.asarray(dd.weight)[:dd.n]
                  if dd.weight is not None else None)
        with obs.span("eval/metric"):
            return eval_metric_rows(self.objective, self.metrics, name,
                                    raw, label, weight,
                                    dd.query_boundaries, self.num_class)

    def _convert_output_np(self, raw: np.ndarray) -> np.ndarray:
        if self.num_class == 1:
            raw = raw[:, 0]
        return np.asarray(self.objective.convert_output(jnp.asarray(raw)))

    # ------------------------------------------------------------------
    def predict(self, X: np.ndarray, raw_score: bool = False,
                start_iteration: int = 0, num_iteration: int = -1,
                pred_leaf: bool = False, **overrides) -> np.ndarray:
        """Predict on raw features (binned through the train mappers).

        ``overrides``: per-call serving-knob overrides (upstream's
        predict-kwargs-as-params convention) — ``tpu_predict_
        parallel_trees`` / ``tpu_predict_buckets`` /
        ``tpu_predict_chunk_rows`` tune one call without mutating the
        engine config."""
        if not obs.any_enabled():
            return self._predict_impl(X, raw_score, start_iteration,
                                      num_iteration, pred_leaf,
                                      **overrides)
        return obs.predict_instrumented(
            lambda: self._predict_impl(X, raw_score, start_iteration,
                                       num_iteration, pred_leaf,
                                       **overrides), X)

    def _predict_impl(self, X: np.ndarray, raw_score: bool = False,
                      start_iteration: int = 0, num_iteration: int = -1,
                      pred_leaf: bool = False,
                      **overrides) -> np.ndarray:
        if self.linear_tree:
            # linear leaves need raw feature values — host-model path
            # (cached; the model list only grows)
            from ..io.model_text import HostModel
            hm_key = (len(self.models), self._models_version)
            cache = getattr(self, "_hm_cache", (None, None))
            if cache[0] != hm_key:
                cache = (hm_key,
                         HostModel.from_engine(self, self.config))
                self._hm_cache = cache
            return cache[1].predict(X, raw_score=raw_score,
                                    start_iteration=start_iteration,
                                    num_iteration=num_iteration,
                                    pred_leaf=pred_leaf)
        ds = self.train_set
        sparse_in = hasattr(X, "tocsc") and not isinstance(X, np.ndarray)
        if sparse_in:
            # scipy sparse: bin column-at-a-time without densifying the
            # full matrix (same path training binning uses — Criteo-
            # scale sparse predict must not materialize n x F floats)
            Xc = X.tocsc()
            n_rows = Xc.shape[0]
            if Xc.shape[1] != ds.num_total_features:
                log.fatal(
                    f"The number of features in data ({Xc.shape[1]}) is "
                    f"not the same as it was in training data "
                    f"({ds.num_total_features})")
        else:
            from ..io.dataset import apply_pandas_categorical
            X = apply_pandas_categorical(
                X, getattr(ds, "pandas_categorical", None))
            X = Dataset._to_matrix(X)
            n_rows = X.shape[0]
            if X.shape[1] != ds.num_total_features:
                log.fatal(
                    f"The number of features in data ({X.shape[1]}) is "
                    f"not the same as it was in training data "
                    f"({ds.num_total_features})")
        # one native row-major pass over all columns where possible
        # (Dataset._bin_all_columns; the strided per-column fallback
        # otherwise) — same binning the training construct used
        src = Xc if sparse_in else X
        bins = ds._bin_all_columns(src, sparse_in, ds.binned_dtype(),
                                   n_rows=n_rows)
        total_iters = len(self.models) // self.num_class
        if num_iteration <= 0:
            num_iteration = total_iters - start_iteration
        num_iteration = min(num_iteration, total_iters - start_iteration)
        n_trees = num_iteration * self.num_class
        start_tree = start_iteration * self.num_class
        n = n_rows
        if n_trees <= 0:
            if pred_leaf:
                return np.zeros((n, 0), dtype=np.int32)
            raw = np.tile(self.init_scores, (n, 1))
            if raw_score:
                return raw[:, 0] if self.num_class == 1 else raw
            return self._convert_output_np(raw)

        def post(raw_np: np.ndarray) -> np.ndarray:
            # per-chunk post-processing on the still-PADDED rows (all
            # steps are row-local, so padded rows never affect real
            # ones, and the convert step's jit sees only the bounded
            # bucket/chunk shapes — not one shape per request size)
            if self.average_output:
                # RF: trees carry the init-score bias; average them
                raw_np = raw_np / num_iteration
            elif start_iteration == 0:
                raw_np = raw_np + self.init_scores[None, :]
            if raw_score:
                return raw_np[:, 0] if self.num_class == 1 else raw_np
            return self._convert_output_np(raw_np)

        from ..config import coerce_bool
        use_cache = (coerce_bool(overrides["tpu_predict_cache"])
                     if "tpu_predict_cache" in overrides else None)
        stacked, class_idx = self._stack_for_predict(
            start_tree, n_trees, use_cache=use_cache)
        out, leaves = self._run_forest_chunks(
            stacked, class_idx, bins, n_trees, want_leaves=pred_leaf,
            # pred_leaf discards raw scores: skip their copy + convert
            postprocess=None if pred_leaf else post, overrides=overrides)
        if pred_leaf:
            return leaves.T.astype(np.int32)
        return out

    # ------------------------------------------------------------------
    def _stack_for_predict(self, start_tree: int, n_trees: int,
                           use_cache=None):
        """Stack the requested tree range with shape-stabilizing
        padding. The full forest stacks exactly (the serving steady
        state — one stacked shape per model size, and the same shape
        the score-rebuild/valid-eval paths already compiled). Partial
        ranges — ``num_iteration``/``start_iteration`` early-stop
        serving — pad the tree count to the next power of two and every
        tree to the config leaf cap, so each distinct slice length
        reuses a bucketed traversal compile instead of triggering a
        fresh one (the same ``pad_count``/``pad_leaves`` knobs DART's
        drop stacks use).

        ``_stable_predict_shapes`` (set by serving.ModelWatcher when
        this engine serves under a checkpoint watch) extends the
        bucketed padding to the FULL forest too: successive hot-swapped
        models whose actual max leaf counts differ would otherwise
        stack to different shapes and recompile the warm path on every
        swap — padded to (pow2 tree count, config num_leaves), every
        swap in the same bucket reuses the compiled programs
        (CompileWatch-pinned in tests/test_chaos.py)."""
        if (not getattr(self, "_stable_predict_shapes", False)
                and start_tree == 0 and n_trees == len(self.models)):
            return self._stack_model_list(list(range(n_trees)),
                                          use_cache=use_cache)
        pad_count = _next_pow2(n_trees)
        mesh = getattr(self, "_predict_mesh", None)
        if mesh is not None:
            # NamedSharding needs the tree axis divisible by the mesh:
            # pad further with inert single-leaf trees (a pow2 count
            # already divides pow2 meshes; this covers the rest)
            pad_count = _ceil_to(pad_count, int(mesh.devices.size))
        return self._stack_model_list(
            list(range(start_tree, start_tree + n_trees)),
            pad_count=pad_count,
            pad_leaves=self.config.num_leaves, use_cache=use_cache)

    def _run_forest_chunks(self, stacked, class_idx, bins, n_trees: int,
                           want_leaves: bool = False, postprocess=None,
                           overrides=None):
        """Traverse the stacked forest over host-binned rows with
        batch-shape bucketing and chunked double-buffered streaming.

        Small batches pad up to power-of-two row buckets (bounded
        compile cache under arbitrary request sizes); jobs larger than
        ``tpu_predict_chunk_rows`` stream in fixed-size chunks — every
        chunk the SAME shape — with ``copy_to_host_async`` issued
        before the next chunk's dispatch so device compute and the
        device->host copy overlap (the dispatch-latency lesson
        docs/perf.md records for training). ``postprocess`` (row-local:
        score averaging / init-score add / output convert) runs per
        chunk while rows are still padded, so its jit also sees only
        bucket shapes. Padded rows are sliced off before returning;
        real-row outputs are identical to one unpadded pass.

        Returns (per-row output ``[n, ...]`` f64,
                 leaf indices ``[n_trees, n]`` int32 or None).
        """
        from ..config import coerce_bool
        cfg = self.config

        def knob(name, cast):
            if overrides and name in overrides:
                return cast(overrides[name])
            return cast(getattr(cfg, name))

        n_rows = bins.shape[0]
        mode = (None if knob("tpu_predict_parallel_trees", coerce_bool)
                else "scan")
        mesh = getattr(self, "_predict_mesh", None)
        consts = getattr(self, "_shard_consts", None)
        feat_num_bin, feat_has_nan = (
            consts if (mesh is not None and consts is not None)
            else (self.feat_num_bin, self.feat_has_nan))
        chunk = max(knob("tpu_predict_chunk_rows", int), 1024)
        if n_rows <= chunk:
            pad_to = predict_pad_rows(
                n_rows, chunk, knob("tpu_predict_buckets", coerce_bool))
            plan = [(0, n_rows, pad_to)]
        else:
            plan = [(s, min(chunk, n_rows - s), chunk)
                    for s in range(0, n_rows, chunk)]

        raw_parts: List[np.ndarray] = []
        leaf_parts: List[np.ndarray] = []

        def drain(item):
            raw_dev, leaves_dev, rows = item
            if raw_dev is not None:
                raw_np = np.asarray(raw_dev, dtype=np.float64)
                if postprocess is not None:
                    raw_np = postprocess(raw_np)
                raw_parts.append(raw_np[:rows])
            if leaves_dev is not None:
                leaf_parts.append(np.asarray(leaves_dev)[:, :rows])

        if obs.enabled():
            # bucket/chunk accounting: padded rows quantify the cost of
            # the bounded-compile-cache guarantee, chunk count the
            # streaming fan-out
            obs.inc("predict.chunks", len(plan))
            obs.inc("predict.padded_rows",
                    sum(p - r for _s, r, p in plan))
        # depth=1 window == the double buffer this loop hand-rolled
        # before utils/prefetch.py existed: block on the oldest chunk's
        # async D2H copy only once a second chunk is dispatched.
        window = InflightWindow(1, drain)
        for start, rows, pad_to in plan:
            blk = bins[start:start + rows]
            if pad_to > rows:
                blk = np.concatenate(
                    [blk, np.zeros((pad_to - rows, blk.shape[1]),
                                   blk.dtype)])
            if mesh is not None:
                # replicate THIS request's rows across the mesh (the
                # H2D upload it would pay anyway, fanned out)
                from ..serve.shard import replicate_on
                blk_dev = replicate_on(mesh, blk)
            else:
                blk_dev = jnp.asarray(blk)
            raw_dev, leaves_dev = forest_predict_binned(
                stacked, blk_dev, feat_num_bin, feat_has_nan,
                class_idx, self.num_class, mode=mode, mesh=mesh)
            if want_leaves:
                # leaf-only request: the raw scores are never read back
                leaves_dev.copy_to_host_async()
                window.push((None, leaves_dev, rows))
            else:
                raw_dev.copy_to_host_async()
                window.push((raw_dev, None, rows))
        window.drain()
        if want_leaves:
            leaves = (leaf_parts[0] if len(leaf_parts) == 1
                      else np.concatenate(leaf_parts, axis=1))[:n_trees]
            return None, leaves
        raw = (raw_parts[0] if len(raw_parts) == 1
               else np.concatenate(raw_parts, axis=0))
        return raw, None

    # ------------------------------------------------------------------
    def predict_contrib(self, X, start_iteration: int = 0,
                        num_iteration: int = -1, host_model=None,
                        force_f64=None, **overrides) -> np.ndarray:
        """Device-native TreeSHAP (``pred_contrib``) through the same
        serving machinery as :meth:`predict`: memoized device-resident
        path tables (``_shap_cache``), pow2 row buckets + fixed-size
        chunking + the InflightWindow double buffer, and the
        tree-sharded scan when a ``_predict_mesh`` is enabled.

        Output is host-format: ``[n, n_feat + 1]`` for one class, else
        ``[n, K * (n_feat + 1)]`` — identical to
        ``HostModel.predict(pred_contrib=True)`` (f64-exact on CPU
        backends; documented ~3e-5 f32 tolerance on TPU)."""
        if not obs.any_enabled():
            return self._predict_contrib_impl(
                X, start_iteration, num_iteration, host_model,
                force_f64, **overrides)
        return obs.predict_instrumented(
            lambda: self._predict_contrib_impl(
                X, start_iteration, num_iteration, host_model,
                force_f64, **overrides), X)

    def _predict_contrib_impl(self, X, start_iteration: int,
                              num_iteration: int, host_model,
                              force_f64, **overrides) -> np.ndarray:
        from ..ops import shap as shap_ops
        if host_model is None:
            # SHAP walks host trees (original-feature split ids, folded
            # init-score bias) — same cached conversion predict's
            # linear-tree path uses
            from ..io.model_text import HostModel
            hm_key = (len(self.models), self._models_version)
            cache = getattr(self, "_hm_cache", (None, None))
            if cache[0] != hm_key:
                cache = (hm_key,
                         HostModel.from_engine(self, self.config))
                self._hm_cache = cache
            host_model = cache[1]
        ds = self.train_set
        sparse_in = hasattr(X, "tocsr") and not isinstance(X, np.ndarray)
        if sparse_in:
            X = X.tocsr()
            n_rows = X.shape[0]
            n_cols = X.shape[1]
        else:
            from ..io.dataset import apply_pandas_categorical
            X = apply_pandas_categorical(
                X, getattr(ds, "pandas_categorical", None))
            X = np.ascontiguousarray(
                np.asarray(Dataset._to_matrix(X), np.float64))
            n_rows, n_cols = X.shape
        if n_cols != ds.num_total_features:
            log.fatal(
                f"The number of features in data ({n_cols}) is "
                f"not the same as it was in training data "
                f"({ds.num_total_features})")
        n_feat = ds.num_total_features
        K = max(self.num_class, 1)
        total_iters = len(self.models) // K
        if num_iteration <= 0:
            num_iteration = total_iters - start_iteration
        num_iteration = min(num_iteration, total_iters - start_iteration)
        n_trees = num_iteration * K
        start_tree = start_iteration * K
        if n_trees <= 0:
            out = np.zeros((n_rows, K, n_feat + 1), np.float64)
        else:
            trees = host_model.trees[start_tree:start_tree + n_trees]
            if all(t.num_leaves <= 1 for t in trees):
                out = shap_ops.stump_only_contrib(trees, n_rows,
                                                  n_feat, K)
            else:
                with obs.span("predict/contrib", rows=n_rows,
                              trees=n_trees):
                    out = self._run_shap_chunks(
                        trees, X, sparse_in, n_rows, n_feat, K,
                        start_tree, n_trees, force_f64, overrides)
            if self.average_output:
                out = out / max(n_trees // K, 1)
        return out[:, 0, :] if K == 1 else out.reshape(
            n_rows, K * (n_feat + 1))

    def _shap_tables_for(self, trees, start_tree: int, n_trees: int,
                         n_feat: int, K: int, dtype_name: str, mesh):
        """Device-resident stacked path tables for a tree slice,
        memoized next to ``_stack_model_list``'s forest cache: keyed on
        ``(len(models), _models_version)`` so hot-swaps re-cost, LRU
        over ``(start_tree, n_trees, dtype)`` slices, shape-stabilized
        (config leaf cap + pow2 depth/slot/tree-count buckets) exactly
        like ``_stack_for_predict`` so warm SHAP re-derives nothing and
        recompiles nothing within a bucket."""
        from ..ops import shap as shap_ops
        ver = (len(self.models), self._models_version)
        key = (start_tree, n_trees, dtype_name)
        cache = self._shap_cache
        if cache is not None and cache[0] == ver and key in cache[1]:
            entry = cache[1].pop(key)
            cache[1][key] = entry          # LRU refresh
            if obs.enabled():
                obs.inc("predict.contrib_cache_hits")
            return entry
        if obs.enabled():
            obs.inc("predict.contrib_cache_misses")
        (L_a, D_a, U_a, NN_a), paths = shap_ops.shap_path_dims(trees)
        partial = not (start_tree == 0 and n_trees == len(self.models))
        if getattr(self, "_stable_predict_shapes", False) or partial:
            # bucketed caps: leaf/node dims pinned to the config cap,
            # depth/slot dims to pow2 buckets — successive hot-swapped
            # models (or early-stop slices) in the same buckets reuse
            # the compiled scan
            L = max(L_a, int(self.config.num_leaves))
            NN = max(NN_a, L - 1)
            D = _next_pow2(max(D_a, 1))
            U = _next_pow2(max(U_a, 1))
            T_pad = _next_pow2(n_trees)
        else:
            L, D, U, NN = L_a, D_a, U_a, NN_a
            T_pad = n_trees
        if mesh is not None:
            T_pad = _ceil_to(T_pad, int(mesh.devices.size))
        stacked_np, dims = shap_ops.build_shap_tables(
            trees, n_feat, K, dims=(L, D, U, NN),
            pad_trees=T_pad - n_trees, paths=paths)
        if mesh is not None:
            from ..serve.shard import place_shap_sharded
            dev = place_shap_sharded(stacked_np, mesh)
        else:
            dev = {k: jnp.asarray(v) for k, v in stacked_np.items()}
        entry = (dev, dims, T_pad)
        if cache is None or cache[0] != ver:
            cache = (ver, {})
            self._shap_cache = cache
        cache[1][key] = entry
        while len(cache[1]) > _STACK_CACHE_ENTRIES:
            cache[1].pop(next(iter(cache[1])))
        return entry

    def _run_shap_chunks(self, trees, X, sparse_in: bool, n_rows: int,
                         n_feat: int, K: int, start_tree: int,
                         n_trees: int, force_f64, overrides):
        """Run the SHAP scan over ``X`` with the SAME batch-shape
        bucketing, fixed-size chunking, and double-buffered D2H
        streaming as ``_run_forest_chunks`` — the per-chunk host work
        is only the routing-bit pass (vectorized numpy), the tables
        come from the device cache. Returns ``[n, K, n_feat+1]`` f64."""
        import contextlib
        from ..config import coerce_bool
        from ..ops import shap as shap_ops
        from ..ops.predict import onehot_bounded_rows
        cfg = self.config

        def knob(name, cast):
            if overrides and name in overrides:
                return cast(overrides[name])
            return cast(getattr(cfg, name))

        if force_f64 is None:
            force_f64 = jax.default_backend() == "cpu"
        mesh = getattr(self, "_predict_mesh", None)
        if force_f64 and jax.default_backend() != "cpu":
            # exact-f64 escape hatch runs on the host CPU device —
            # never through an accelerator mesh
            mesh = None
        dtype_name = "float64" if force_f64 else "float32"
        ctx = contextlib.ExitStack()
        if force_f64:
            ctx.enter_context(jax.enable_x64(True))
            if jax.default_backend() != "cpu":
                ctx.enter_context(
                    jax.default_device(jax.devices("cpu")[0]))
        out = np.zeros((n_rows, K, n_feat + 1), np.float64)
        with ctx:
            dev, (L, D, U, NN), T_pad = self._shap_tables_for(
                trees, start_tree, n_trees, n_feat, K, dtype_name,
                mesh)
            chunk = max(knob("tpu_predict_chunk_rows", int), 1024)
            # bound the scan's widest [rows, L*max(D, U+2)] operand the
            # same way the level traversal bounds its one-hots
            chunk = min(chunk, onehot_bounded_rows(L * max(D, U + 2)))
            if n_rows <= chunk:
                pad_to = predict_pad_rows(
                    n_rows, chunk,
                    knob("tpu_predict_buckets", coerce_bool))
                plan = [(0, n_rows, pad_to)]
            else:
                plan = [(s, min(chunk, n_rows - s), chunk)
                        for s in range(0, n_rows, chunk)]
            if obs.enabled():
                obs.inc("predict.chunks", len(plan))
                obs.inc("predict.padded_rows",
                        sum(p - r for _s, r, p in plan))
            use_sharded = (mesh is not None
                           and int(mesh.devices.size) > 1
                           and T_pad % int(mesh.devices.size) == 0)
            run = (shap_ops.sharded_scan_kernel(
                       mesh, D, U, NN, n_feat, K, dtype_name)
                   if use_sharded else
                   shap_ops._scan_kernel(D, U, NN, n_feat, K,
                                         dtype_name))

            def drain(item):
                phi_dev, lo, rows = item
                out[lo:lo + rows] = np.asarray(phi_dev,
                                               np.float64)[:rows]

            window = InflightWindow(1, drain)
            for start, rows, pad_to in plan:
                if sparse_in:
                    blk = np.asarray(
                        X[start:start + rows].toarray(), np.float64)
                else:
                    blk = X[start:start + rows]
                if pad_to > rows:
                    blk = np.concatenate(
                        [blk, np.zeros((pad_to - rows, blk.shape[1]),
                                       np.float64)])
                # host routing-bit pass: once per (rows-bucket, model
                # version) chunk, not per call — tables are cached
                conds = np.stack(
                    [shap_ops._host_cond_bits(t, blk, NN)
                     for t in trees])
                if T_pad > len(trees):
                    conds = np.concatenate(
                        [conds,
                         np.zeros((T_pad - len(trees),)
                                  + conds.shape[1:], np.uint8)])
                batch = dict(dev)
                if use_sharded:
                    from ..serve.shard import place_tree_axis
                    batch["cond"] = place_tree_axis(mesh, conds)
                else:
                    batch["cond"] = jnp.asarray(conds)
                phi_dev = run(batch)
                phi_dev.copy_to_host_async()
                window.push((phi_dev, start, rows))
            window.drain()
        return out

    @property
    def current_iteration(self) -> int:
        return self.iter_

    def num_trees(self) -> int:
        return len(self.models)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Current observability snapshot (docs/observability.md):
        process-wide metrics registry contents with the device/compile
        gauges refreshed. Enable collection with ``tpu_metrics=true``
        (off by default, so an un-enabled engine returns an empty or
        partial snapshot)."""
        return obs.snapshot()
