"""Binned dataset + metadata.

Reference: src/io/dataset.cpp, src/io/metadata.cpp,
include/LightGBM/dataset.h (UNVERIFIED — empty mount, see SURVEY.md banner).

TPU-first representational choice (SURVEY.md §7.1): instead of the
reference's per-feature-group ``Bin`` objects (dense/sparse/multi-val
hierarchies tuned for CPU caches), the binned matrix is ONE packed integer
array ``[n_rows, n_used_features]`` (uint8 when every feature has <=256
bins) destined for HBM, row-sharded over the mesh. EFB still happens at bin
time (bundled features share a column with bin offsets) — see bundling.py.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from ..utils import log
from .binning import (BIN_TYPE_CATEGORICAL, BinMapper, find_bin_mappers,
                      load_forced_bins, resolve_ingest_threads)


def _host_mem_bytes():
    """Total physical host RAM, or None when undeterminable."""
    try:
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return None


def _is_pandas_df(data) -> bool:
    return (hasattr(data, "dtypes") and hasattr(data, "columns")
            and hasattr(data, "values"))


def _pandas_cat_columns(df) -> list:
    return [c for c, dt in zip(df.columns, df.dtypes)
            if str(dt) == "category"]


def extract_pandas_categorical(df):
    """Per category-dtype column (in column order), the category-value
    list — the mapping stock LightGBM records as ``pandas_categorical``
    in the model file (basic.py _data_from_pandas, UNVERIFIED — empty
    mount). None when the frame has no category columns. Category
    values must be JSON-serializable (they go into the model text
    verbatim) — rejected HERE with a clear error rather than as a
    TypeError at save time."""
    cols = _pandas_cat_columns(df)
    if not cols:
        return None
    import json
    out = []
    for c in cols:
        cats = list(df[c].cat.categories.tolist())
        try:
            json.dumps(cats)
        except TypeError:
            log.fatal(
                f"Categories of column '{c}' are not "
                f"JSON-serializable (e.g. pd.cut Intervals or "
                f"Timestamps) and cannot be stored in the model file — "
                f"convert them to str or int first "
                f"(e.g. df['{c}'] = df['{c}'].astype(str)"
                f".astype('category'))")
        out.append(cats)
    return out


def apply_pandas_categorical(data, pandas_categorical):
    """Replace a DataFrame's category-dtype columns with their integer
    CODES under ``pandas_categorical``'s category lists (float64; NaN
    for missing AND for values outside the recorded lists). Train time
    passes the frame's own lists; predict time passes the lists stored
    in the model, so a frame whose categories arrive in a different
    order — or with new values — still maps code-compatibly with
    training. Non-DataFrame inputs pass through untouched."""
    if not _is_pandas_df(data):
        return data
    cols = _pandas_cat_columns(data)
    if not cols:
        return data
    if pandas_categorical is None or \
            len(pandas_categorical) != len(cols):
        log.fatal(
            f"Input DataFrame has {len(cols)} category-dtype columns "
            f"but the model/dataset records "
            f"{0 if pandas_categorical is None else len(pandas_categorical)} "
            f"— train and predict frames must have matching categorical "
            f"columns (pandas_categorical)")
    data = data.copy(deep=False)
    for c, cats in zip(cols, pandas_categorical):
        # vectorized value->code: set_categories drops values outside
        # ``cats`` to NaN (code -1), exactly the unseen-category
        # semantics of the bitset miss; at train time cats == the
        # column's own list so this is the plain .cat.codes
        codes = data[c].cat.set_categories(cats).cat.codes.to_numpy()
        vals = codes.astype(np.float64)
        vals[codes < 0] = np.nan
        data[c] = vals
    return data


def _coerce_1d(a) -> np.ndarray:
    """1-D float64 coercion accepting numpy / lists / pandas Series /
    pyarrow Array-ChunkedArray (np.asarray would wrap arrow objects as
    dtype=object)."""
    if hasattr(a, "to_numpy") and \
            (type(a).__module__ or "").startswith("pyarrow"):
        a = a.to_numpy(zero_copy_only=False)
    return np.asarray(a, dtype=np.float64)


@dataclasses.dataclass
class Metadata:
    """Per-row training metadata (reference: Metadata, metadata.cpp)."""

    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    # query boundaries: int array of size num_queries+1 (cumulative), like
    # the reference's query_boundaries_ built from per-query counts
    query_boundaries: Optional[np.ndarray] = None
    init_score: Optional[np.ndarray] = None
    # per-row presentation positions (Metadata::positions, v4.2+):
    # consumed by lambdarank_unbiased instead of the score rank
    position: Optional[np.ndarray] = None

    def set_group(self, group: Optional[np.ndarray]) -> None:
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).ravel()
        self.query_boundaries = np.concatenate([[0], np.cumsum(group)])

    def num_queries(self) -> int:
        if self.query_boundaries is None:
            return 0
        return len(self.query_boundaries) - 1


class Dataset:
    """User-facing Dataset mirroring ``lightgbm.Dataset`` semantics.

    Lazy construction: raw data is kept until ``construct()`` is called
    (by ``train()``/``Booster``), at which point binning runs — matching
    basic.py's ``Dataset._lazy_init``. A validation dataset created via
    ``create_valid``/``reference=`` reuses the training set's BinMappers,
    exactly as the reference requires aligned bin boundaries.
    """

    def __init__(self, data, label=None, reference: "Dataset" = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        self.data = data
        self.params = dict(params or {})
        self.reference = reference
        self.free_raw_data = free_raw_data
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.metadata = Metadata()
        if label is not None:
            self.metadata.label = _coerce_1d(label).ravel()
        if weight is not None:
            self.metadata.weight = _coerce_1d(weight).ravel()
        if group is not None:
            self.metadata.set_group(_coerce_1d(group))
        if init_score is not None:
            self.metadata.init_score = _coerce_1d(init_score)
        # filled by construct()
        self._constructed = False
        self.bin_mappers: List[BinMapper] = []
        self._ingest = None          # device-resident ingest result
        self.binned: Optional[np.ndarray] = None   # [n_rows, n_used]
        self.used_features: List[int] = []         # original feature indices
        self.num_total_features = 0
        self.num_data = 0
        self._raw_for_linear: Optional[np.ndarray] = None
        # category-value lists of pandas category-dtype columns
        # (stock lightgbm's pandas_categorical); filled at construct
        self.pandas_categorical = None
        import os as _os
        if isinstance(data, (str, _os.PathLike)):
            self._init_from_file(_os.fspath(data))

    # ------------------------------------------------------------------
    @property
    def binned(self) -> Optional[np.ndarray]:
        """Host ``[n, n_used]`` binned matrix. Under device ingest
        (``tpu_ingest_device``) the matrix lives on the accelerator and
        the host copy materializes LAZILY here, only for the paths that
        genuinely need host bytes (save_binary / EFB bundling / subset /
        model-text round trips) — training reads the device arrays
        directly via ``device_ingested()``."""
        b = getattr(self, "_binned", None)
        if b is None:
            ing = getattr(self, "_ingest", None)
            if ing is not None:
                b = ing.host_binned()
                self._binned = b
        return b

    @binned.setter
    def binned(self, value) -> None:
        self._binned = value

    def device_ingested(self):
        """The on-device ingest result (ops/ingest.DeviceIngestResult)
        or None when this dataset was binned host-side."""
        return getattr(self, "_ingest", None)

    def binned_dtype(self):
        """Bin-id dtype WITHOUT forcing a host materialization of a
        device-resident binned matrix (predict needs only the dtype)."""
        b = getattr(self, "_binned", None)
        if b is not None:
            return b.dtype
        ing = getattr(self, "_ingest", None)
        if ing is not None:
            return np.dtype(ing.bins.dtype)
        return self.binned.dtype

    # ------------------------------------------------------------------
    @staticmethod
    def _to_matrix(data) -> np.ndarray:
        """Accept numpy / pandas / pyarrow / list-of-lists / scipy-sparse.

        Reference: LGBM_DatasetCreateFromMat/CSR/CSC/Arrow (c_api.cpp,
        UNVERIFIED — empty mount); the arrow path mirrors basic.py's
        pyarrow Table handling."""
        if hasattr(data, "toarray"):          # scipy sparse
            dense_bytes = int(data.shape[0]) * int(data.shape[1]) * 8
            budget = _host_mem_bytes()
            note = ("Training, valid-set construction and predict all "
                    "bin sparse input column-wise without densifying — "
                    "pass the sparse matrix to those APIs directly, or "
                    "chunk rows for paths that need raw values")
            if budget is not None and dense_bytes > 0.9 * budget:
                log.fatal(
                    f"densifying sparse input of shape {data.shape} "
                    f"would need {dense_bytes / 2**30:.1f} GiB — more "
                    f"than 90% of host RAM. {note}")
            elif budget is not None and dense_bytes > 0.25 * budget:
                log.warning(
                    f"densifying sparse input of shape {data.shape} "
                    f"({dense_bytes / 2**30:.1f} GiB, > 25% of host "
                    f"RAM). {note}")
            return np.asarray(data.toarray(), dtype=np.float64)
        if (type(data).__module__ or "").startswith("pyarrow") \
                and hasattr(data, "column_names"):   # pyarrow.Table
            cols = [np.asarray(data.column(i).to_numpy(
                zero_copy_only=False), dtype=np.float64)
                for i in range(data.num_columns)]
            return np.stack(cols, axis=1) if cols else \
                np.zeros((0, 0), np.float64)
        if hasattr(data, "values") and hasattr(data, "columns"):  # pandas
            return np.asarray(data.values, dtype=np.float64)
        arr = np.asarray(data, dtype=np.float64)
        if arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        return arr

    def _resolve_feature_names(self, n_features: int) -> List[str]:
        if isinstance(self.feature_name, list):
            return list(self.feature_name)
        if hasattr(self.data, "column_names"):    # pyarrow (checked
            # first: arrow Tables also expose a `.columns` of arrays)
            return [str(c) for c in self.data.column_names]
        if hasattr(self.data, "columns"):     # pandas
            return [str(c) for c in self.data.columns]
        return [f"Column_{i}" for i in range(n_features)]

    def _resolve_categorical(self, names: List[str]) -> List[int]:
        """Column indices of the categorical features: the constructor's
        ``categorical_feature`` where it is given, else the one in
        ``params`` (any of its aliases; a list, or LightGBM's
        ``"0,1,2"`` / ``"name:c1,c2"`` string), else pandas category
        dtypes. Given both ways the argument wins with a warning, as
        upstream (basic.py Dataset._lazy_init)."""
        from ..config import _ALIASES
        from_params = next(
            (v for k, v in self.params.items()
             if _ALIASES.get(k, k) == "categorical_feature"), None)
        if isinstance(from_params, str):
            by_name = from_params.startswith("name:")
            from_params = [
                c if by_name else int(c) for c in
                (from_params[5:] if by_name else from_params).split(",")
                if c.strip()]
        cf = self.categorical_feature
        if cf == "auto" or cf is None:
            cf = from_params or None
        elif from_params:
            log.warning("categorical_feature in the Dataset's params is "
                        "ignored: the categorical_feature argument "
                        "is given and wins")
        if cf is None:
            # pandas category dtype auto-detection
            if hasattr(self.data, "dtypes"):
                return [i for i, dt in enumerate(self.data.dtypes)
                        if str(dt) == "category"]
            return []
        out = []
        for c in cf:
            if isinstance(c, str):
                if c in names:
                    out.append(names.index(c))
                else:
                    log.warning(f"categorical_feature {c} not in data")
            else:
                out.append(int(c))
        return out

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._constructed:
            return self
        from .. import obs
        with obs.span("dataset/construct"):
            return self._construct_impl()

    def _construct_impl(self) -> "Dataset":
        # warm-start: point jax's persistent compile cache BEFORE the
        # first construct-time kernel (the ingest assignment jit)
        from ..config import get_param, setup_compile_cache
        setup_compile_cache(get_param(self.params,
                                      "tpu_compile_cache_dir"))
        if getattr(self, "_stream_path", None):
            return self._construct_streamed()
        if self._finish_pushed():
            return self
        # scipy sparse binning never densifies the raw matrix (8 bytes x
        # n x F would dwarf the uint8 binned output at Criteo-class
        # sparsity); one float64 column is materialized at a time from
        # CSC (LGBM_DatasetCreateFromCSC, c_api.cpp — UNVERIFIED)
        is_sparse = (hasattr(self.data, "tocsc")
                     and hasattr(self.data, "nnz")
                     and not isinstance(self.data, np.ndarray))
        if is_sparse:
            Xc = self.data.tocsc()
            X = Xc          # find_bin_mappers handles sparse natively
            self.num_data, self.num_total_features = Xc.shape
        else:
            data = self.data
            if _is_pandas_df(data) and _pandas_cat_columns(data):
                # valid sets inherit the TRAINING frame's category
                # lists so codes agree across datasets
                self.pandas_categorical = (
                    self.reference.construct().pandas_categorical
                    if self.reference is not None
                    else extract_pandas_categorical(data))
                data = apply_pandas_categorical(
                    data, self.pandas_categorical)
            from ..config import coerce_bool as _cb2
            if (isinstance(data, np.ndarray) and data.ndim == 2
                    and data.dtype in (np.float32, np.float64)
                    and not _cb2(self.params.get("linear_tree", False))):
                # fast path: bin columns of the caller's matrix
                # directly (the native binner takes f32 and strided
                # views) instead of materializing a float64 copy —
                # at 10M x 28 that copy alone is ~2.2 GB. Bin mappers
                # still see float64 (from_sample converts its sample).
                # linear_tree keeps the f64 path: leaf ridge fits read
                # _raw_for_linear and must match predict-time f64.
                X = data
            else:
                X = self._to_matrix(data)
            self.num_data, self.num_total_features = X.shape
        self._validate_metadata()
        names = self._resolve_feature_names(self.num_total_features)
        self.feature_names = names
        cat_idx = self._resolve_categorical(names)
        self.categorical_idx = cat_idx

        if self.reference is not None:
            ref = self.reference.construct()
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.feature_names = ref.feature_names
            self.categorical_idx = ref.categorical_idx
        elif self.bin_mappers:
            # pre-injected mappers (the distributed bin-boundary sync:
            # parallel/launch.py builds identical mappers on every
            # process from an all-gathered sample, the TPU-native
            # analog of DatasetLoader's distributed bin sync —
            # dataset_loader.cpp, UNVERIFIED)
            if len(self.bin_mappers) != self.num_total_features:
                log.fatal(
                    f"preset bin_mappers cover {len(self.bin_mappers)} "
                    f"features but the data has "
                    f"{self.num_total_features}")
            self.used_features = [i for i, m
                                  in enumerate(self.bin_mappers)
                                  if not m.is_trivial]
        else:
            from .. import obs
            from .binning import mappers_from_params
            with obs.span("ingest/find_bins"):
                self.bin_mappers = mappers_from_params(
                    X, self.params, categorical_idx=cat_idx)
            self.used_features = [i for i, m in enumerate(self.bin_mappers)
                                  if not m.is_trivial]
            if len(self.used_features) < self.num_total_features:
                n_drop = self.num_total_features - len(self.used_features)
                log.info(f"Dropped {n_drop} constant feature(s)")
            if not self.used_features:
                log.warning("There are no meaningful features which satisfy "
                            "the provided configuration.")

        dtype = self._binned_dtype_with_guard()
        if self._want_device_ingest(X, is_sparse, dtype):
            from ..ops.ingest import device_ingest
            self._ingest = device_ingest(
                X, self.bin_mappers, self.used_features, dtype,
                chunk_rows=get_param(self.params,
                                     "tpu_ingest_chunk_rows"),
                emit_transposed=self._want_transposed_ingest(dtype))
            self.binned = None    # host copy materializes lazily
        else:
            self.binned = self._bin_all_columns(X, is_sparse, dtype)
        from ..config import coerce_bool as _cb
        if _cb(self.params.get("linear_tree", False)):
            if is_sparse:
                log.fatal("linear_tree requires dense input data (leaf "
                          "ridge fits read raw feature values)")
            self._raw_for_linear = X[:, self.used_features].copy()
        self._constructed = True
        if self.free_raw_data:
            self.data = None
        return self

    def _want_device_ingest(self, X, is_sparse: bool, dtype) -> bool:
        """Route bin ASSIGNMENT to the accelerator (ops/ingest.py)?
        "true" forces; "auto" engages on a TPU backend for dense
        numeric ndarray input big enough to amortize the dispatch —
        but stands down when the binned matrix would not comfortably
        fit in HBM (the >HBM case belongs to the streaming engine's
        host-resident bins); "false" (or sparse / non-numeric / no
        usable features) keeps the host loop. Even forced "true"
        yields to a forced streaming engine (its host-block scan never
        adopts device bins — they would sit orphaned in HBM) and to
        categorical ids outside the exact float32/int32 window (the
        f32 chunk stream cannot represent them; the host int64 path
        can)."""
        from .. import capabilities
        from ..config import coerce_tristate, get_param
        mode = coerce_tristate(
            get_param(self.params, "tpu_ingest_device"),
            "tpu_ingest_device")
        if mode == "false":
            return False
        if (is_sparse or not isinstance(X, np.ndarray) or X.ndim != 2
                or X.dtype not in (np.float32, np.float64)
                or not self.used_features):
            return False
        forced = mode == "true"
        if capabilities.device_ingest_verdict(self.params) \
                != capabilities.SUPPORTED:
            # the engine these params force (the streaming engine's
            # host-block scan) never adopts device-resident bins — they
            # would sit orphaned in HBM; the capability table owns the
            # per-engine adoption verdicts (capabilities.DEVICE_INGEST)
            if forced:
                log.warning("tpu_ingest_device=true ignored: "
                            "tpu_streaming=true keeps bins "
                            "host-resident")
            return False
        from ..ops.ingest import cat_device_safe
        if not cat_device_safe(self.bin_mappers, self.used_features):
            if forced:
                log.warning("tpu_ingest_device=true ignored: "
                            "categorical ids exceed the exact "
                            "float32/int32 device window; binning "
                            "host-side")
            return False
        from ..utils.hbm import (STREAM_HBM_FRACTION, binned_device_bytes,
                                 hbm_bytes_limit)
        limit = hbm_bytes_limit()
        if limit:
            est = binned_device_bytes(
                self.num_data, len(self.used_features),
                np.dtype(dtype).itemsize,
                self._want_transposed_ingest(dtype))
            # budget 2x the resident size: the chunk parts AND the
            # final concatenated arrays are alive together at the end
            # of device_ingest, so transient peak is ~double. Even a
            # FORCED device ingest stands down here — past this size
            # auto-streaming (boosting._should_stream, same helper)
            # picks the host-block engine, which never adopts device
            # bins: they would sit orphaned in HBM
            if 2 * est > STREAM_HBM_FRACTION * limit:
                if forced:
                    log.warning("tpu_ingest_device=true ignored: binned "
                                "matrix too large to sit comfortably in "
                                "HBM (streaming territory); binning "
                                "host-side")
                return False
        # a distributed learner on >1 device will SHARD host numpy in
        # _DeviceData — device-resident single-device bins would just be
        # materialized back to host and re-uploaded sharded (strictly
        # slower than host binning), so even forced mode stands down
        import jax
        if jax.device_count() > 1:
            tl = str(get_param(self.params, "tree_learner")).lower()
            if tl != "serial":
                if forced:
                    log.warning("tpu_ingest_device=true ignored: a "
                                "distributed tree_learner shards "
                                "host-binned data; binning host-side")
                return False
        if forced:
            return True
        if jax.default_backend() != "tpu" or self.num_data < 65_536:
            return False
        return True

    def _want_transposed_ingest(self, dtype) -> bool:
        """Emit the feature-major int8 ``bins_t`` tile during ingest?
        Where the engine's Pallas kernel will run, so the host transpose
        in ``_DeviceData`` never runs — the fused kernel writes both
        layouts per chunk."""
        from ..capabilities import pallas_histogram_runs
        from ..config import get_param
        return pallas_histogram_runs(
            np.iinfo(dtype).max + 1,
            get_param(self.params, "tpu_double_precision_hist"))

    def _bin_all_columns(self, X, is_sparse: bool, dtype,
                         n_rows: int = None) -> np.ndarray:
        """Pack the binned matrix [n, n_used]. Dense row-major input
        takes ONE native row-major pass over all numeric columns
        (native/binning.cpp bin_matrix — column-at-a-time binning
        cache-misses every strided read); categorical columns and the
        fallbacks go per-column."""
        used = self.used_features
        if n_rows is None:
            n_rows = self.num_data
        if not used:
            return np.zeros((n_rows, 0), dtype=dtype)
        from ..config import get_param
        from .binning import _native
        lib = _native()
        dense_fast = (lib is not None and not is_sparse
                      and isinstance(X, np.ndarray) and X.ndim == 2
                      and X.dtype in (np.float32, np.float64)
                      and X.flags.c_contiguous
                      and n_rows > 65536)
        if dense_fast:
            import ctypes
            n_cols = len(used)
            is_num = np.array(
                [self.bin_mappers[f].bin_type != BIN_TYPE_CATEGORICAL
                 for f in used], dtype=np.int32)
            ubs = [np.ascontiguousarray(
                       self.bin_mappers[f].bin_upper_bound
                       if is_num[j] else np.zeros(1), dtype=np.float64)
                   for j, f in enumerate(used)]
            ub_off = np.zeros(n_cols + 1, dtype=np.int64)
            np.cumsum([len(u) for u in ubs], out=ub_off[1:])
            ub_concat = np.concatenate(ubs)
            mt_code = {"none": 0, "zero": 1, "nan": 2}
            meta_mt = np.array(
                [mt_code.get(self.bin_mappers[f].missing_type, 0)
                 for f in used], dtype=np.int32)
            meta_db = np.array(
                [self.bin_mappers[f].default_bin for f in used],
                dtype=np.int64)
            meta_nb = np.array(
                [self.bin_mappers[f].num_bin for f in used],
                dtype=np.int64)
            col_idx = np.array(used, dtype=np.int64)
            out = np.empty((n_rows, n_cols), dtype=dtype)
            out_kind = {np.uint8: 0, np.uint16: 1,
                        np.int32: 2}[np.dtype(dtype).type]
            c = ctypes
            row_stride = X.strides[0] // X.itemsize

            def bin_rows(s: int, e: int) -> None:
                lib.bin_matrix(
                    c.c_void_p(X.ctypes.data
                               + s * row_stride * X.itemsize),
                    int(X.dtype == np.float32), e - s, row_stride,
                    col_idx.ctypes.data_as(c.POINTER(c.c_int64)),
                    n_cols,
                    ub_concat.ctypes.data_as(c.POINTER(c.c_double)),
                    ub_off.ctypes.data_as(c.POINTER(c.c_int64)),
                    meta_mt.ctypes.data_as(c.POINTER(c.c_int32)),
                    meta_db.ctypes.data_as(c.POINTER(c.c_int64)),
                    meta_nb.ctypes.data_as(c.POINTER(c.c_int64)),
                    is_num.ctypes.data_as(c.POINTER(c.c_int32)),
                    c.c_void_p(out.ctypes.data
                               + s * n_cols * out.itemsize), out_kind)

            # row-chunked thread parallelism over the native pass:
            # ctypes releases the GIL for the call's duration and each
            # chunk writes a disjoint out slice, so the kernel scales
            # with cores (it is per-value binary search — pure CPU)
            n_threads = min(
                resolve_ingest_threads(
                    get_param(self.params, "tpu_ingest_threads")),
                max(n_rows // 262_144, 1))
            if n_threads > 1:
                from concurrent.futures import ThreadPoolExecutor
                blk = -(-n_rows // n_threads)
                spans = [(s, min(s + blk, n_rows))
                         for s in range(0, n_rows, blk)]
                with ThreadPoolExecutor(max_workers=n_threads) as ex:
                    list(ex.map(lambda se: bin_rows(*se), spans))
            else:
                bin_rows(0, n_rows)
            for j, f in enumerate(used):     # categorical remainder
                if not is_num[j]:
                    out[:, j] = self.bin_mappers[f].values_to_bins(
                        X[:, f]).astype(dtype)
            return out

        def col_values(f):
            if is_sparse:
                # X is the CSC matrix here (construct passes it through)
                colv = np.zeros(n_rows, np.float64)
                sl = slice(X.indptr[f], X.indptr[f + 1])
                colv[X.indices[sl]] = X.data[sl]
                return colv
            return X[:, f]

        # per-column fallback: thread-pooled for non-accelerator users
        # (numpy's searchsorted/unique release the GIL, so columns bin
        # in parallel); small jobs keep the serial loop — pool startup
        # would dominate
        n_threads = min(
            resolve_ingest_threads(
                get_param(self.params, "tpu_ingest_threads")),
            len(used))
        if n_threads > 1 and n_rows * len(used) >= 2_000_000:
            from concurrent.futures import ThreadPoolExecutor
            out = np.empty((n_rows, len(used)), dtype=dtype)

            def bin_one(jf):
                j, f = jf
                out[:, j] = self.bin_mappers[f].values_to_bins(
                    col_values(f))

            with ThreadPoolExecutor(max_workers=n_threads) as ex:
                list(ex.map(bin_one, enumerate(used)))
            return out
        return np.stack(
            [self.bin_mappers[f].values_to_bins(col_values(f))
             .astype(dtype) for f in used], axis=1)

    # ------------------------------------------------------------------
    def _binned_dtype_with_guard(self):
        """Bin-id dtype for the packed matrix + the host-RAM capacity
        guard: fail with a clear message BEFORE allocating a binned
        matrix that cannot fit (file input can stream out-of-core via
        two_round=true, but the BINNED matrix itself must fit)."""
        max_num_bin = max((self.bin_mappers[f].num_bin
                           for f in self.used_features), default=2)
        dtype = np.uint8 if max_num_bin <= 256 else np.uint16
        est = (int(self.num_data) * max(len(self.used_features), 1)
               * np.dtype(dtype).itemsize)
        budget = _host_mem_bytes()
        if budget is not None and est > 0.9 * budget:
            log.fatal(
                f"binned dataset ({self.num_data} rows x "
                f"{len(self.used_features)} features) would need "
                f"{est / 2**30:.1f} GiB — more than 90% of host RAM "
                f"({budget / 2**30:.1f} GiB). Reduce rows/features, "
                f"lower max_bin to fit uint8, or shard rows across "
                f"hosts (parallel/multihost.py)")
        return dtype

    def _construct_streamed(self) -> "Dataset":
        """Two-round out-of-core load (dataset_loader.cpp two-round path
        + utils/pipeline_reader.h, UNVERIFIED — empty mount): round 1
        streams the file to draw a uniform row sample (bottom-k keys =
        sampling without replacement) and collect the small metadata
        columns; round 2 streams again, binning each chunk directly into
        the preallocated packed matrix. Peak memory is the BINNED matrix
        (1-2 bytes/cell) + one raw chunk — never the n x F float64 raw
        matrix."""
        from ..config import coerce_bool, get_param
        from .text_loader import iter_text_chunks
        p = self.params
        sp = self._stream_cols
        if coerce_bool(p.get("linear_tree", False)):
            log.fatal("two_round streaming cannot keep the raw feature "
                      "matrix linear_tree needs; load in one round")
        chunk_rows = get_param(p, "tpu_stream_chunk_rows")
        cap = int(p.get("bin_construct_sample_cnt", 200000))
        rng = np.random.default_rng(int(p.get("data_random_seed", 1)))

        def chunks():
            return iter_text_chunks(
                self._stream_path, chunk_rows=chunk_rows,
                label_column=sp.get("label_column", "auto"),
                weight_column=sp.get("weight_column"),
                group_column=sp.get("group_column"),
                ignore_column=sp.get("ignore_column"),
                has_header=(coerce_bool(sp["header"]) if "header" in sp
                            else None))

        # ---- round 1: sample + metadata (a valid set built against a
        # reference skips the sample pool and adopts the reference's
        # mappers, mirroring the one-round path) -----------------------
        use_ref = self.reference is not None
        pool_X = pool_keys = None
        labels, weights, qids = [], [], []
        n_total = 0
        feat_names = None
        for ch in chunks():
            n_total += len(ch.X)
            feat_names = ch.feature_names or feat_names
            if ch.label is not None:
                labels.append(ch.label)
            if ch.weight is not None:
                weights.append(ch.weight)
            if ch.qid is not None:
                qids.append(ch.qid)
            n_feat_seen = ch.X.shape[1]
            if use_ref:
                continue
            keys = rng.random(len(ch.X))
            if pool_X is None:
                pool_X, pool_keys = ch.X, keys
            else:
                pool_X = np.concatenate([pool_X, ch.X])
                pool_keys = np.concatenate([pool_keys, keys])
            if len(pool_keys) > cap:
                top = np.argpartition(pool_keys, cap)[:cap]
                pool_X, pool_keys = pool_X[top], pool_keys[top]
        if n_total == 0:
            log.fatal(f"Data file {self._stream_path} is empty")
        self.num_data = n_total
        self.num_total_features = n_feat_seen
        if self.metadata.label is None and labels:
            self.metadata.label = np.concatenate(labels)
        if self.metadata.weight is None and weights:
            self.metadata.weight = np.concatenate(weights)
        if self.metadata.query_boundaries is None and qids:
            qid = np.concatenate(qids)
            change = np.flatnonzero(np.diff(qid) != 0) + 1
            self.metadata.set_group(np.diff(
                np.concatenate([[0], change, [len(qid)]])))
        # sidecar files, like the one-round loader (metadata.cpp:
        # <data>.weight / <data>.query)
        import os as _os
        if self.metadata.weight is None \
                and _os.path.exists(self._stream_path + ".weight"):
            self.metadata.weight = np.loadtxt(
                self._stream_path + ".weight", dtype=np.float64).ravel()
        if self.metadata.query_boundaries is None \
                and _os.path.exists(self._stream_path + ".query"):
            self.metadata.set_group(np.loadtxt(
                self._stream_path + ".query", dtype=np.int64).ravel())
        self._validate_metadata()
        if use_ref:
            ref = self.reference.construct()
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.feature_names = ref.feature_names
            self.categorical_idx = ref.categorical_idx
            if self.num_total_features != ref.num_total_features:
                log.fatal(f"streamed file has {self.num_total_features} "
                          f"features, reference has "
                          f"{ref.num_total_features}")
        else:
            self.feature_names = (feat_names if feat_names else
                                  [f"Column_{i}" for i in
                                   range(self.num_total_features)])
            cat_idx = self._resolve_categorical(self.feature_names)
            self.categorical_idx = cat_idx
            self.bin_mappers = find_bin_mappers(
                pool_X,
                max_bin=int(p.get("max_bin", 255)),
                min_data_in_bin=int(p.get("min_data_in_bin", 3)),
                sample_cnt=cap,
                use_missing=coerce_bool(p.get("use_missing", True)),
                zero_as_missing=coerce_bool(p.get("zero_as_missing",
                                                  False)),
                categorical_features=cat_idx,
                max_bin_by_feature=p.get("max_bin_by_feature"),
                seed=int(p.get("data_random_seed", 1)),
                n_threads=resolve_ingest_threads(
                    get_param(p, "tpu_ingest_threads")),
                forced_bins=(load_forced_bins(
                    str(p["forcedbins_filename"]))
                    if p.get("forcedbins_filename") else None))
            del pool_X, pool_keys
            self.used_features = [
                i for i, m in enumerate(self.bin_mappers)
                if not m.is_trivial]
            if not self.used_features:
                log.warning("There are no meaningful features which "
                            "satisfy the provided configuration.")

        # ---- round 2: bin chunk-by-chunk into the packed matrix ------
        # each chunk goes through _bin_all_columns — the SAME ingest
        # path push_rows and construct use (native one-pass row-major
        # binning, thread-pooled fallback) — instead of the per-column
        # strided loop; peak memory stays one raw chunk + the binned
        # matrix (pinned by the peak-RSS test in test_io_files.py)
        dtype = self._binned_dtype_with_guard()
        self.binned = np.empty((n_total, len(self.used_features)),
                               dtype=dtype)
        r0 = 0
        for ch in chunks():
            r1 = r0 + len(ch.X)
            self.binned[r0:r1] = self._bin_all_columns(
                np.ascontiguousarray(ch.X), False, dtype,
                n_rows=len(ch.X))
            r0 = r1
        if r0 != n_total:
            log.fatal(f"file changed between streaming rounds: "
                      f"{r0} rows vs {n_total}")
        self._constructed = True
        log.info(f"two_round: streamed {n_total} rows x "
                 f"{self.num_total_features} features into a "
                 f"{self.binned.nbytes / 2**20:.0f} MiB binned matrix")
        return self

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, params=params,
                       free_raw_data=self.free_raw_data)

    # ------------------------------------------------------------------
    def push_rows(self, chunk, label=None, weight=None) -> "Dataset":
        """Streaming row ingestion (LGBM_DatasetPushRows / the streaming
        C API seam, c_api.cpp — UNVERIFIED). Build with ``Dataset(None,
        reference=...)`` and push row chunks; with a reference whose bin
        mappers exist, each chunk is binned IMMEDIATELY and the raw
        floats are dropped (true streaming memory behavior). Without a
        reference, raw chunks accumulate until ``construct`` samples
        them for binning."""
        if self._constructed:
            log.fatal("push_rows after construct()")
        if self.data is not None:
            log.fatal("push_rows requires Dataset(None, ...)")
        chunk = self._to_matrix(chunk)
        if not hasattr(self, "_pushed"):
            self._pushed, self._pushed_meta = [], {"label": [],
                                                   "weight": []}
        if self.reference is not None:
            ref = self.reference.construct()
            if chunk.shape[1] != ref.num_total_features:
                log.fatal(f"pushed chunk has {chunk.shape[1]} features, "
                          f"reference has {ref.num_total_features}")
            dtype = ref.binned_dtype()
            if ref.used_features:
                # native one-pass binning (same hot path construct and
                # predict use) — the per-column Python fallback is
                # ~200x slower, which matters exactly here: push_rows
                # is the >HBM streaming ingest path
                self._pushed.append(
                    ref._bin_all_columns(chunk, False, dtype,
                                         n_rows=len(chunk)))
            else:
                self._pushed.append(
                    np.zeros((len(chunk), 0), dtype))
        else:
            self._pushed.append(chunk)
        if label is not None:
            self._pushed_meta["label"].append(_coerce_1d(label).ravel())
        if weight is not None:
            self._pushed_meta["weight"].append(_coerce_1d(weight).ravel())
        return self

    def _validate_metadata(self) -> None:
        """Length-check every metadata field against num_data (the
        reference validates all Metadata fields at construct;
        metadata.cpp — UNVERIFIED)."""
        n = self.num_data
        md = self.metadata
        for fname in ("label", "weight", "position"):
            v = getattr(md, fname)
            if v is not None and len(v) != n:
                log.fatal(f"Length of {fname} ({len(v)}) does not "
                          f"match number of data ({n})")
        if md.init_score is not None:
            m = len(np.asarray(md.init_score).ravel())
            # num_data, or num_data * num_class for multiclass
            if m != n and (n == 0 or m % n != 0):
                log.fatal(f"Length of init_score ({m}) does not match "
                          f"number of data ({n})")
        if md.query_boundaries is not None \
                and int(md.query_boundaries[-1]) != n:
            log.fatal(f"Sum of query counts "
                      f"({int(md.query_boundaries[-1])}) does not match "
                      f"number of data ({n})")

    def _finish_pushed(self) -> bool:
        """Finalize streamed rows at construct time; True if handled
        fully (reference path: chunks are already binned)."""
        if not getattr(self, "_pushed", None):
            return False
        if self._pushed_meta["label"]:
            self.metadata.label = np.concatenate(
                self._pushed_meta["label"])
        if self._pushed_meta["weight"]:
            self.metadata.weight = np.concatenate(
                self._pushed_meta["weight"])
        # free the metadata chunk lists in BOTH branches (at 1e9+
        # streamed rows the retained label chunks alone are ~10 GB)
        self._pushed_meta = {"label": [], "weight": []}
        if self.reference is not None:
            ref = self.reference.construct()
            self.binned = np.concatenate(self._pushed, axis=0)
            self.num_data = len(self.binned)
            self._validate_metadata()
            self.num_total_features = ref.num_total_features
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.feature_names = ref.feature_names
            self.categorical_idx = ref.categorical_idx
            self._pushed = []
            self._constructed = True
            return True
        # no reference: hand the stacked raw rows to the normal path
        self.data = np.concatenate(self._pushed, axis=0)
        self._pushed = []
        return False

    def set_label(self, label) -> "Dataset":
        self.metadata.label = _coerce_1d(label).ravel()
        return self

    def set_weight(self, weight) -> "Dataset":
        self.metadata.weight = (None if weight is None else
                                _coerce_1d(weight).ravel())
        return self

    def set_group(self, group) -> "Dataset":
        self.metadata.set_group(None if group is None
                                else _coerce_1d(group))
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.metadata.init_score = (None if init_score is None else
                                    _coerce_1d(init_score))
        return self

    def set_position(self, position) -> "Dataset":
        self.metadata.position = (None if position is None else
                                  _coerce_1d(position).astype(np.int32))
        return self

    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name == "label":
            return self.set_label(data)
        if field_name == "weight":
            return self.set_weight(data)
        if field_name == "group":
            return self.set_group(data)
        if field_name == "init_score":
            return self.set_init_score(data)
        if field_name == "position":
            return self.set_position(data)
        log.fatal(f"Unknown field name {field_name}")

    def get_field(self, field_name: str):
        if field_name == "label":
            return self.metadata.label
        if field_name == "weight":
            return self.metadata.weight
        if field_name == "group":
            return self.metadata.query_boundaries
        if field_name == "init_score":
            return self.metadata.init_score
        if field_name == "position":
            return self.metadata.position
        log.fatal(f"Unknown field name {field_name}")

    def get_label(self):
        return self.metadata.label

    def get_weight(self):
        return self.metadata.weight

    def get_group(self):
        qb = self.metadata.query_boundaries
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        return self.metadata.init_score

    def _init_from_file(self, path: str) -> None:
        """Load from disk: the framework's binary dataset format
        (save_binary) or CSV/TSV/LibSVM text (DatasetLoader::LoadFromFile
        semantics — label/weight/group columns + sidecar files)."""
        import pickle
        with open(path, "rb") as f:
            magic = f.read(8)
        if magic == b"LGBTBIN1":
            with open(path, "rb") as f:
                f.read(8)
                state = pickle.load(f)
            user_md = self.metadata
            user_params = self.params
            for k, v in state.items():
                setattr(self, k, v)
            # user-passed metadata/params override the stored copies
            for field in ("label", "weight", "init_score",
                          "query_boundaries"):
                v = getattr(user_md, field)
                if v is not None:
                    setattr(self.metadata, field, v)
            self.params = {**self.params, **user_params}
            self._constructed = True
            self.data = None
            return
        from ..config import Config, coerce_bool
        from .text_loader import load_text
        # resolve reference aliases (label=, weight=, group=/query=,
        # has_header=, ignore_feature=...) to canonical names
        p = {Config.canonical_name(k): v for k, v in self.params.items()}
        if coerce_bool(p.get("two_round", False)):
            # out-of-core two-round load: defer to construct(), which
            # streams the file twice (sample pass + binning pass) and
            # never materializes the raw matrix
            self._stream_path = path
            self._stream_cols = p
            return
        loaded = load_text(
            path,
            label_column=p.get("label_column", "auto"),
            weight_column=p.get("weight_column"),
            group_column=p.get("group_column"),
            ignore_column=p.get("ignore_column"),
            has_header=(coerce_bool(p["header"]) if "header" in p
                        else None))
        self.data = loaded.X
        if self.metadata.label is None and loaded.label is not None:
            self.metadata.label = loaded.label.astype(np.float64)
        if self.metadata.weight is None and loaded.weight is not None:
            self.metadata.weight = loaded.weight.astype(np.float64)
        if self.metadata.query_boundaries is None \
                and loaded.group is not None:
            self.metadata.set_group(loaded.group)
        if self.feature_name == "auto" and loaded.feature_names:
            self.feature_name = loaded.feature_names

    def save_binary(self, path: str) -> "Dataset":
        """Serialize the CONSTRUCTED dataset (binned matrix + mappers +
        metadata) — the reference's binary dataset file
        (dataset.cpp SaveBinaryFile), loadable via Dataset(path)."""
        import pickle
        self.construct()
        state = {k: getattr(self, k) for k in (
            "binned", "bin_mappers", "used_features", "feature_names",
            "categorical_idx", "num_total_features", "num_data",
            "metadata", "params")}
        with open(path, "wb") as f:
            f.write(b"LGBTBIN1")
            pickle.dump(state, f, protocol=pickle.HIGHEST_PROTOCOL)
        return self

    def num_feature(self) -> int:
        self.construct()
        return len(self.used_features)

    def num_data_(self) -> int:
        self.construct()
        return self.num_data

    def __len__(self) -> int:
        if self._constructed:
            return self.num_data
        if self.data is None:             # push_rows-style streaming
            return sum(len(c) for c in getattr(self, "_pushed", []))
        if hasattr(self.data, "shape"):   # ndarray/scipy/pandas — no
            return int(self.data.shape[0])  # densifying coercion
        if hasattr(self.data, "num_rows"):  # pyarrow
            return int(self.data.num_rows)
        return len(self._to_matrix(self.data))

    # ------------------------------------------------------------------
    def subset(self, used_indices: Sequence[int],
               params: Optional[Dict[str, Any]] = None) -> "Dataset":
        """Row-subset sharing this dataset's bin mappers (for cv folds)."""
        self.construct()
        idx = np.asarray(used_indices, dtype=np.int64)
        sub = Dataset.__new__(Dataset)
        sub.data = None
        sub.params = dict(params or self.params)
        sub.reference = self
        sub.free_raw_data = self.free_raw_data
        sub.feature_name = self.feature_name
        sub.categorical_feature = self.categorical_feature
        sub.pandas_categorical = self.pandas_categorical
        sub.metadata = Metadata()
        md = self.metadata
        if md.label is not None:
            sub.metadata.label = md.label[idx]
        if md.weight is not None:
            sub.metadata.weight = md.weight[idx]
        if md.init_score is not None:
            sub.metadata.init_score = np.asarray(md.init_score)[idx]
        if md.position is not None:
            sub.metadata.position = md.position[idx]
        if md.query_boundaries is not None:
            # rebuild query boundaries from per-row query ids; assumes idx
            # keeps whole queries together (cv's group-aware folds do)
            qid = np.searchsorted(md.query_boundaries, idx,
                                  side="right") - 1
            change = np.flatnonzero(np.diff(qid)) + 1
            counts = np.diff(np.concatenate([[0], change, [len(idx)]]))
            sub.metadata.set_group(counts)
        sub._constructed = True
        sub.bin_mappers = self.bin_mappers
        sub.binned = self.binned[idx]
        sub.used_features = self.used_features
        sub.feature_names = self.feature_names
        sub.categorical_idx = self.categorical_idx
        sub.num_total_features = self.num_total_features
        sub.num_data = len(idx)
        sub._raw_for_linear = (None if self._raw_for_linear is None
                               else self._raw_for_linear[idx])
        return sub

    # ------------------------------------------------------------------
    def feature_num_bins(self) -> np.ndarray:
        """num_bin per used feature (padded arrays for the jit learner)."""
        self.construct()
        return np.array([self.bin_mappers[f].num_bin
                         for f in self.used_features], dtype=np.int32)
