"""Device-side dataset ingest: chunked on-accelerator bin assignment.

Reference: ``Dataset::Construct`` + ``BinMapper::ValueToBin`` +
``DenseBin::Push`` (src/io/dataset.cpp, include/LightGBM/bin.h,
UNVERIFIED — empty mount, see SURVEY.md banner): the reference binds the
full raw matrix on CPU, one value at a time, as a one-time load cost.

TPU-first inversion: bin-boundary *finding* stays host-side (it runs on
a bounded sample and is semantics-heavy — binning.py), but bin
*assignment* of the full ``[n, F]`` raw matrix moves onto the
accelerator. Raw float32 row chunks stream host→device with async
dispatch double-buffering (the ``copy_to_host_async`` discipline of
``GBDT._run_forest_chunks``, inverted), every feature is bucketized at
once against a padded ``[F, B]`` boundary matrix (a vectorized
``searchsorted``), missing/zero/categorical mapping applies on device,
and the kernel emits BOTH layouts the training engine consumes — the
row-major uint8/uint16 block and the feature-major int8 ``bins_t`` tile
— so the host transpose in ``_DeviceData`` disappears entirely.

Exactness contract (pinned by tests/test_ingest.py): device-assigned
bins are bit-identical to the host ``BinMapper.values_to_bins`` path for
every input value that is exactly float32-representable (float32 inputs
always; float64 inputs whose values round-trip through float32 — e.g.
any f32-generated matrix). The trick making a float32 compare exact
against float64 boundaries: each boundary ``b`` is replaced by the
smallest float32 STRICTLY greater than ``b`` (``_f32_exclusive``), so
``count(b < v)`` over f64 equals ``count(b32' <= v)`` over f32 — a
``side="right"`` searchsorted. Genuinely-f64 values within half an f32
ulp of a boundary may land one bin off; ``tpu_ingest_device=auto`` still
takes the device path for f64 input (bin edges are themselves sample
quantiles — a half-ulp edge shift is far below the binning noise floor),
and ``false`` restores the host path for strict f64 semantics.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

MT_CODE = {"none": 0, "zero": 1, "nan": 2}

# int32 pad value for the SORTED categorical table: sorts past every
# real id (cat_device_safe guarantees real ids are < 2**31 - 128, the
# largest float32 below 2**31) and can never equal a candidate value
_CAT_PAD = np.int32(2**31 - 1)


def cat_device_safe(bin_mappers, used_features: Sequence[int]) -> bool:
    """True when every categorical feature's seen category ids survive
    the device path EXACTLY: raw chunks stream as float32 and the
    lookup table is int32, so each id must be int32-range and exactly
    float32-representable. ``Dataset._want_device_ingest`` gates on
    this (ids outside the window — e.g. 64-bit hashes — keep the host
    int64 path, which handles them exactly)."""
    from ..io.binning import BIN_TYPE_CATEGORICAL
    for f in used_features:
        m = bin_mappers[f]
        if m.bin_type != BIN_TYPE_CATEGORICAL or m.bin_to_cat is None:
            continue
        cv = np.asarray(m.bin_to_cat[1:], dtype=np.int64)
        if not len(cv):
            continue
        if ((cv >= 2**31) | (cv <= -2**31)).any():
            return False
        if (cv.astype(np.float32).astype(np.int64) != cv).any():
            return False
    return True


def _f32_exclusive(bounds: np.ndarray) -> np.ndarray:
    """Smallest float32 strictly greater than each float64 bound.

    For a float32 value v and float64 bound b:  (b < v)  <=>  (b32' <= v)
    where b32' = min{float32 x : x > b}. This turns the host's f64
    ``searchsorted(side="left")`` (count of bounds < v) into an exact
    f32 ``searchsorted(side="right")`` (count of b32' <= v) for every
    f32-representable v. +inf maps to +inf (the terminator bin catches
    +inf values via the final clip, matching the host clip).
    """
    b = np.asarray(bounds, dtype=np.float64)
    c = b.astype(np.float32)
    # where the round-to-nearest f32 is <= b, step up one ulp
    need_up = c.astype(np.float64) <= b
    up = np.nextafter(c, np.float32(np.inf), dtype=np.float32)
    out = np.where(need_up, up, c)
    out[np.isposinf(b)] = np.inf
    return out.astype(np.float32)


@dataclasses.dataclass
class IngestTables:
    """Padded per-used-feature mapping tables for the device kernel.

    All arrays are host numpy; ``device_ingest`` uploads them once per
    construct (they are tiny: F x max_bin floats).
    """

    ub: np.ndarray          # [Fu, B] f32 exclusive upper bounds (+inf pad)
    n_ub: np.ndarray        # [Fu] int32 — real bound count per feature
    mt: np.ndarray          # [Fu] int32 missing_type code (MT_CODE)
    default_bin: np.ndarray  # [Fu] int32
    num_bin: np.ndarray     # [Fu] int32
    is_cat: np.ndarray      # [Fu] bool
    cat_sorted: np.ndarray  # [Fu, C] int32 category values, ASCENDING
    cat_perm: np.ndarray    # [Fu, C] int32 bin index per sorted slot
    out_dtype: np.dtype     # uint8 / uint16 row-major bin dtype


def build_tables(bin_mappers, used_features: Sequence[int],
                 out_dtype) -> IngestTables:
    """Flatten the used features' BinMappers into padded device tables."""
    from ..io.binning import BIN_TYPE_CATEGORICAL
    used = list(used_features)
    if not cat_device_safe(bin_mappers, used):
        raise ValueError(
            "categorical ids outside the exact float32/int32 device "
            "window — the host path must bin this dataset "
            "(Dataset._want_device_ingest gates on cat_device_safe)")
    Fu = len(used)
    n_ub = np.ones(Fu, dtype=np.int32)
    mt = np.zeros(Fu, dtype=np.int32)
    dbin = np.zeros(Fu, dtype=np.int32)
    nbin = np.ones(Fu, dtype=np.int32)
    is_cat = np.zeros(Fu, dtype=bool)
    ubs: List[np.ndarray] = []
    cats: List[np.ndarray] = []
    for j, f in enumerate(used):
        m = bin_mappers[f]
        mt[j] = MT_CODE.get(m.missing_type, 0)
        dbin[j] = int(m.default_bin)
        nbin[j] = int(m.num_bin)
        if m.bin_type == BIN_TYPE_CATEGORICAL:
            is_cat[j] = True
            # bin_to_cat[0] is the NaN/unseen slot; slots 1.. hold the
            # raw category values, bin index = slot index
            cats.append(np.asarray(m.bin_to_cat[1:], dtype=np.int64))
            ubs.append(np.asarray([np.inf]))
            n_ub[j] = 1
        else:
            ub = np.asarray(m.bin_upper_bound, dtype=np.float64)
            n_ub[j] = len(ub)
            ubs.append(ub)
            cats.append(np.empty(0, dtype=np.int64))
    B = max((len(u) for u in ubs), default=1)
    C = max((len(c) for c in cats), default=0)
    ub_pad = np.full((Fu, B), np.inf, dtype=np.float32)
    for j, u in enumerate(ubs):
        ub_pad[j, :len(u)] = _f32_exclusive(u)
    # sorted table + permutation: slot k of bin_to_cat[1:] is bin k+1,
    # so the kernel binary-searches cat_sorted and maps the hit position
    # through cat_perm back to the bin index
    cat_sorted = np.full((Fu, max(C, 1)), _CAT_PAD, dtype=np.int32)
    cat_perm = np.zeros((Fu, max(C, 1)), dtype=np.int32)
    for j, cv in enumerate(cats):
        if len(cv):
            order = np.argsort(cv, kind="stable")
            cat_sorted[j, :len(cv)] = cv[order].astype(np.int32)
            cat_perm[j, :len(cv)] = order.astype(np.int32) + 1
    return IngestTables(ub=ub_pad, n_ub=n_ub, mt=mt, default_bin=dbin,
                        num_bin=nbin, is_cat=is_cat, cat_sorted=cat_sorted,
                        cat_perm=cat_perm, out_dtype=np.dtype(out_dtype))


def _assign_chunk_impl(raw, ub, n_ub, mt, default_bin, num_bin, is_cat,
                       cat_sorted, cat_perm, out_dtype, emit_transposed,
                       cat_cols=()):
    """One chunk of rows through the full mapping, on device.

    raw: ``[R, Fu]`` float32 (NaN = missing). ``cat_cols`` (static): the
    positions of the categorical columns; each kind of column goes
    through its own search only. Returns the row-major ``[R, Fu]`` bin
    block, (optionally) the feature-major ``[Fu, R]`` int8 tile (uint8
    bits bitcast — the wraparound layout the Pallas histogram kernel
    reads) and, with categorical columns, the count of their cells whose
    id is in no bin.
    """
    import jax
    import jax.numpy as jnp
    nanm = jnp.isnan(raw)

    def numeric(cols):
        """Bins of the numeric columns ``cols`` (None = all)."""
        def take(a, axis=0):
            return a if cols is None else jnp.take(a, cols, axis=axis)
        nan_c = take(nanm, 1)
        v = jnp.where(nan_c, jnp.float32(0.0), take(raw, 1))
        # vectorized searchsorted(side="right") against the exclusive-
        # f32 bounds: one batched binary search per feature column
        # (padding bounds are +inf, so they only count for v=+inf —
        # removed by the same clip the host applies)
        cnt = jax.vmap(
            lambda bnd, col: jnp.searchsorted(bnd, col, side="right"),
            in_axes=(0, 1), out_axes=1)(take(ub), v).astype(jnp.int32)
        vb = jnp.minimum(cnt, take(n_ub)[None, :] - 1)
        miss = jnp.where(take(mt)[None, :] == 2, take(num_bin)[None, :] - 1,
                         take(default_bin)[None, :])
        return jnp.where(nan_c, jnp.broadcast_to(miss, vb.shape), vb)

    other = None
    if not cat_cols:
        out = numeric(None)
    else:
        # categorical: truncate-toward-zero int cast (the host's
        # .astype(int64)); NaN -> -1 (the host's missing sentinel),
        # inf / out-of-int32-range -> INT32_MIN (matches no table entry
        # — build_tables guarantees real ids are int32-safe via
        # cat_device_safe). The lookup compares a cell with EVERY id of
        # its column's table inside one fused reduction (no [R, Fc, C]
        # array is written, and no gather is made: a binary search's
        # gathers cost this chip 10 ns a cell and step, the compares
        # next to nothing); ids are distinct in a table, so the sum of
        # the hits' bins is the one hit's bin, and a miss is bin 0.
        from .. import obs
        cc = np.asarray(cat_cols, dtype=np.int32)
        nc = np.setdiff1d(np.arange(raw.shape[1], dtype=np.int32), cc)
        with obs.scope("ingest/cat_lookup"):
            raw_c, nan_c = jnp.take(raw, cc, axis=1), jnp.take(nanm, cc,
                                                              axis=1)
            inr = ((raw_c >= jnp.float32(-2**31))
                   & (raw_c < jnp.float32(2**31)))
            iv = jnp.where(nan_c, jnp.float32(-1.0),
                           jnp.where(inr, raw_c, jnp.float32(-2**31))
                           ).astype(jnp.int32)
            eq = iv[:, :, None] == jnp.take(cat_sorted, cc, axis=0)[None]
            cb = jnp.sum(jnp.where(eq, jnp.take(cat_perm, cc, axis=0)[None],
                                   0), axis=2, dtype=jnp.int32)
            # cells that hold an id (not NaN) which no bin of the
            # column's table holds: the `ingest.cat_other` counter
            other = jnp.sum(~nan_c & (cb == 0), dtype=jnp.int32)
        parts = [cb] if not len(nc) else [numeric(nc), cb]
        order = np.argsort(np.concatenate([nc, cc]), kind="stable")
        out = jnp.take(jnp.concatenate(parts, axis=1), order, axis=1)
    row = out.astype(out_dtype)
    if not emit_transposed:
        return row, None, other
    bt = jax.lax.bitcast_convert_type(out.T.astype(jnp.uint8), jnp.int8)
    return row, bt, other


_ASSIGN_JIT = None


def _assign_chunk(*args, **kwargs):
    """Jit wrapper built lazily so importing this module never touches
    jax (io/dataset.py imports stay accelerator-free until used)."""
    global _ASSIGN_JIT
    if _ASSIGN_JIT is None:
        import functools

        import jax

        from .. import obs
        _ASSIGN_JIT = functools.partial(
            jax.jit, static_argnames=("out_dtype", "emit_transposed",
                                      "cat_cols"))(
            obs.scope("ingest/assign")(_assign_chunk_impl))
    return _ASSIGN_JIT(*args, **kwargs)


def ingest_program_cache_size() -> int:
    """Distinct compiled bin-assignment programs held by this process
    (the warm-start contract: a second same-shape construct adds zero)."""
    return 0 if _ASSIGN_JIT is None else _ASSIGN_JIT._cache_size()


@dataclasses.dataclass
class DeviceIngestResult:
    """Device-resident binned matrix produced by ``device_ingest``.

    ``bins``: ``[n, Fu]`` uint8/uint16 row-major (device).
    ``bins_t``: ``[Fu, n]`` int8 feature-major (device) or None.
    The host copy is NOT materialized here — ``Dataset.binned``'s lazy
    property pulls it only for checkpoint / model-text / EFB paths.
    """

    bins: object
    bins_t: Optional[object]
    n_rows: int
    chunk_rows: int

    def host_binned(self) -> np.ndarray:
        # slice defensively: the engine swaps its row-PADDED device
        # array back into ``bins`` after adoption (so the unpadded
        # original's HBM is released) — host consumers always see
        # exactly the real rows
        return np.asarray(self.bins[:self.n_rows])


def device_ingest(X: np.ndarray, bin_mappers, used_features,
                  out_dtype, chunk_rows: int = 262_144,
                  emit_transposed: bool = False) -> DeviceIngestResult:
    """Bin the full raw matrix on the accelerator, chunk by chunk.

    ``X``: ``[n, F]`` float32/float64 host matrix (original feature
    indexing; only ``used_features`` columns are read). Chunks are cast
    to float32 on host (cheap, parallel with device compute thanks to
    async dispatch) and streamed H2D double-buffered: while the device
    bucketizes chunk i, the host slices/casts chunk i+1 — the inverse of
    the predict path's ``copy_to_host_async`` overlap. Every chunk is
    the SAME padded shape, so the kernel compiles exactly once per
    (chunk_rows, Fu, B) family — and with a persistent compilation cache
    (``tpu_compile_cache_dir``) only once per machine.
    """
    import jax
    import jax.numpy as jnp

    from .. import obs
    used = list(used_features)
    n = int(X.shape[0])
    Fu = len(used)
    tables = build_tables(bin_mappers, used, out_dtype)
    out_jdtype = jnp.uint8 if tables.out_dtype == np.uint8 else jnp.uint16
    dev_tables = (jnp.asarray(tables.ub), jnp.asarray(tables.n_ub),
                  jnp.asarray(tables.mt), jnp.asarray(tables.default_bin),
                  jnp.asarray(tables.num_bin), jnp.asarray(tables.is_cat),
                  jnp.asarray(tables.cat_sorted),
                  jnp.asarray(tables.cat_perm))
    cat_cols = tuple(int(j) for j in np.flatnonzero(tables.is_cat))
    R = max(min(int(chunk_rows), max(n, 1)), 1)
    # single-chunk jobs skip the chunk-shape padding entirely
    col_sel = np.asarray(used, dtype=np.intp)
    take_all = Fu == X.shape[1] and np.array_equal(col_sel,
                                                   np.arange(Fu))

    def host_prep(s: int, e: int) -> np.ndarray:
        blk = X[s:e] if take_all else X[s:e][:, col_sel]
        blk = np.ascontiguousarray(blk, dtype=np.float32)
        if e - s < R:
            # NaN, not 0: a padded row must not count as an id
            blk = np.concatenate(
                [blk, np.full((R - (e - s), Fu), np.nan, np.float32)])
        return blk

    row_parts = []
    t_parts = []
    other_parts = []
    pending = None
    # the four spans a chunk say where ingest's time goes: host slicing
    # and conversion, the link, the device program's dispatch, or the
    # wait on the two-deep queue
    with obs.span("ingest/device", rows=n, features=Fu):
        for s in range(0, max(n, 1), R):
            e = min(s + R, n)
            with obs.span("ingest/host_prep"):
                blk = host_prep(s, e)
            with obs.span("ingest/h2d"):
                chunk_dev = jax.device_put(blk)
            # work counters, always kept (once a chunk): every streamed
            # raw chunk (padded f32) crosses the host->device link once
            obs.inc("ingest.cells", (e - s) * Fu, force=True)
            obs.inc("ingest.h2d_bytes", int(blk.nbytes), force=True)
            obs.inc("ingest.chunks", force=True)
            with obs.span("ingest/assign_dispatch"):
                res = _assign_chunk(chunk_dev, *dev_tables,
                                    out_dtype=out_jdtype,
                                    emit_transposed=emit_transposed,
                                    cat_cols=cat_cols)
            row_parts.append(res[0])
            if emit_transposed:
                t_parts.append(res[1])
            if cat_cols:
                other_parts.append(res[2])
                obs.inc("ingest.cat_cells", (e - s) * len(cat_cols),
                        force=True)
            # double buffer: keep at most two chunks in flight so host
            # prep overlaps device compute without unbounded queueing
            if pending is not None:
                with obs.span("ingest/wait"):
                    pending.block_until_ready()
            pending = res[0]
        if cat_cols:
            # one small fetch at the end (it waits for the last chunk)
            obs.inc("ingest.cat_other",
                    float(np.sum(jax.device_get(other_parts),
                                 dtype=np.int64)), force=True)
    bins = (row_parts[0] if len(row_parts) == 1
            else jnp.concatenate(row_parts, axis=0))[:n]
    bins_t = None
    if emit_transposed:
        bins_t = (t_parts[0] if len(t_parts) == 1
                  else jnp.concatenate(t_parts, axis=1))[:, :n]
    return DeviceIngestResult(bins=bins, bins_t=bins_t, n_rows=n,
                              chunk_rows=R)
