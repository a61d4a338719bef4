"""Native runtime pieces: on-demand-compiled C++ loaded via ctypes.

The image has g++ but no pybind11, so native components use the C ABI +
ctypes (the reference's analog is its C API boundary, c_api.cpp). Shared
objects are compiled once per source hash into a cache dir; every native
entry point has a pure-Python fallback so a missing toolchain degrades
gracefully.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

from ..utils import log

_CACHED: dict = {}


def _source_path(name: str) -> str:
    return os.path.join(os.path.dirname(__file__), name)


def load_native(name: str = "text_parser.cpp",
                extra_flags: tuple = ()) -> Optional[ctypes.CDLL]:
    """Compile (cached) + dlopen a native source; None if unavailable."""
    key = (name, extra_flags)
    if key in _CACHED:
        return _CACHED[key]
    lib = None
    try:
        src = _source_path(name)
        with open(src, "rb") as f:
            payload = f.read() + repr(extra_flags).encode()
        digest = hashlib.sha256(payload).hexdigest()[:16]
        cache_dir = os.path.join(tempfile.gettempdir(),
                                 "lightgbm_tpu_native")
        os.makedirs(cache_dir, exist_ok=True)
        so = os.path.join(cache_dir,
                          f"{os.path.splitext(name)[0]}_{digest}.so")
        if not os.path.exists(so):
            tmp = so + f".build{os.getpid()}"
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 *extra_flags, "-o", tmp, src],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, so)
        lib = ctypes.CDLL(so)
    except (OSError, subprocess.SubprocessError) as e:
        # no g++ / sandboxed tmp / bad toolchain: the Python fallback
        # is this module's contract, but it is many times slower, so
        # the failure is said once (``_CACHED`` keeps the None) with
        # what the compiler wrote
        stderr = getattr(e, "stderr", None) or b""
        log.warning(
            f"native {' '.join((name, *extra_flags))} unavailable "
            f"({type(e).__name__}: {e}); using the Python fallback. "
            f"{stderr.decode(errors='replace')[-2000:]}".rstrip())
    _CACHED[key] = lib
    return lib


def c_api() -> Optional[ctypes.CDLL]:
    """The minimal LGBMTPU_* C ABI (model load + predict surface).

    Reference analog: src/c_api.cpp's LGBM_* boundary (SURVEY.md L7,
    UNVERIFIED). Only the predict/model functions exist — training is a
    jitted XLA program and gains nothing from a C entry point. See
    native/c_api.cpp's header comment and docs/design.md for the scope
    decision.
    """
    lib = load_native("c_api.cpp", extra_flags=("-fopenmp",))
    if lib is None:
        # -fopenmp may be missing from a stripped toolchain; the ABI is
        # still correct single-threaded
        lib = load_native("c_api.cpp")
    if lib is None:
        return None
    if not getattr(lib, "_sigs_set", False):
        c = ctypes
        H = c.c_void_p
        lib.LGBMTPU_GetLastError.restype = c.c_char_p
        lib.LGBMTPU_GetLastError.argtypes = []
        lib.LGBMTPU_BoosterLoadModelFromString.restype = c.c_int
        lib.LGBMTPU_BoosterLoadModelFromString.argtypes = [
            c.c_char_p, c.POINTER(c.c_int), c.POINTER(H)]
        lib.LGBMTPU_BoosterCreateFromModelfile.restype = c.c_int
        lib.LGBMTPU_BoosterCreateFromModelfile.argtypes = [
            c.c_char_p, c.POINTER(c.c_int), c.POINTER(H)]
        lib.LGBMTPU_BoosterFree.restype = c.c_int
        lib.LGBMTPU_BoosterFree.argtypes = [H]
        for fn in ("GetNumClasses", "GetNumFeature",
                   "GetCurrentIteration", "GetNumTreePerIteration"):
            f = getattr(lib, f"LGBMTPU_Booster{fn}")
            f.restype = c.c_int
            f.argtypes = [H, c.POINTER(c.c_int)]
        lib.LGBMTPU_BoosterSaveModel.restype = c.c_int
        lib.LGBMTPU_BoosterSaveModel.argtypes = [H, c.c_char_p]
        lib.LGBMTPU_BoosterGetModelSize.restype = c.c_int
        lib.LGBMTPU_BoosterGetModelSize.argtypes = [
            H, c.POINTER(c.c_int64)]
        lib.LGBMTPU_BoosterGetModelString.restype = c.c_int
        lib.LGBMTPU_BoosterGetModelString.argtypes = [
            H, c.c_int64, c.c_char_p]
        lib.LGBMTPU_BoosterPredictForMat.restype = c.c_int
        lib.LGBMTPU_BoosterPredictForMat.argtypes = [
            H, c.POINTER(c.c_double), c.c_int32, c.c_int32, c.c_int,
            c.c_int, c.c_int, c.c_int, c.POINTER(c.c_double),
            c.POINTER(c.c_int64)]
        lib._sigs_set = True
    return lib


class CBooster:
    """Thin Python wrapper over the LGBMTPU_* ABI — exists so tests can
    drive the C boundary exactly the way an external C caller would,
    and as living documentation of the calling convention."""

    PREDICT_NORMAL, PREDICT_RAW, PREDICT_LEAF = 0, 1, 2

    def __init__(self, model_str: str = None, model_file: str = None):
        import numpy as np
        self._np = np
        self._lib = c_api()
        if self._lib is None:
            raise RuntimeError("native c_api unavailable (no g++?)")
        h = ctypes.c_void_p()
        it = ctypes.c_int()
        if model_file is not None:
            rc = self._lib.LGBMTPU_BoosterCreateFromModelfile(
                model_file.encode(), ctypes.byref(it), ctypes.byref(h))
        else:
            rc = self._lib.LGBMTPU_BoosterLoadModelFromString(
                model_str.encode(), ctypes.byref(it), ctypes.byref(h))
        if rc != 0:
            raise ValueError(self.last_error())
        self._h = h
        self.num_iterations = it.value

    def last_error(self) -> str:
        return self._lib.LGBMTPU_GetLastError().decode()

    def __del__(self):
        if getattr(self, "_h", None) is not None:
            self._lib.LGBMTPU_BoosterFree(self._h)
            self._h = None

    def _get_int(self, fn: str) -> int:
        out = ctypes.c_int()
        rc = getattr(self._lib, f"LGBMTPU_Booster{fn}")(
            self._h, ctypes.byref(out))
        if rc != 0:
            raise ValueError(self.last_error())
        return out.value

    @property
    def num_classes(self) -> int:
        return self._get_int("GetNumClasses")

    @property
    def num_feature(self) -> int:
        return self._get_int("GetNumFeature")

    def save_model(self, path: str) -> None:
        if self._lib.LGBMTPU_BoosterSaveModel(self._h,
                                              path.encode()) != 0:
            raise ValueError(self.last_error())

    def model_to_string(self) -> str:
        size = ctypes.c_int64()
        if self._lib.LGBMTPU_BoosterGetModelSize(
                self._h, ctypes.byref(size)) != 0:
            raise ValueError(self.last_error())
        buf = ctypes.create_string_buffer(size.value + 1)
        if self._lib.LGBMTPU_BoosterGetModelString(
                self._h, size.value + 1, buf) != 0:
            raise ValueError(self.last_error())
        return buf.value.decode()

    def predict(self, X, predict_type: int = 0, start_iteration: int = 0,
                num_iteration: int = -1):
        np = self._np
        X = np.ascontiguousarray(np.asarray(X, dtype=np.float64))
        n, ncol = X.shape
        k = self.num_classes
        nt = (self.num_iterations - start_iteration
              if num_iteration <= 0 else
              min(num_iteration, self.num_iterations - start_iteration))
        nt = max(nt, 0)
        if predict_type == self.PREDICT_LEAF:
            width = nt * max(1, self._trees_per_iter)
            if width == 0:
                return np.zeros((n, 0), dtype=np.float64)
        else:
            width = k
        out = np.zeros((n, width), dtype=np.float64)
        out_len = ctypes.c_int64()
        rc = self._lib.LGBMTPU_BoosterPredictForMat(
            self._h, X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n, ncol, 1, predict_type, start_iteration, num_iteration,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            ctypes.byref(out_len))
        if rc != 0:
            raise ValueError(self.last_error())
        assert out_len.value == n * width
        if width == 1:
            return out[:, 0]
        return out

    @property
    def _trees_per_iter(self) -> int:
        # num_tree_per_iteration == num_class for multiclass
        return self._get_int("GetNumTreePerIteration")


def binning() -> Optional[ctypes.CDLL]:
    """Native binning hot paths (greedy bound search + bin apply);
    bit-exact ports of io/binning.py's Python implementations, which
    remain the fallback."""
    lib = load_native("binning.cpp")
    if lib is None:
        return None
    if not getattr(lib, "_sigs_set", False):
        c = ctypes
        lib.greedy_find_bounds.restype = c.c_int64
        lib.greedy_find_bounds.argtypes = [
            c.POINTER(c.c_double), c.POINTER(c.c_int64), c.c_int64,
            c.c_int64, c.c_int64, c.c_int64, c.POINTER(c.c_double)]
        lib.bin_numeric_column.restype = None
        lib.bin_numeric_column.argtypes = [
            c.c_void_p, c.c_int, c.c_int64, c.c_int64,
            c.POINTER(c.c_double), c.c_int64, c.c_int, c.c_int64,
            c.c_int64, c.c_void_p, c.c_int, c.c_int64]
        lib.bin_matrix.restype = None
        lib.bin_matrix.argtypes = [
            c.c_void_p, c.c_int, c.c_int64, c.c_int64,
            c.POINTER(c.c_int64), c.c_int64, c.POINTER(c.c_double),
            c.POINTER(c.c_int64), c.POINTER(c.c_int),
            c.POINTER(c.c_int64), c.POINTER(c.c_int64),
            c.POINTER(c.c_int), c.c_void_p, c.c_int]
        lib._sigs_set = True
    return lib


def text_parser() -> Optional[ctypes.CDLL]:
    lib = load_native("text_parser.cpp")
    if lib is None:
        return None
    if not getattr(lib, "_sigs_set", False):
        c = ctypes
        lib.count_lines.restype = c.c_long
        lib.count_lines.argtypes = [c.c_char_p]
        lib.count_fields.restype = c.c_int
        lib.count_fields.argtypes = [c.c_char_p, c.c_char]
        lib.parse_dense.restype = c.c_long
        lib.parse_dense.argtypes = [
            c.c_char_p, c.c_char, c.c_int,
            c.POINTER(c.c_double), c.c_long, c.c_int]
        lib.parse_libsvm.restype = c.c_long
        lib.parse_libsvm.argtypes = [
            c.c_char_p, c.c_int, c.POINTER(c.c_int), c.POINTER(c.c_int),
            c.POINTER(c.c_double), c.POINTER(c.c_double), c.c_long,
            c.c_long]
        lib._sigs_set = True
    return lib
