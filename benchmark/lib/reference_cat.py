"""The plain reference of a configuration with categorical columns: what
lib/reference.py is for numeric tables, with set-splits read from the
model text. numpy float64 and one plain C++ loop (route_cat.cpp); it
imports nothing of the program and takes nothing the program made but
its OUTPUT, the model as LightGBM text. Rows are routed by their RAW
values, ids and real-valued thresholds, never by bins.

A categorical node (decision-type bit 0) holds in `threshold` the index k
of its bitset, the words cat_boundaries[k] .. cat_boundaries[k + 1] of
cat_threshold, a bitset over category VALUES. A row goes left iff its
value is a non-negative integer whose bit is set; NaN, a negative value
and an id past the end of the bitset go right (LightGBM's
Tree::CategoricalDecision). Numeric nodes are lib/reference.py's.

What says the same is taken from lib/reference.py by import: `auc`,
`expected_root_rows` and the numeric decision. The follower of the
window's first trees (`follow_window`, with the noise arithmetic) is that
file's, copied: it has to read a leaf's sums back from its output, and
under a set-split the output is taken with another l2 (noted there).

Departures from LightGBM's published behaviour, noted as they are met:
- a value is truncated toward zero before the bitset is asked, as
  LightGBM's static_cast<int> does; values of 2^31 and more go right;
- LightGBM before 3.0 read NaN at a categorical node of missing type
  none as category 0; this follows the current rule (NaN goes right),
  which is also what the program's bins do (NaN and unseen ids share
  bin 0, which no left set holds).
"""
from __future__ import annotations

import concurrent.futures as cf
import ctypes
import hashlib
import os
import subprocess
import sys
import time

import numpy as np

from lib import reference
from lib.reference import auc, expected_root_rows  # noqa: F401  (re-export)

_INT_KEYS = reference._INT_KEYS + ("cat_boundaries",)
_FLOAT_KEYS = reference._FLOAT_KEYS


def parse_model(text: str) -> list[dict]:
    """Trees of a LightGBM text model, numeric and categorical splits."""
    trees = []
    for chunk in text.split("\nTree=")[1:]:
        t: dict = {}
        for line in chunk.split("\n\n")[0].splitlines()[1:]:
            k, _, v = line.partition("=")
            if k in _INT_KEYS:
                t[k] = np.array(v.split(), dtype=np.int64)
            elif k in _FLOAT_KEYS:
                t[k] = np.array(v.split(), dtype=np.float64)
            elif k == "cat_threshold":
                t[k] = np.array(v.split(), dtype=np.uint32)
            elif k in ("num_leaves", "num_cat"):
                t[k] = int(v)
            elif k == "shrinkage":
                t[k] = float(v)
        if not t.get("num_cat", 0):
            t["cat_boundaries"] = np.zeros(1, np.int64)
            t["cat_threshold"] = np.zeros(1, np.uint32)
        trees.append(t)
    return trees


def is_categorical(tree: dict) -> np.ndarray:
    """Which of a tree's nodes are set-splits (decision-type bit 0)."""
    return (tree["decision_type"] & 1) > 0


def in_bitset(tree: dict, node: int, x: np.ndarray) -> np.ndarray:
    """Whether each float64 value of x is a member of the node's set."""
    k = int(tree["threshold"][node])
    lo, hi = (int(tree["cat_boundaries"][k]),
              int(tree["cat_boundaries"][k + 1]))
    words = tree["cat_threshold"][lo:hi]
    ok = ~np.isnan(x) & (x >= 0.0) & (x < 32.0 * len(words))
    ids = np.where(ok, x, 0.0).astype(np.int64)
    bit = (words[ids >> 5] >> (ids & 31).astype(np.uint32)) & np.uint32(1)
    return ok & (bit > 0)


def _decide(tree: dict, node: int, x: np.ndarray) -> np.ndarray:
    if int(tree["decision_type"][node]) & 1:
        return in_bitset(tree, node, x)
    return reference._decide(tree, node, x)


def route(tree: dict, Xt: np.ndarray) -> np.ndarray:
    """Leaf index of every column of Xt, the table FEATURE-MAJOR
    ([features, rows], NaN = missing), in numpy: each node splits the
    rows that reached it (lib/reference.py's loop over this file's
    decision)."""
    n = Xt.shape[1]
    leaf = np.zeros(n, np.int32)
    if tree["num_leaves"] <= 1:
        return leaf
    stack = [(0, np.arange(n, dtype=np.int32))]
    while stack:
        node, idx = stack.pop()
        if node < 0:
            leaf[idx] = ~node
            continue
        x = Xt[tree["split_feature"][node]].take(idx).astype(np.float64)
        left = _decide(tree, node, x)
        stack.append((int(tree["left_child"][node]), idx[left]))
        stack.append((int(tree["right_child"][node]), idx[~left]))
    return leaf


_NATIVE: list = []


def _native():
    """route_cat.cpp built once a checkout into <checkout>/.bench_build,
    as lib/reference.py builds route.cpp; None where there is no
    compiler (numpy then does it, many times slower)."""
    if _NATIVE:
        return _NATIVE[0]
    _NATIVE.append(None)
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "route_cat.cpp")
    build = os.path.join(os.path.dirname(os.path.dirname(here)),
                         ".bench_build")
    with open(src, "rb") as f:
        tag = hashlib.sha1(f.read()).hexdigest()[:12]
    lib = os.path.join(build, f"route_cat_{tag}.so")
    try:
        if not os.path.exists(lib):
            os.makedirs(build, exist_ok=True)
            tmp = f"{lib}.{os.getpid()}"
            subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-o", tmp, src],
                           check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib)
        dll = ctypes.CDLL(lib)
        dll.route_rows_cat.restype = None
        _NATIVE[0] = dll
    except (OSError, subprocess.SubprocessError) as e:
        print(f"reference_cat: no native traversal ({e}); numpy does it",
              file=sys.stderr)
    return _NATIVE[0]


_NODE_KEYS = reference._NODE_KEYS + (("cat_boundaries", np.int32),
                                     ("cat_threshold", np.uint32))


def leaves(tree: dict, X: np.ndarray) -> np.ndarray:
    """Leaf index (int32) of every row of a row-major float32 block."""
    n = X.shape[0]
    if tree["num_leaves"] <= 1:
        return np.zeros(n, np.int32)
    dll = _native()
    if dll is None or not reference._plain(X):
        return route(tree, np.ascontiguousarray(X.T))
    if "_c" not in tree:
        tree["_c"] = [np.ascontiguousarray(tree[k], t) for k, t in _NODE_KEYS]
    out = np.empty(n, np.int32)
    dll.route_rows_cat(reference._ptr(X), ctypes.c_int64(n),
                       ctypes.c_int32(X.shape[1]),
                       *map(reference._ptr, tree["_c"]), reference._ptr(out))
    return out


def predict_raw(trees: list[dict], X: np.ndarray,
                threads: int | None = None,
                block_rows: int = 1 << 20) -> np.ndarray:
    """Sum of leaf values over the rows of X, float64, in row blocks over
    a thread pool."""
    n = X.shape[0]
    out = np.zeros(n, np.float64)

    def one(lo: int) -> None:
        xb = X[lo:lo + block_rows]
        for t in trees:
            out[lo:lo + block_rows] += t["leaf_value"][leaves(t, xb)]

    with cf.ThreadPoolExecutor(reference._threads(threads)) as ex:
        list(ex.map(one, range(0, n, block_rows)))
    return out


def under_a_set(tree: dict) -> np.ndarray:
    """For each leaf, whether the split above it is a set-split."""
    out = np.zeros(tree["num_leaves"], bool)
    sets = is_categorical(tree)
    for child in (tree["left_child"], tree["right_child"]):
        leaf = child < 0
        out[~child[leaf]] = sets[leaf]
    return out


def follow_window(trees: list[dict], before: int, follow: int,
                  X: np.ndarray, y: np.ndarray, params: dict,
                  precision: dict, threads: int | None = None,
                  block_rows: int = 1 << 20) -> dict:
    """Follow trees[before : before + follow], the first the window grew,
    over the whole training table: lib/reference.py's follower, line for
    line, over this file's traversal, but for ONE thing. The children of
    a set-split take their outputs with lambda_l2 + cat_l2 in the
    denominator (LightGBM's FindBestThresholdCategorical adds cat_l2 to
    the l2 of the split's outputs as well as of its gain), so the sums a
    leaf's output stands for are read back with that leaf's own l2.

    params states the sampling (data_sample_strategy goss with top_rate
    and other_rate, from iteration 1 / learning_rate on; otherwise every
    row counts once). precision = {"num_grad_quant_bins": q, ...}: after
    sampling and amplification gradients are rounded stochastically to
    whole multiples of max|g| / (q // 2), hessians of max h / (q - 1); a
    row's rounding is unbiased with variance f (1 - f) for its
    fractional part f.
    """
    qbins = int(precision["num_grad_quant_bins"])
    g_levels, h_levels = max(qbins // 2, 1), max(qbins - 1, 1)
    n = X.shape[0]
    if before < 1:
        raise ValueError("the first tree's outputs carry the initial score")
    lr = float(params["learning_rate"])
    lam = float(params.get("lambda_l2", 0.0))
    cat_l2 = float(params.get("cat_l2", 10.0))    # LightGBM's default
    goss = (params.get("data_sample_strategy") == "goss"
            and before >= int(1.0 / max(lr, 1e-6)))
    if goss:
        a, b = float(params["top_rate"]), float(params["other_rate"])
        k_top, k_rand = max(1, int(n * a)), int(n * b)
        n_rest = n - k_top
        amp = (1.0 - a) / max(b, 1e-12)
        p = min(k_rand, n_rest) / max(n_rest, 1)
    else:
        k_top, n_rest, amp, p = n, 0, 0.0, 0.0
    blocks = [(lo, min(lo + block_rows, n)) for lo in range(0, n, block_rows)]
    times = {"scores": -time.perf_counter()}
    score = predict_raw(trees[:before], X, threads, block_rows)
    times["scores"] += time.perf_counter()
    g = np.empty(n, np.float64)
    h = np.empty(n, np.float64)
    m = np.empty(n, np.float64)
    z2 = {"count": [], "g": [], "h": []}
    per_tree = []

    def frac_var(q):
        f = q - np.floor(q)
        return f * (1.0 - f)

    def pool(fn, jobs):
        with cf.ThreadPoolExecutor(reference._threads(threads)) as ex:
            return list(ex.map(fn, jobs))

    # |g * h| is under 1/4 for a binary log loss; its k-th largest value
    # is found from a histogram of this many cells, then within one cell
    # (np.partition crawls where a million rows share one score)
    cells = 1 << 16

    def cell_of(mb):
        return np.minimum((mb * (4.0 * cells)).astype(np.int64), cells)

    def grads(blk):
        lo, hi = blk
        pr = 1.0 / (1.0 + np.exp(-score[lo:hi]))
        g[lo:hi] = pr - y[lo:hi]
        h[lo:hi] = pr * (1.0 - pr)
        np.abs(g[lo:hi] * h[lo:hi], out=m[lo:hi])
        return np.bincount(cell_of(m[lo:hi]), minlength=cells + 1)

    def kth_largest(hist, kth):
        from_top = np.cumsum(hist[::-1])
        j = int(np.searchsorted(from_top, kth))     # cells from the top
        above = int(from_top[j - 1]) if j else 0
        inside = np.concatenate(pool(
            lambda blk: m[blk[0]:blk[1]][cell_of(m[blk[0]:blk[1]])
                                         == cells - j], blocks))
        return float(np.sort(inside)[inside.size - (kth - above)])

    for k in range(before, before + follow):
        tree = trees[k]
        L = tree["num_leaves"]
        t0 = time.perf_counter()
        hist = sum(pool(grads, blocks))
        times["grads"] = times.get("grads", 0.0) + time.perf_counter() - t0
        t0 = time.perf_counter()
        # GOSS keeps exactly k_top rows with the largest |g * h|; ties at
        # the threshold go to the lowest row indices
        thr = kth_largest(hist, k_top) if goss else -1.0

        times["threshold"] = (times.get("threshold", 0.0)
                              + time.perf_counter() - t0)
        t0 = time.perf_counter()

        def split(blk):
            lo, hi = blk
            mb = m[lo:hi]
            top = mb > thr
            gb, hb = np.abs(g[lo:hi]), h[lo:hi]
            return (int(top.sum()), int((mb == thr).sum()),
                    np.where(top, gb, 0.0).max(), np.where(top, hb, 0.0).max(),
                    np.where(top, 0.0, gb).max(), np.where(top, 0.0, hb).max())

        parts = pool(split, blocks)
        # how many of each block's ties at the threshold are still top
        need = k_top - sum(pt[0] for pt in parts)
        quota = []
        for pt in parts:
            quota.append(min(max(need, 0), pt[1]))
            need -= quota[-1]
        # the steps this tree's (amplified) gradients are rounded to. The
        # program's maxima are over the rows it drew, these over all that
        # it could draw: a hair apart at this many rows. (Ties that stay
        # top count among the rest here; they are at the threshold, far
        # from either maximum.)
        step_g = max(max(pt[2] for pt in parts),
                     amp * max(pt[4] for pt in parts)) / g_levels
        step_h = max(max(pt[3] for pt in parts),
                     amp * max(pt[5] for pt in parts)) / h_levels
        times["split"] = times.get("split", 0.0) + time.perf_counter() - t0
        t0 = time.perf_counter()

        def one(job, tree=tree, L=L):
            (lo, hi), q = job
            leaf = leaves(tree, X[lo:hi])
            gb, hb, mb = g[lo:hi], h[lo:hi], m[lo:hi]
            top = mb > thr
            if q:
                top[np.flatnonzero(mb == thr)[:q]] = True
            # rows so near the threshold that the program's float32
            # scores may put them on the other side
            band = (np.abs(mb - thr) <= 1e-5 * thr) & (mb != thr)
            t = top.astype(np.float64)
            r = 1.0 - t

            def bc(w):
                return np.bincount(leaf, weights=w, minlength=L)

            score[lo:hi] += tree["leaf_value"][leaf]
            gr, hr = gb * r, hb * r
            return np.array([
                bc(t), bc(r), bc(band.astype(np.float64)),
                bc(gb * t), bc(gr), bc(gr * gr),
                bc(hb * t), bc(hr), bc(hr * hr),
                bc(frac_var(gb / step_g) * t),
                bc(frac_var(amp * gr / step_g)),
                bc(frac_var(hb / step_h) * t),
                bc(frac_var(amp * hr / step_h))])

        (cT, cR, cU, gT, gR, gR2, hT, hR, hR2, fgT, fgR, fhT,
         fhR) = sum(pool(one, zip(blocks, quota)))
        times["leaves"] = times.get("leaves", 0.0) + time.perf_counter() - t0
        pq = p * (1.0 - p) * n_rest / max(n_rest - 1, 1)

        def drawn(s1, s2):
            """Variance of the drawn rows' sum for a leaf whose other rows
            sum to s1, their squares to s2: k_rand of the n_rest rows are
            drawn without replacement."""
            return pq * (s2 - s1 * s1 / max(n_rest, 1))

        cnt_want = cT + p * cR
        cnt_var = drawn(cR, cR) + cU * cU
        G = gT + amp * p * gR
        H = hT + amp * p * hR
        VG = amp * amp * drawn(gR, gR2) + step_g ** 2 * (fgT + p * fgR)
        VH = amp * amp * drawn(hR, hR2) + step_h ** 2 * (fhT + p * fhR)
        # the program's own sums, back from its leaf outputs and weights:
        # v = -G / (W + lam) * lr
        W = tree["leaf_weight"]
        G_prog = -tree["leaf_value"] * (W + lam
                                        + cat_l2 * under_a_set(tree)) / lr
        # only leaves whose hessian sum the draw and the rounding leave
        # known to 5%: in a leaf of few rows the split search has picked
        # the noise it liked (a leaf that looks purer than it is), and
        # the sums are off by more than chance in sound runs too
        ok = (cT + cR > 0) & (VG > 0) & (VH > 0) & (np.sqrt(VH) < 0.05 * H)
        z2["g"].append((G_prog[ok] - G[ok]) ** 2 / VG[ok])
        z2["h"].append((W[ok] - H[ok]) ** 2 / VH[ok])
        okc = ok & (cnt_var > 0)
        z2["count"].append((tree["leaf_count"][okc] - cnt_want[okc]) ** 2
                           / cnt_var[okc])
        per_tree.append({
            "tree": k, "leaves": int(ok.sum()),
            "noise_g": float(np.mean(z2["g"][-1])) if ok.any() else 0.0,
            "noise_h": float(np.mean(z2["h"][-1])) if ok.any() else 0.0,
            "noise_count": (float(np.mean(z2["count"][-1]))
                            if okc.any() else 0.0),
            "near_threshold": int(cU.sum()), "step_g": step_g,
            "step_h": step_h})

    def excess(parts):
        z = np.concatenate(parts) if parts else np.zeros(0)
        return max(float(np.mean(z)) - 1.0, 0.0) if z.size else None

    out = {"leaf_sum_noise": None, "leaf_count_noise": 0.0,
           "leaves_compared": int(sum(map(len, z2["g"]))),
           "per_tree": per_tree,
           "seconds": {k: round(v, 2) for k, v in times.items()}}
    eg, eh = excess(z2["g"]), excess(z2["h"])
    if eg is not None:
        out["leaf_sum_noise"] = max(eg, eh)
    if goss:
        out["leaf_count_noise"] = excess(z2["count"])
    return out
