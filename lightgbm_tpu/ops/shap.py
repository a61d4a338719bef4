"""TreeSHAP feature contributions (predict_contrib).

Reference: ``Tree::PredictContrib`` / TreeSHAP recursion (src/io/tree.cpp
`TreeSHAP` + include/LightGBM/tree.h PathElement, UNVERIFIED — empty
mount, see SURVEY.md banner). Implements the Lundberg & Lee
path-dependent TreeSHAP: exact Shapley values under the tree's own
cover distribution; last output column is the expected value (bias).

Two implementations:

- :func:`forest_shap_batch` (default path) — rows-vectorized and
  device-resident: per-node routing decisions are evaluated once on
  the host (exact f64 threshold compares, NaN defaults, categorical
  bitsets) and bit-packed; everything else — per-leaf path matching,
  the SHAP ``extend`` recurrences, the per-feature unwound sums — runs
  as one jitted ``lax.scan`` over the stacked per-tree path tables
  (matmuls + elementwise, no per-row gathers). The enabling identity:
  extending a decision path with ``(zero=1, one=1)`` dummy elements
  leaves every unwound sum invariant (verified numerically), so all
  leaf paths pad to ONE uniform length and the recurrences need no
  masking. The reference's ``PredictContrib`` parallelizes the same
  per-row recursion over OpenMP threads; this is its MXU/VPU shape.
- :func:`tree_shap_batch` — the original per-row recursion, kept as
  the slow exact oracle (f64) the vectorized path is tested against.

Measured (100k rows x 100 nl=127 trees, v5e + 1-core host): recursive
~17 h extrapolated (122.7 s for 200 rows) -> vectorized 28.8 s
(~2000x), of which ~10 s is the host routing-bit pass (vectorized
numpy; scales with host cores elsewhere). Precision: CPU backend runs
f64 (matches the oracle to ~1e-13); TPU runs f32 with the scatter
matmul at HIGHEST precision — measured ~3e-5 vs the f64 oracle and
~5e-6 local-accuracy error at the 100-tree flagship shape (use
force_f64 on a CPU backend for exact values).
"""
from __future__ import annotations

import numpy as np


class _Path:
    """Decision path state: parallel arrays of (feature, zero, one, w)."""

    __slots__ = ("feature", "zero_fraction", "one_fraction", "pweight")

    def __init__(self, depth_cap: int):
        self.feature = np.zeros(depth_cap, dtype=np.int64)
        self.zero_fraction = np.zeros(depth_cap, dtype=np.float64)
        self.one_fraction = np.zeros(depth_cap, dtype=np.float64)
        self.pweight = np.zeros(depth_cap, dtype=np.float64)

    def copy(self, length: int) -> "_Path":
        p = _Path(len(self.feature))
        p.feature[:length] = self.feature[:length]
        p.zero_fraction[:length] = self.zero_fraction[:length]
        p.one_fraction[:length] = self.one_fraction[:length]
        p.pweight[:length] = self.pweight[:length]
        return p


def _extend(p: _Path, length: int, zero: float, one: float,
            feat: int) -> int:
    p.feature[length] = feat
    p.zero_fraction[length] = zero
    p.one_fraction[length] = one
    p.pweight[length] = 1.0 if length == 0 else 0.0
    for i in range(length - 1, -1, -1):
        p.pweight[i + 1] += one * p.pweight[i] * (i + 1) / (length + 1)
        p.pweight[i] = zero * p.pweight[i] * (length - i) / (length + 1)
    return length + 1


def _unwind(p: _Path, length: int, idx: int) -> int:
    length -= 1
    one = p.one_fraction[idx]
    zero = p.zero_fraction[idx]
    n = p.pweight[length]
    for i in range(length - 1, -1, -1):
        if one != 0.0:
            t = p.pweight[i]
            p.pweight[i] = n * (length + 1) / ((i + 1) * one)
            n = t - p.pweight[i] * zero * (length - i) / (length + 1)
        else:
            p.pweight[i] = p.pweight[i] * (length + 1) / (
                zero * (length - i))
    for i in range(idx, length):
        p.feature[i] = p.feature[i + 1]
        p.zero_fraction[i] = p.zero_fraction[i + 1]
        p.one_fraction[i] = p.one_fraction[i + 1]
    return length


def _unwound_sum(p: _Path, length: int, idx: int) -> float:
    one = p.one_fraction[idx]
    zero = p.zero_fraction[idx]
    total = 0.0
    n = p.pweight[length - 1]
    for i in range(length - 2, -1, -1):
        if one != 0.0:
            t = n * length / ((i + 1) * one)
            total += t
            n = p.pweight[i] - t * zero * (length - 1 - i) / length
        else:
            total += p.pweight[i] * length / (zero * (length - 1 - i))
    return total


def _node_cover(tree, node: int) -> float:
    if node < 0:
        return float(tree.leaf_count[-node - 1])
    return float(tree.internal_count[node])


def _route_left(tree, node: int, v: np.ndarray) -> np.ndarray:
    """Numerical toward-left routing for a batch of values at one node
    — the SAME ``node_missing_type`` semantics as
    ``Tree._leaf_index_raw`` (mt=none converts NaN to 0.0; mt=zero
    routes |x|<=1e-35 and NaN by default direction; mt=nan routes NaN
    by default direction), so SHAP hot paths agree with prediction."""
    thr = tree.threshold_real[node]
    dl = bool(tree.default_left[node])
    miss = np.isnan(v)
    nmt = getattr(tree, "node_missing_type", None)
    if nmt is None:
        return np.where(miss, dl, v <= thr)
    mt = int(nmt[node])
    if mt == 2:
        return np.where(miss, dl, v <= thr)
    v0 = np.where(miss, 0.0, v)
    if mt == 1:
        return np.where(miss | (np.abs(v0) <= 1e-35), dl, v0 <= thr)
    return v0 <= thr


def _tree_shap_row(tree, x: np.ndarray, phi: np.ndarray) -> None:
    max_depth = int(tree.leaf_depths().max()) + 2 if tree.num_leaves > 1 \
        else 1

    def recurse(node: int, p: _Path, length: int, zero: float, one: float,
                feat: int) -> None:
        length = _extend(p, length, zero, one, feat)
        if node < 0:  # leaf
            leaf_val = float(tree.leaf_value[-node - 1])
            for i in range(1, length):
                w = _unwound_sum(p, length, i)
                phi[p.feature[i]] += w * (p.one_fraction[i]
                                          - p.zero_fraction[i]) * leaf_val
            return
        f = int(tree.split_feature[node])
        v = x[f]
        thr = tree.threshold_real[node]
        if tree.is_categorical is not None and tree.is_categorical[node]:
            go_left = bool(tree._cat_go_left(np.array([thr]),
                                             np.array([v]))[0])
        else:
            go_left = bool(_route_left(tree, node, np.array([v]))[0])
        hot = int(tree.left_child[node] if go_left
                  else tree.right_child[node])
        cold = int(tree.right_child[node] if go_left
                   else tree.left_child[node])
        cover = _node_cover(tree, node)
        hot_r = _node_cover(tree, hot) / cover if cover > 0 else 0.0
        cold_r = _node_cover(tree, cold) / cover if cover > 0 else 0.0
        iz, io = 1.0, 1.0
        k = -1
        for i in range(1, length):
            if p.feature[i] == f:
                k = i
                break
        if k >= 0:
            iz = p.zero_fraction[k]
            io = p.one_fraction[k]
            length = _unwind(p, length, k)
        recurse(hot, p.copy(length), length, iz * hot_r, io, f)
        recurse(cold, p.copy(length), length, iz * cold_r, 0.0, f)

    if tree.num_leaves <= 1:
        return
    recurse(0, _Path(max_depth + 2), 0, 1.0, 1.0, -1)


def tree_shap_batch(tree, X: np.ndarray, n_feat: int) -> np.ndarray:
    """SHAP contributions for one tree over a batch.

    Returns ``[n, n_feat + 1]``; the last column is the tree's expected
    value (bias term).
    """
    n = X.shape[0]
    out = np.zeros((n, n_feat + 1), dtype=np.float64)
    if tree.num_leaves <= 1:
        out[:, -1] = tree.leaf_value[0] if len(tree.leaf_value) else 0.0
        return out
    total = float(tree.leaf_count.sum())
    expected = float(np.sum(tree.leaf_value[:tree.num_leaves]
                            * tree.leaf_count[:tree.num_leaves]) / total) \
        if total > 0 else 0.0
    for r in range(n):
        phi = np.zeros(n_feat + 1, dtype=np.float64)
        _tree_shap_row(tree, X[r], phi)
        out[r, :n_feat] = phi[:n_feat]
        out[r, -1] = expected
    return out


# ---------------------------------------------------------------------------
# rows-vectorized forest TreeSHAP (round 4)
# ---------------------------------------------------------------------------
def _walk_paths(tree):
    """DFS all root->leaf paths. Returns a list over leaves of
    ``(leaf_idx, entries)`` where entries = [(node, toward_left,
    feature, cover_ratio), ...] along the path."""
    out = []
    stack = [(0, [])]
    while stack:
        node, path = stack.pop()
        if node < 0:
            out.append((-node - 1, path))
            continue
        cover = _node_cover(tree, node)
        for child, toward_left in ((int(tree.left_child[node]), True),
                                   (int(tree.right_child[node]), False)):
            r = (_node_cover(tree, child) / cover) if cover > 0 else 0.0
            stack.append((child, path + [(node, toward_left,
                                          int(tree.split_feature[node]),
                                          r)]))
    return out


def _path_tables(tree, L, D, U, n_feat, paths=None):
    """Host prep: padded per-tree path tables for the device scan.

    Returns dict of arrays — entry level: node_id/dir/active [L, D],
    slot membership M [L, D, U]; slot level: z/slot_feat [L, U];
    leaf values [L]; expected value scalar. Pad slots carry the
    (z=1, o=1) dummy identity, so they contribute exactly zero.
    """
    node_id = np.zeros((L, D), np.int32)
    dirs = np.zeros((L, D), np.float32)
    e_act = np.zeros((L, D), np.float32)
    M = np.zeros((L, D, U), np.float32)
    z = np.ones((L, U), np.float64)
    s_act = np.zeros((L, U), bool)
    s_feat = np.full((L, U), n_feat, np.int32)   # pad -> bias column
    vleaf = np.zeros(L, np.float64)
    if tree.num_leaves > 1:
        if paths is None:
            paths = _walk_paths(tree)
        for leaf, entries in paths:
            slots = {}
            for e, (nd, tl, f, r) in enumerate(entries):
                node_id[leaf, e] = nd
                dirs[leaf, e] = 1.0 if tl else 0.0
                e_act[leaf, e] = 1.0
                u = slots.setdefault(f, len(slots))
                M[leaf, e, u] = 1.0
                z[leaf, u] = z[leaf, u] * r if s_act[leaf, u] else r
                s_act[leaf, u] = True
                s_feat[leaf, u] = f
            vleaf[leaf] = float(tree.leaf_value[leaf])
    total = float(tree.leaf_count[:tree.num_leaves].sum())
    expected = (float(np.sum(tree.leaf_value[:tree.num_leaves]
                             * tree.leaf_count[:tree.num_leaves]) / total)
                if total > 0 else
                (float(tree.leaf_value[0]) if len(tree.leaf_value)
                 else 0.0))
    return dict(node_id=node_id, dirs=dirs, e_act=e_act, M=M,
                z=z, s_act=s_act.astype(np.float32), s_feat=s_feat,
                vleaf=vleaf, expected=np.float64(expected))


def _host_cond_bits(tree, X, NN):
    """Per-node toward-left routing of every row, bit-packed
    ``[n, ceil(NN/8)]``. Exact f64 compares + the same NaN/categorical
    semantics as the recursive implementation — all nodes of a tree in
    one vectorized pass (the per-node loop was the 100-tree
    bottleneck, 25 of 33 s at 100k rows)."""
    n = X.shape[0]
    nn = tree.num_nodes
    nb = max((NN + 7) // 8, 1)
    if nn == 0:
        return np.zeros((n, nb), np.uint8)
    sf = np.asarray(tree.split_feature[:nn], np.int64)
    thr = np.asarray(tree.threshold_real[:nn], np.float64)
    dl = np.asarray(tree.default_left[:nn], bool)
    V = X[:, sf]                                       # [n, nn]
    miss = np.isnan(V)
    nmt = getattr(tree, "node_missing_type", None)
    if nmt is None:
        cl = np.where(miss, dl[None, :], V <= thr[None, :])
    else:
        # node_missing_type semantics, vectorized (see _route_left)
        mt = np.asarray(nmt[:nn])[None, :]
        V0 = np.where(miss, 0.0, V)
        zeroish = miss | (np.abs(V0) <= 1e-35)
        cl = np.where(
            mt == 2, np.where(miss, dl[None, :], V <= thr[None, :]),
            np.where(mt == 1, np.where(zeroish, dl[None, :],
                                       V0 <= thr[None, :]),
                     V0 <= thr[None, :]))
    if tree.is_categorical is not None:
        for nd in np.flatnonzero(tree.is_categorical[:nn]):
            cl[:, nd] = tree._cat_go_left(
                np.full(n, tree.threshold_real[nd]), X[:, sf[nd]])
    if nn < nb * 8:
        cl = np.concatenate(
            [cl, np.zeros((n, nb * 8 - nn), bool)], axis=1)
    return np.packbits(cl, axis=1, bitorder="little")


import functools as _functools


def _dims_from(trees, all_paths):
    L = max((t.num_leaves for t in trees), default=1)
    D = max((int(t.leaf_depths().max()) if t.num_leaves > 1 else 0
             for t in trees), default=0)
    NN = max((t.num_nodes for t in trees), default=0)
    U = max((len({f for _, _, f, _ in es})
             for paths in all_paths for _, es in paths), default=0)
    return L, D, U, NN


def shap_path_dims(trees):
    """Actual table dimensions ``(L, D, U, NN)`` for a tree list, plus
    the DFS paths (the U computation needs the full root->leaf walk, so
    callers reuse it for :func:`build_shap_tables` instead of walking
    twice)."""
    all_paths = [_walk_paths(t) if t.num_leaves > 1 else []
                 for t in trees]
    return _dims_from(trees, all_paths), all_paths


def _inert_tables(L, D, U, n_feat, K):
    """Pad-tree tables: e_act=0 makes every entry match, z=1/s_act=0
    makes every slot the (zero=1, one=1) dummy, so contrib = total *
    (o - z) == 0 exactly, and cls=0 zeroes the class scatter on top —
    a pad tree contributes nothing in any dtype."""
    return dict(node_id=np.zeros((L, D), np.int32),
                dirs=np.zeros((L, D), np.float32),
                e_act=np.zeros((L, D), np.float32),
                M=np.zeros((L, D, U), np.float32),
                z=np.ones((L, U), np.float64),
                s_act=np.zeros((L, U), np.float32),
                s_feat=np.full((L, U), n_feat, np.int32),
                vleaf=np.zeros(L, np.float64),
                expected=np.float64(0.0),
                cls=np.zeros(K, np.float32))


def build_shap_tables(trees, n_feat, K, dims=None, pad_trees=0,
                      paths=None):
    """Host prep for the whole forest, hoisted out of
    :func:`forest_shap_batch` so callers (the engine's device-resident
    SHAP cache, ``HostModel``'s per-slice cache) can build once and
    reuse across calls.

    Returns ``(stacked, (L, D, U, NN))`` where ``stacked`` maps table
    name -> ``[T + pad_trees, ...]`` numpy array, or ``None`` when
    there is nothing to scan (empty / all-stump forest — callers take
    the bias-only path). ``dims`` caps are lower bounds: actual tree
    dims are maxed in, so bucketed callers get stable shapes without
    ever truncating a tree. ``pad_trees`` appends inert pad trees
    (see :func:`_inert_tables`) so the stacked tree axis can be padded
    to a pow2 / mesh-divisible length."""
    if not trees or all(t.num_leaves <= 1 for t in trees):
        return None
    if paths is None:
        actual, paths = shap_path_dims(trees)
    else:
        actual = _dims_from(trees, paths)
    if dims is None:
        L, D, U, NN = actual
    else:
        L, D, U, NN = (max(a, b) for a, b in zip(actual, dims))
    tables = []
    for ti, (t, tree_paths) in enumerate(zip(trees, paths)):
        tab = _path_tables(t, L, D, U, n_feat, paths=tree_paths)
        cls = np.zeros(K, np.float32)
        cls[ti % K] = 1.0
        tab["cls"] = cls
        tables.append(tab)
    if pad_trees:
        tables.extend([_inert_tables(L, D, U, n_feat, K)] *
                      int(pad_trees))
    stacked = {k: np.stack([tab[k] for tab in tables])
               for k in tables[0]}
    return stacked, (L, D, U, NN)


def stump_only_contrib(trees, n, n_feat, K):
    """Bias-only output for forests with no splits anywhere — nothing
    to scan, every row gets each stump's constant in the bias column."""
    out = np.zeros((n, K, n_feat + 1), np.float64)
    for i, t in enumerate(trees):
        out[:, i % K, -1] += (float(t.leaf_value[0])
                              if len(t.leaf_value) else 0.0)
    return out


def _scan_body(D, U, NN, n_feat, K, dtype):
    """The per-chunk forest scan, unjitted — shared by the
    single-device kernel (:func:`_scan_kernel`) and the tree-sharded
    wrapper (:func:`sharded_scan_kernel`), which runs it per shard and
    psums the per-tree phi sums (order-free per feature)."""
    import jax
    import jax.numpy as jnp

    def one_tree(phi, t):
        cb = t["cond"]                                  # [n, nb] uint8
        n = cb.shape[0]
        idx = jnp.arange(NN, dtype=jnp.int32)
        cond = ((cb[:, idx >> 3] >> (idx & 7)) & 1).astype(dtype)
        # path-entry match: did this row go the path's way at each
        # entry's node? one-hot matmul (0/1 exact at any precision)
        oh_node = (t["node_id"].reshape(-1)[None, :]
                   == idx[:, None]).astype(dtype)       # [NN, L*D]
        pick = jax.lax.dot_general(
            cond, oh_node, (((1,), (0,)), ((), ())),
            preferred_element_type=dtype)               # [n, L*D]
        L = t["node_id"].shape[0]
        pick = pick.reshape(n, L, D)
        dirs = t["dirs"][None]
        match = jnp.where(t["e_act"][None] > 0,
                          jnp.where(dirs > 0, pick, 1.0 - pick), 1.0)
        # o[slot] = AND over the slot's entries (miss count == 0)
        miss = jnp.einsum("nld,ldu->nlu", 1.0 - match, t["M"],
                          preferred_element_type=dtype)
        o = jnp.where(t["s_act"][None] > 0, (miss < 0.5).astype(dtype),
                      jnp.asarray(1.0, dtype))          # [n, L, U]
        z = t["z"].astype(dtype)[None]                  # [1, L, U]
        # SHAP extend: uniform length (pads are (1,1) dummies — an
        # exact invariance of the unwound sums), dummy root first.
        # The inner position loop is one shifted-add per path element
        # on the whole [n, L, U+2] coefficient array.
        Lf = U + 1
        pw = jnp.zeros((n, L, U + 2), dtype).at[:, :, 0].set(1.0)
        pos = jnp.arange(U + 2, dtype=dtype)
        for j in range(U):
            length = j + 1
            wz = jnp.clip((length - pos) / (length + 1.0), 0.0, None)
            wo = pos / (length + 1.0)
            shifted = jnp.concatenate(
                [jnp.zeros((n, L, 1), dtype), pw[:, :, :-1]], axis=2)
            pw = (z[:, :, j:j + 1] * pw * wz
                  + o[:, :, j:j + 1] * shifted * wo)
        # unwound sums for ALL slots at once: the backward recurrence
        # is sequential in path position but independent across slots
        zs, os_ = z, o                                  # [*, L, U]
        hot = os_ > 0
        total = jnp.zeros((n, L, U), dtype)
        nrun = jnp.broadcast_to(pw[:, :, Lf - 1:Lf], (n, L, U))
        for i in range(Lf - 2, -1, -1):
            pwi = pw[:, :, i:i + 1]
            t1 = nrun * Lf / ((i + 1.0) * jnp.maximum(os_, 1e-30))
            t0 = pwi * Lf / (jnp.maximum(zs, 1e-30) * (Lf - 1.0 - i))
            total = total + jnp.where(hot, t1, t0)
            nrun = jnp.where(hot, pwi - t1 * zs * ((Lf - 1.0 - i) / Lf),
                             nrun)
        contrib = total * (os_ - zs)                    # [n, L, U]
        contrib = contrib * t["vleaf"].astype(dtype)[None, :, None]
        oh_feat = (t["s_feat"].reshape(-1)[:, None]
                   == jnp.arange(n_feat + 1)[None, :]).astype(dtype)
        # HIGHEST precision: contrib entries are large with cancelling
        # signs while their per-feature sums are small — the TPU's
        # default bf16 operand rounding here cost 0.6 ABSOLUTE error
        # (measured); with exact f32 products the sum is exact-f32
        phi_t = jax.lax.dot_general(
            contrib.reshape(n, L * U), oh_feat,
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=dtype)               # [n, n_feat+1]
        phi_t = phi_t.at[:, n_feat].add(t["expected"].astype(dtype))
        phi = phi + t["cls"].astype(dtype)[None, :, None] \
            * phi_t[:, None, :]
        return phi, 0.0

    def run(stacked):
        n = stacked["cond"].shape[1]
        phi0 = jnp.zeros((n, K, n_feat + 1), dtype)
        phi, _ = jax.lax.scan(one_tree, phi0, stacked)
        return phi

    return run


@_functools.lru_cache(maxsize=32)
def _scan_kernel(D, U, NN, n_feat, K, dtype):
    """Jitted single-device forest scan (shapes static; cached so
    repeated pred_contrib calls reuse the compiled executable)."""
    import jax
    return jax.jit(_scan_body(D, U, NN, n_feat, K, dtype))


# (mesh, shape signature) -> jitted sharded scan; same lifetime pattern
# as ops/predict.py's _SHARDED_TRAVERSE (meshes are few and long-lived)
_SHARDED_SCAN: dict = {}


def sharded_scan_kernel(mesh, D, U, NN, n_feat, K, dtype):
    """Tree-sharded forest scan over ``mesh``'s tree axis.

    Each device scans only its shard of the stacked ``[T, ...]`` path
    tables (and the routing bits, sharded the same way), then one
    ``psum`` over the tree axis combines the per-shard phi sums —
    per-tree contributions are order-free per feature, so the reduce is
    exact in f64 and only reassociates an already-documented-tolerance
    sum in f32. Output is replicated (like ``forest_predict_sharded``).
    """
    key = (mesh, D, U, NN, n_feat, K, dtype)
    fn = _SHARDED_SCAN.get(key)
    if fn is None:
        import jax
        from jax.sharding import PartitionSpec
        from ..parallel import mesh as mesh_lib
        from ..serve.shard import TREE_AXIS

        body = _scan_body(D, U, NN, n_feat, K, dtype)

        def local(stacked):
            return jax.lax.psum(body(stacked), TREE_AXIS)

        def run(stacked):
            specs = {k: PartitionSpec(TREE_AXIS) for k in stacked}
            # check_vma=False, as at every other shard_map site of
            # the package: the scan's carry starts as zeros (the same
            # on every shard) and leaves the body varying over
            # TREE_AXIS, which the varying-manual-axes check refuses;
            # the psum in ``local`` is what makes the output
            # replicated, and the check cannot see through the scan
            return mesh_lib.shard_map(
                local, mesh=mesh, in_specs=(specs,),
                out_specs=PartitionSpec(), check_vma=False)(stacked)

        fn = jax.jit(run)
        _SHARDED_SCAN[key] = fn
    return fn


def forest_shap_batch(trees, X, n_feat, K=1, row_chunk=131072,
                      force_f64=None, tables=None):
    """Vectorized TreeSHAP over a whole forest: ``[n, K, n_feat+1]``.

    ``force_f64``: run the scan in float64. Defaults to True on CPU
    backends; on a TPU host setting it True routes the scan to the
    host CPU device (slower, exact) — the escape hatch for exact-f64
    parity with stock LightGBM's double-precision TreeSHAP.

    ``tables``: a prebuilt :func:`build_shap_tables` result for these
    exact trees — callers that hold a table cache (``HostModel``)
    skip the per-call path walk entirely.
    """
    import jax

    X = np.ascontiguousarray(np.asarray(X, np.float64))
    n = X.shape[0]
    if tables is None:
        tables = build_shap_tables(trees, n_feat, K)
    if tables is None:
        return stump_only_contrib(trees, n, n_feat, K)
    stacked, (L, D, U, NN) = tables

    if force_f64 is None:
        force_f64 = jax.default_backend() == "cpu"
    import contextlib
    ctx = contextlib.ExitStack()
    if force_f64:
        ctx.enter_context(jax.enable_x64(True))
        if jax.default_backend() != "cpu":
            ctx.enter_context(
                jax.default_device(jax.devices("cpu")[0]))
    out = np.zeros((n, K, n_feat + 1), np.float64)
    with ctx:
        import jax.numpy as jnp
        dtype = jnp.float64 if force_f64 else jnp.float32
        run = _scan_kernel(D, U, NN, n_feat, K,
                           np.dtype(dtype).name)
        dev = {k: jnp.asarray(v) for k, v in stacked.items()
               if k != "cond"}
        for lo in range(0, n, row_chunk):
            hi = min(lo + row_chunk, n)
            conds = np.stack([_host_cond_bits(t, X[lo:hi], NN)
                              for t in trees])
            dev["cond"] = jnp.asarray(conds)
            out[lo:hi] = np.asarray(run(dev), np.float64)
    return out
