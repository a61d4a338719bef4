"""One general tabular generator, driven by the `data` section of a
configuration file. A configuration adds columns and a label model as
data; this file knows no configuration by name.

Rows are made in blocks, each from its own counter-based generator
keyed by (seed, stream, block), so the result does not depend on how
many threads made it. `stream` 0 is the training table, 1 the hold-out, 2 the fixed table a
cell may find its bin boundaries on.

Column groups (`data.columns`, in order):
  {"kind": "ordinal", "names": [...], "cardinality": c, "offset": o,
   "zipf": a}   integer codes o..o+c-1 as float32; a = 0 is uniform,
                a > 0 a Zipf law over a fixed shuffled ranking
A table that needs another kind of column brings a generator file of
its own beside this one and names it in `data.generator`.

Label model (`data.label`): a fixed (label.seed) sparse additive model
with pairwise interactions over standardized columns; the label is a
Bernoulli draw from its sigmoid, so the noise floor is the model's own.
"""
from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

STREAM_TRAIN, STREAM_HOLDOUT, STREAM_BINS = 0, 1, 2
_QUANTILES = 1 << 16


class Spec:
    """The fixed part of a configuration's data: shapes and label model,
    drawn once from the seeds in the file, never from the run's seed."""

    def __init__(self, data: dict):
        self.block_rows = int(data.get("block_rows", 1 << 20))
        self.groups = []
        self.names = []
        shape_rng = np.random.default_rng(int(data.get("shape_seed", 1)))
        lo = 0
        for g in data["columns"]:
            kind = g["kind"]
            n = len(g["names"]) if "names" in g else int(g["count"])
            grp = {"kind": kind, "lo": lo, "n": n}
            if kind == "ordinal":
                card = int(g["cardinality"])
                grp.update(card=card, offset=float(g.get("offset", 0)))
                a = float(g.get("zipf", 0.0))
                if a > 0:
                    # the law as a table of 2^16 equal quantiles: a draw
                    # is one lookup
                    w = 1.0 / np.arange(1, card + 1) ** a
                    w = w[shape_rng.permutation(card)]
                    q = (np.arange(_QUANTILES) + 0.5) / _QUANTILES
                    grp["table"] = np.minimum(
                        np.searchsorted(np.cumsum(w / w.sum()), q),
                        card - 1).astype(np.int32)
            else:
                raise ValueError(f"unknown column kind {kind!r}")
            self.groups.append(grp)
            self.names += list(g.get("names",
                                     [f"{kind}{lo + i}" for i in range(n)]))
            lo += n
        self.n_features = lo
        lab = data["label"]
        lrng = np.random.default_rng(int(lab["seed"]))
        k = min(int(lab["informative"]), self.n_features)
        self.term_cols = lrng.choice(self.n_features, k, replace=False)
        self.term_coef = lrng.normal(size=k)
        self.term_fn = lrng.integers(0, 3, size=k)
        m = int(lab.get("interactions", 0))
        self.pair_cols = lrng.choice(self.term_cols, (m, 2)) if m else \
            np.zeros((0, 2), np.int64)
        self.pair_coef = lrng.normal(size=m)
        self.strength = float(lab["strength"])
        self.bias = float(lab["bias"])

    def group_of(self, col: int) -> dict:
        for g in self.groups:
            if g["lo"] <= col < g["lo"] + g["n"]:
                return g
        raise IndexError(col)

    def standardized(self, X: np.ndarray, col: int) -> np.ndarray:
        """Column `col` mapped to [-1, 1]."""
        g = self.group_of(col)
        x = X[:, col].astype(np.float64)
        if g["card"] <= 1:
            return np.zeros_like(x)
        return 2.0 * (x - g["offset"]) / (g["card"] - 1) - 1.0

    def logit(self, X: np.ndarray) -> np.ndarray:
        z = {int(c): self.standardized(X, int(c)) for c in self.term_cols}
        t = np.zeros(X.shape[0], np.float64)
        for c, coef, fn in zip(self.term_cols, self.term_coef, self.term_fn):
            v = z[int(c)]
            f = v if fn == 0 else (np.abs(v) - 0.5 if fn == 1
                                   else np.sin(np.pi * v))
            t += coef * f
        for (a, b), coef in zip(self.pair_cols, self.pair_coef):
            t += coef * z[int(a)] * z[int(b)]
        return self.bias + self.strength * t


def _fill_block(spec: Spec, X: np.ndarray, y: np.ndarray, seed: int,
                stream: int, block: int) -> None:
    rng = np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([int(seed), int(stream), int(block)])))
    n = X.shape[0]
    for g in spec.groups:
        lo, k = g["lo"], g["n"]
        for j in range(k):
            if "table" in g:
                code = g["table"][rng.integers(0, _QUANTILES, n,
                                               dtype=np.int32)]
            else:
                code = rng.integers(0, g["card"], n, dtype=np.int32)
            if g["offset"]:
                code += np.int32(g["offset"])
            X[:, lo + j] = code
    p = 1.0 / (1.0 + np.exp(-spec.logit(X)))
    y[:] = rng.random(n) < p


def generate(spec: Spec, n_rows: int, seed: int, stream: int,
             threads: int | None = None):
    """(X float32 [n_rows, F], y float32 [n_rows]) for one stream."""
    X = np.empty((n_rows, spec.n_features), np.float32)
    y = np.empty(n_rows, np.float32)
    br = spec.block_rows
    blocks = [(b, b * br, min((b + 1) * br, n_rows))
              for b in range((n_rows + br - 1) // br)]
    threads = threads or min(len(blocks), max(1, (os.cpu_count() or 2) - 1))
    with cf.ThreadPoolExecutor(threads) as ex:
        list(ex.map(lambda t: _fill_block(spec, X[t[1]:t[2]], y[t[1]:t[2]],
                                          seed, stream, t[0]), blocks))
    return X, y
