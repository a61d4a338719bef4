"""The generator of a click-log table: integer count columns with a heavy
right tail and missing values, and categorical columns of dictionary-
encoded ids with a power-law frequency. Driven by the `data` section of a
configuration file (named there as `data.generator`); this file knows no
configuration by name. The interface is lib/datagen.py's (`Spec`,
`generate`, `STREAM_*`): rows are made in blocks, each from its own
counter-based generator keyed by (seed, stream, block), so the table does
not depend on how many threads made it, and everything that SHAPES the
table (frequency laws, the ids' order, the label model's effects) comes
from the seeds in the file, only the rows from the run's seed.

Columns (`data.columns`, one entry a column, in order):
  {"kind": "count", "name": n, "mu": m, "sigma": s, "missing": q}
      floor(exp(N(m, s))): a non-negative integer as float32, NaN with
      probability q
  {"kind": "categorical", "name": n, "cardinality": N, "zipf": a,
   "missing": q}
      an id in [0, N) as float32, NaN with probability q. The RANK r
      (1 the most frequent) is floor(x) for x of density x^-a on
      [1, N + 1): P(r) = ((r+1)^(1-a) - r^(1-a)) / ((N+1)^(1-a) - 1), a
      Zipf law with a closed inverse, so a draw is one power and no
      table of N entries is searched. The id of a rank is its place in
      the order of first appearance: each rank gets an arrival time
      Exp(1) / P(r) from `shape_seed`, and ids count the arrivals. A
      frequent category so has a small id, in no order that a
      threshold on the id could use.

Label model (`data.label`): from `label.seed`, `cat_terms` categorical
columns each with a weight and a PER-CATEGORY effect (a hash of column
and id to a standard normal: not a function of the id's size; a missing
id is a category of its own), `count_terms` count columns through
tanh((log1p(x) - mu) / sigma) (0 where missing) at `count_weight` of a
categorical term, and `interactions` products of two categorical effects.
The label is a Bernoulli draw from the sigmoid of
bias + strength * (sum of terms) / sqrt(number of terms).
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import statistics

import numpy as np

STREAM_TRAIN, STREAM_HOLDOUT, STREAM_BINS = 0, 1, 2
_QUANTILES = 1 << 16
_M1, _M2 = np.uint64(0xBF58476D1CE4E5B9), np.uint64(0x94D049BB133111EB)


def _normal_table() -> np.ndarray:
    inv = statistics.NormalDist().inv_cdf
    return np.array([inv((i + 0.5) / _QUANTILES) for i in range(_QUANTILES)])


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 (wraps, as meant)."""
    x = (x ^ (x >> np.uint64(30))) * _M1
    x = (x ^ (x >> np.uint64(27))) * _M2
    return x ^ (x >> np.uint64(31))


def _threads(threads: int | None, jobs: int) -> int:
    return threads or max(1, min(jobs, (os.cpu_count() or 2) - 1))


class Spec:
    """The fixed part of a configuration's data: the columns' laws, the
    ids' order and the label model, drawn once from the seeds in the
    file, never from the run's seed."""

    def __init__(self, data: dict):
        self.block_rows = int(data.get("block_rows", 1 << 20))
        shape_seed = int(data.get("shape_seed", 1))
        self.columns = []
        for j, c in enumerate(data["columns"]):
            col = {"kind": c["kind"], "name": c["name"], "index": j,
                   "missing": float(c.get("missing", 0.0))}
            if c["kind"] == "count":
                col.update(mu=float(c["mu"]), sigma=float(c["sigma"]))
            elif c["kind"] == "categorical":
                col.update(card=int(c["cardinality"]), a=float(c["zipf"]))
            else:
                raise ValueError(f"unknown column kind {c['kind']!r}")
            self.columns.append(col)
        self.names = [c["name"] for c in self.columns]
        self.n_features = len(self.columns)
        self.cat_cols = [c["index"] for c in self.columns
                         if c["kind"] == "categorical"]
        cats = [c for c in self.columns if c["kind"] == "categorical"]
        with cf.ThreadPoolExecutor(_threads(None, len(cats) or 1)) as ex:
            list(ex.map(lambda c: _first_appearance(c, shape_seed), cats))

        lab = data["label"]
        lrng = np.random.default_rng(int(lab["seed"]))
        counts = [c["index"] for c in self.columns if c["kind"] == "count"]
        kc = min(int(lab["cat_terms"]), len(self.cat_cols))
        kn = min(int(lab["count_terms"]), len(counts))
        self.cat_terms = [int(c) for c in
                          lrng.choice(self.cat_cols, kc, replace=False)]
        self.cat_coef = lrng.normal(size=kc)
        self.cat_salt = lrng.integers(1, 1 << 62, size=kc).astype(np.uint64)
        self.count_terms = [int(c) for c in
                            lrng.choice(counts, kn, replace=False)]
        self.count_coef = (lrng.normal(size=kn)
                           * float(lab.get("count_weight", 0.5)))
        m = int(lab.get("interactions", 0))
        self.pairs = (lrng.choice(kc, (m, 2)) if m and kc
                      else np.zeros((0, 2), np.int64))
        self.pair_coef = lrng.normal(size=len(self.pairs))
        self.n_terms = kc + kn + len(self.pairs)
        self.strength = float(lab["strength"])
        self.bias = float(lab["bias"])
        self._normal = _normal_table()

    def effect(self, term: int, ids: np.ndarray) -> np.ndarray:
        """The per-category effect of categorical term `term` for a
        column of ids (NaN = missing, a category of its own)."""
        card = self.columns[self.cat_terms[term]]["card"]
        key = np.where(np.isnan(ids), card, ids).astype(np.uint64)
        return self._normal[(_mix(key + self.cat_salt[term])
                             >> np.uint64(48)).astype(np.int64)]

    def logit(self, X: np.ndarray) -> np.ndarray:
        eff = [self.effect(t, X[:, c]) for t, c in enumerate(self.cat_terms)]
        t = np.zeros(X.shape[0], np.float64)
        for e, coef in zip(eff, self.cat_coef):
            t += coef * e
        for c, coef in zip(self.count_terms, self.count_coef):
            col = self.columns[c]
            x = X[:, c].astype(np.float64)
            v = np.tanh((np.log1p(x) - col["mu"]) / col["sigma"])
            t += coef * np.where(np.isnan(x), 0.0, v)
        for (a, b), coef in zip(self.pairs, self.pair_coef):
            t += coef * eff[a] * eff[b]
        return self.bias + self.strength * t / np.sqrt(max(self.n_terms, 1))


def _rank_mass(card: int, a: float) -> np.ndarray:
    """P(rank) for ranks 1..card under the law in the module's text."""
    edges = np.arange(1, card + 2, dtype=np.float64)
    cdf = np.log(edges) if a == 1.0 else edges ** (1.0 - a)
    return np.diff(cdf) / (cdf[-1] - cdf[0])


def _first_appearance(col: dict, shape_seed: int) -> None:
    """col["id_of_rank"]: the id (int32) of each rank, the ranks numbered
    in the order of their exponential arrival times at rate P(rank)."""
    rng = np.random.default_rng([shape_seed, col["index"]])
    arrival = rng.exponential(size=col["card"]) / _rank_mass(col["card"],
                                                             col["a"])
    ids = np.empty(col["card"], np.int32)
    ids[np.argsort(arrival, kind="stable")] = np.arange(col["card"],
                                                        dtype=np.int32)
    col["id_of_rank"] = ids


def _ranks(col: dict, u: np.ndarray) -> np.ndarray:
    """Ranks (0-based) for uniforms u: the law's inverse, one power."""
    n1, a = col["card"] + 1.0, col["a"]
    if a == 1.0:
        x = n1 ** u
    else:
        x = (1.0 + u * (n1 ** (1.0 - a) - 1.0)) ** (1.0 / (1.0 - a))
    return np.minimum(x.astype(np.int64) - 1, col["card"] - 1)


def _fill_block(spec: Spec, X: np.ndarray, y: np.ndarray, seed: int,
                stream: int, block: int) -> None:
    rng = np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([int(seed), int(stream), int(block)])))
    n = X.shape[0]
    # feature-major while it is made (a column is contiguous), row-major
    # once at the end
    cols = np.empty((spec.n_features, n), np.float32)
    for col in spec.columns:
        if col["kind"] == "count":
            v = np.floor(np.exp(col["mu"]
                                + col["sigma"] * rng.standard_normal(n)))
        else:
            v = col["id_of_rank"][_ranks(col, rng.random(n))]
        cols[col["index"]] = v
        if col["missing"] > 0.0:
            cols[col["index"], rng.random(n) < col["missing"]] = np.nan
    p = 1.0 / (1.0 + np.exp(-spec.logit(cols.T)))
    y[:] = rng.random(n) < p
    X[:] = cols.T


def generate(spec: Spec, n_rows: int, seed: int, stream: int,
             threads: int | None = None):
    """(X float32 [n_rows, F], y float32 [n_rows]) for one stream."""
    X = np.empty((n_rows, spec.n_features), np.float32)
    y = np.empty(n_rows, np.float32)
    br = spec.block_rows
    blocks = [(b, b * br, min((b + 1) * br, n_rows))
              for b in range((n_rows + br - 1) // br)]
    with cf.ThreadPoolExecutor(_threads(threads, len(blocks))) as ex:
        list(ex.map(lambda t: _fill_block(spec, X[t[1]:t[2]], y[t[1]:t[2]],
                                          seed, stream, t[0]), blocks))
    return X, y
