"""The generator of a click log as a GBDT is fed it after target and count
encoding: every column a float. Integer count columns as lib/clickgen.py
makes them, then for each id column TWO columns, the click rate and the
number of impressions of the row's category in a fixed HISTORY table
(LightGBM docs/Experiments.rst, "Parallel Experiment": the categorical
features of the Criteo Terabyte log "encoded by the CTR and count" of the
first ten days), then the same two for each crossed pair of id columns.
Driven by the `data` section of a configuration file (named there as
`data.generator`); this file knows no configuration by name. The interface
is lib/datagen.py's (`Spec`, `generate`, `STREAM_*`): rows are made in
blocks, each from its own counter-based generator keyed by (seed, stream,
block), and everything that SHAPES the table (frequency laws, the label
model's effects, the history) comes from the seeds in the file.

`data.counts` (one entry a column): {"name", "mu", "sigma", "missing"}:
    floor(exp(N(mu, sigma))) as float32, NaN with probability `missing`.
`data.ids` (one entry an id column): {"name", "cardinality", "zipf",
    "missing"}: a category drawn by clickgen's Zipf law (one power, no
    table); a missing id is a category of its own. The id itself never
    reaches the table, so a category IS its rank here.
`data.crosses`: pairs of id-column names; the crossed category is the
    pair of the two.
`data.history`: {"seed", "rows"}: that many rows are generated once (stream
    STREAM_HISTORY of that seed) with their labels, and each category's
    impressions n and clicks c over them are kept. A row's two columns for
    an id column are c / n and n of its category; a category the history
    never saw reads NaN and 0.

Label model (`data.label`), clickgen's: from `label.seed`, `id_terms` id
columns each with a weight and a PER-CATEGORY effect (a hash of column and
category to a standard normal), `count_terms` count columns through
tanh((log1p(x) - mu) / sigma) (0 where missing) at `count_weight` of an id
term, one product of the two effects for every crossed pair whose columns
both carry a term, and `interactions` more products. The label is a
Bernoulli draw from the sigmoid of
bias + strength * (sum of terms) / sqrt(number of terms): the rate columns
carry the signal, as they do in a real log.
"""
from __future__ import annotations

import concurrent.futures as cf

import numpy as np

from lib import clickgen

STREAM_TRAIN, STREAM_HOLDOUT, STREAM_BINS = (
    clickgen.STREAM_TRAIN, clickgen.STREAM_HOLDOUT, clickgen.STREAM_BINS)
STREAM_HISTORY = 3


class Spec:
    """The fixed part of a configuration's data: the columns' laws, the
    label model and the history table, drawn once from the seeds in the
    file, never from the run's seed."""

    def __init__(self, data: dict):
        self.block_rows = int(data.get("block_rows", 1 << 20))
        self.counts = [{"name": c["name"], "mu": float(c["mu"]),
                        "sigma": float(c["sigma"]),
                        "missing": float(c.get("missing", 0.0))}
                       for c in data["counts"]]
        self.ids = [{"name": c["name"], "card": int(c["cardinality"]),
                     "a": float(c["zipf"]),
                     "missing": float(c.get("missing", 0.0))}
                    for c in data["ids"]]
        by_name = {c["name"]: j for j, c in enumerate(self.ids)}
        self.crosses = [(by_name[a], by_name[b])
                        for a, b in data.get("crosses", [])]
        self.names = ([c["name"] for c in self.counts]
                      + [f"{c['name']}_{k}" for c in self.ids
                         for k in ("ctr", "cnt")]
                      + [f"{self.ids[a]['name']}x{self.ids[b]['name']}_{k}"
                         for a, b in self.crosses for k in ("ctr", "cnt")])
        self.n_features = len(self.names)

        lab = data["label"]
        lrng = np.random.default_rng(int(lab["seed"]))
        k_id = min(int(lab["id_terms"]), len(self.ids))
        k_n = min(int(lab["count_terms"]), len(self.counts))
        # every crossed column carries a term, so that a crossed pair's
        # product is in the model
        crossed = sorted({j for pair in self.crosses for j in pair})
        rest = [j for j in range(len(self.ids)) if j not in crossed]
        more = max(k_id - len(crossed), 0)
        self.id_terms = crossed + [int(j) for j in lrng.choice(
            rest, min(more, len(rest)), replace=False)]
        self.id_coef = lrng.normal(size=len(self.id_terms))
        self.id_salt = lrng.integers(
            1, 1 << 62, size=len(self.id_terms)).astype(np.uint64)
        self.count_terms = [int(c) for c in lrng.choice(
            len(self.counts), k_n, replace=False)]
        self.count_coef = (lrng.normal(size=k_n)
                           * float(lab.get("count_weight", 0.5)))
        term_of = {j: t for t, j in enumerate(self.id_terms)}
        pairs = [(term_of[a], term_of[b]) for a, b in self.crosses]
        m = int(lab.get("interactions", 0))
        if m and self.id_terms:
            pairs += [tuple(int(v) for v in p) for p in
                      lrng.choice(len(self.id_terms), (m, 2))]
        self.pairs = pairs
        self.pair_coef = lrng.normal(size=len(pairs))
        self.n_terms = len(self.id_terms) + k_n + len(pairs)
        self.strength = float(lab["strength"])
        self.bias = float(lab["bias"])
        self._normal = clickgen._normal_table()

        hist = data["history"]
        self.history_rows = int(hist["rows"])
        self.history_seed = int(hist["seed"])
        self.history = self._make_history()

    # ---- one block's raw draws ------------------------------------------
    def _draw(self, rng, n: int):
        """(counts float32 [n_counts, n], categories int64 [n_ids, n],
        labels bool [n]) of one block. A missing id is category `card`."""
        cnt = np.empty((len(self.counts), n), np.float32)
        for j, c in enumerate(self.counts):
            cnt[j] = np.floor(np.exp(c["mu"]
                                     + c["sigma"] * rng.standard_normal(n)))
            if c["missing"] > 0.0:
                cnt[j, rng.random(n) < c["missing"]] = np.nan
        cat = np.empty((len(self.ids), n), np.int64)
        for j, c in enumerate(self.ids):
            cat[j] = clickgen._ranks(c, rng.random(n))
            if c["missing"] > 0.0:
                cat[j, rng.random(n) < c["missing"]] = c["card"]
        p = 1.0 / (1.0 + np.exp(-self.logit(cnt, cat)))
        return cnt, cat, rng.random(n) < p

    def logit(self, cnt: np.ndarray, cat: np.ndarray) -> np.ndarray:
        eff = [self._normal[(clickgen._mix(cat[j].astype(np.uint64) + salt)
                             >> np.uint64(48)).astype(np.int64)]
               for j, salt in zip(self.id_terms, self.id_salt)]
        t = np.zeros(cat.shape[1], np.float64)
        for e, coef in zip(eff, self.id_coef):
            t += coef * e
        for j, coef in zip(self.count_terms, self.count_coef):
            c = self.counts[j]
            x = cnt[j].astype(np.float64)
            v = np.tanh((np.log1p(x) - c["mu"]) / c["sigma"])
            t += coef * np.where(np.isnan(x), 0.0, v)
        for (a, b), coef in zip(self.pairs, self.pair_coef):
            t += coef * eff[a] * eff[b]
        return self.bias + self.strength * t / np.sqrt(max(self.n_terms, 1))

    def _keys(self, cat: np.ndarray):
        """The category of every encoded column group of a block: the id
        columns, then the crossed pairs."""
        for j in range(len(self.ids)):
            yield cat[j]
        for a, b in self.crosses:
            yield cat[a] * (self.ids[b]["card"] + 1) + cat[b]

    def _make_history(self) -> list[np.ndarray]:
        """For each encoded group a float32 [categories, 2] table: the
        click rate (NaN where never seen) and the impressions."""
        br = self.block_rows
        n_rows = self.history_rows
        blocks = [(b, min(br, n_rows - b * br))
                  for b in range((n_rows + br - 1) // br)]

        sizes = ([c["card"] + 1 for c in self.ids]
                 + [(self.ids[a]["card"] + 1) * (self.ids[b]["card"] + 1)
                    for a, b in self.crosses])
        if max(sizes) >= 1 << 31:
            raise ValueError("a crossed pair has more than 2^31 categories")

        def one(job):
            b, n = job
            _cnt, cat, y = self._draw(_block_rng(
                self.history_seed, STREAM_HISTORY, b), n)
            return [k.astype(np.int32) for k in self._keys(cat)], y

        with cf.ThreadPoolExecutor(clickgen._threads(None, len(blocks))) \
                as ex:
            parts = list(ex.map(one, blocks))
        y = np.concatenate([p[1] for p in parts])

        def table(g):
            keys = np.concatenate([p[0][g] for p in parts])
            seen = np.bincount(keys, minlength=sizes[g])
            clicks = np.bincount(keys[y], minlength=sizes[g])
            tab = np.empty((sizes[g], 2), np.float32)
            tab[:, 1] = seen
            with np.errstate(invalid="ignore"):
                tab[:, 0] = clicks.astype(np.float32) / tab[:, 1]  # 0/0: NaN
            return tab

        # a few at a time: a table of 40M categories passes 1 GB while
        # it is made
        with cf.ThreadPoolExecutor(min(4, clickgen._threads(
                None, len(sizes)))) as ex:
            return list(ex.map(table, range(len(sizes))))


def _block_rng(seed: int, stream: int, block: int):
    return np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([int(seed), int(stream), int(block)])))


def _fill_block(spec: Spec, X: np.ndarray, y: np.ndarray, seed: int,
                stream: int, block: int) -> None:
    n = X.shape[0]
    cnt, cat, lab = spec._draw(_block_rng(seed, stream, block), n)
    nc = cnt.shape[0]
    X[:, :nc] = cnt.T
    for g, (keys, tab) in enumerate(zip(spec._keys(cat), spec.history)):
        X[:, nc + 2 * g:nc + 2 * g + 2] = tab[keys]
    y[:] = lab


def generate(spec: Spec, n_rows: int, seed: int, stream: int,
             threads: int | None = None):
    """(X float32 [n_rows, F], y float32 [n_rows]) for one stream."""
    X = np.empty((n_rows, spec.n_features), np.float32)
    y = np.empty(n_rows, np.float32)
    br = spec.block_rows
    blocks = [(b, b * br, min((b + 1) * br, n_rows))
              for b in range((n_rows + br - 1) // br)]
    with cf.ThreadPoolExecutor(clickgen._threads(threads, len(blocks))) \
            as ex:
        list(ex.map(lambda t: _fill_block(spec, X[t[1]:t[2]], y[t[1]:t[2]],
                                          seed, stream, t[0]), blocks))
    return X, y
