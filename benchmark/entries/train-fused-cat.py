"""Entry `train-fused-cat`: the window of `train-fused` (the same warm
rounds, probe, one timed `train_chunk(R)`, hold-out scores, and the
follower of the window's first trees) over a table with categorical
columns, judged by lib/reference_cat.py, which reads set-splits.

It runs train-fused.py's own `prepare` and `drive` (its private copy of
that module, handed this configuration's reference), and adds what a
categorical table needs:
  - before the table is made, and again on the table's Dataset, it refuses
    (SystemExit) a program that does not make the configuration's
    `categorical_feature` columns categorical: a program that ignores the
    parameter would bin the ids as numbers and time another workload;
  - `window.cat`: the words a set-split's bitset takes in the model, the
    share of "other" cells, the program's bundling counters;
  - `window.chunks`: when each fused chunk's trees reached the host and how
    many histogram calls they took (the program's `hist.calls` counter,
    polled from a thread: one dictionary lookup every 20 ms), so that a slow
    window can be told from a window of costlier trees;
  - `window.host_peak_rss_bytes`.
"""
import os
import resource
import threading
import time
import types

import numpy as np

from lib import reference_cat
from lib.harness import load_module

_base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "train-fused.py"))
_parsed: list = []          # the trees of the model last judged


def _parse_and_keep(text: str) -> list:
    _parsed[:] = reference_cat.parse_model(text)
    return list(_parsed)


def _late(name: str):
    """reference_cat's function of that name, looked up at each call (a
    control plants its fault in the module between two drives)."""
    return lambda *a, **k: getattr(reference_cat, name)(*a, **k)


_base.reference = types.SimpleNamespace(
    parse_model=_parse_and_keep, auc=reference_cat.auc,
    expected_root_rows=reference_cat.expected_root_rows,
    predict_raw=_late("predict_raw"), follow_window=_late("follow_window"))


def _categorical_columns(ds) -> list:
    return [f for f, m in enumerate(ds.bin_mappers)
            if m.bin_type == "categorical"]


def _refuse_numeric_ids(h, ds, what: str) -> None:
    want = sorted(int(c) for c in h.config["params"]["categorical_feature"])
    got = _categorical_columns(ds)
    if got != want:
        raise SystemExit(
            f"the program made columns {got} of {what} categorical where "
            f"the configuration's params name {want}: it would bin ids as "
            f"numbers, and this cell times set-splits")


def prepare(h) -> dict:
    import lightgbm_tpu as lgb
    cfg = h.config
    # a thousand rows of small integers first: a program that ignores the
    # parameter is found out in seconds, not after the whole table's ingest
    probe = np.random.default_rng(0).integers(
        0, 10, (1000, len(cfg["data"]["columns"]))).astype(np.float32)
    probe = lgb.Dataset(probe, label=probe[:, 0] > 4, params=dict(
        cfg["params"], tpu_ingest_device=False)).construct()
    _refuse_numeric_ids(h, probe, "a 1,000-row probe")
    prep = _base.prepare(h)
    _refuse_numeric_ids(h, prep["ds"], "the table")
    return prep


def run(h) -> dict:
    prep = prepare(h)
    return drive(h, prep, prep.pop("params"), free=True)


class _ChunkLog(threading.Thread):
    """(seconds, hist.calls so far) each time the program's counter of
    the sampled chunk program's histogram calls moves: once a chunk."""

    def __init__(self):
        super().__init__(daemon=True)
        from lightgbm_tpu import obs
        self._counter = obs.counter("hist.calls", sampled=1)
        self._halt = threading.Event()
        self.moves = []

    def run(self):
        last = self._counter.value
        while not self._halt.wait(0.02):
            v = self._counter.value
            if v != last:
                self.moves.append((time.perf_counter(), v - last))
                last = v

    def close(self):
        self._halt.set()
        self.join()


def _counter_values(prefixes) -> dict:
    from lightgbm_tpu import obs
    out = {}
    for m in obs.registry().metrics():
        if m.name.startswith(prefixes) and hasattr(m, "value"):
            out[m.name + "".join(f"{{{k}={v}}}" for k, v
                                 in sorted(m.labels.items()))] = m.value
    return out


def drive(h, prep: dict, params: dict, free: bool = False) -> dict:
    log = _ChunkLog()
    log.start()
    try:
        result = _base.drive(h, prep, params, free=free)
    finally:
        log.close()
    window, chunk = result["window"], int(params["tpu_fuse_iters"])
    # the window's chunks are the last R / chunk the counter saw
    moves = log.moves[-(window["iters"] // chunk):]
    window["chunks"] = [[round(t - moves[0][0], 3), int(c)]
                        for t, c in moves]
    trees = _parsed[window["before"]:window["before"] + window["iters"]]
    window["cat"] = dict(
        _counter_values(("ingest.cat_", "split.chosen", "tree.cat_",
                         "bundle.")),
        window_splits=int(sum(t["num_leaves"] - 1 for t in trees)),
        window_cat_splits=int(sum(
            int(np.sum(reference_cat.is_categorical(t))) for t in trees)),
        window_bitset_words=int(sum(len(t["cat_threshold"]) for t in trees
                                    if t.get("num_cat", 0))))
    window["host_peak_rss_bytes"] = 1024 * resource.getrusage(
        resource.RUSAGE_SELF).ru_maxrss
    return result
