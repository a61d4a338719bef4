"""Booster: the user-facing training/prediction handle.

Reference: python-package/lightgbm/basic.py (UNVERIFIED — empty mount, see
SURVEY.md banner). There, ``Booster`` is a ctypes proxy over the C API's
LGBM_Booster* handles; here it wraps the in-process GBDT engine directly —
the TPU framework is Python-hosted, so the ABI seam the reference needs
(C API, SURVEY.md §1 L7) collapses into this class while keeping the same
method surface.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from .boosting import GBDT, create_boosting
from .config import Config
from .io.dataset import Dataset
from .utils import log
from .utils.log import LightGBMError

__all__ = ["Booster", "Dataset", "LightGBMError"]


class Booster:
    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 init_forest=None):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._engine: Optional[GBDT] = None
        self._from_model = None
        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            self.config = Config(self.params)
            train_set.params.setdefault("max_bin", self.config.max_bin)
            for key in ("min_data_in_bin", "bin_construct_sample_cnt",
                        "use_missing", "zero_as_missing",
                        "data_random_seed", "linear_tree",
                        # device-ingest knobs ride along so train-param
                        # settings govern the construct that this
                        # Booster triggers (ops/ingest.py) — including
                        # the gates _want_transposed_ingest /
                        # _want_device_ingest read (precision,
                        # streaming), else construct emits device
                        # arrays the engine will never adopt
                        "tpu_ingest_device", "tpu_ingest_chunk_rows",
                        "tpu_ingest_threads",
                        "tpu_double_precision_hist", "tpu_streaming",
                        "tree_learner", "tpu_compile_cache_dir"):
                train_set.params.setdefault(key, getattr(self.config, key))
            self._engine = create_boosting(self.config, train_set,
                                           init_forest=init_forest)
            self.train_set = train_set
        elif model_file is not None or model_str is not None:
            from .io.model_text import load_model_string
            if model_file is not None:
                with open(model_file) as f:
                    model_str = f.read()
            self._from_model = load_model_string(model_str)
            self.config = Config(self.params)
        else:
            raise TypeError("At least one of train_set, model_file or "
                            "model_str should be provided")
        # serve-side hot-swap (serving.py): tpu_model_watch names a
        # checkpoint dir this Booster polls at predict time, atomically
        # swapping freshly published models in
        self._model_watch = None
        watch = str(getattr(self.config, "tpu_model_watch", "")
                    or "").strip()
        if watch:
            self.watch_checkpoints(
                watch, interval=float(getattr(
                    self.config, "tpu_model_watch_interval", 2.0)))

    def watch_checkpoints(self, directory: str,
                          interval: float = 2.0) -> "Booster":
        """Hot-swap serving: poll ``directory`` (a recovery-subsystem
        checkpoint dir) every ``interval`` seconds at predict time and
        atomically adopt the newest valid checkpoint's model — zero
        dropped requests, zero warm-path recompiles for same-bucket
        models, graceful degradation on corrupt publishes
        (docs/robustness.md "Hot-swap serving"). The param form is
        ``tpu_model_watch`` / ``tpu_model_watch_interval``."""
        from .serving import ModelWatcher
        self._model_watch = ModelWatcher(directory, interval=interval)
        if self._engine is not None:
            # pin the engine to bucketed predict shapes up front so the
            # warm-up predict compiles the SAME programs every later
            # swap reuses (not an unpadded one-off)
            self._engine._stable_predict_shapes = True
        return self

    # ------------------------------------------------------------------
    @property
    def engine(self) -> GBDT:
        if self._engine is None:
            raise LightGBMError("Booster has no training engine "
                                "(loaded from model file)")
        return self._engine

    def metrics(self) -> Dict[str, Any]:
        """Current observability snapshot (docs/observability.md):
        counters / gauges / histograms from the process-wide registry,
        with the device/compile gauges refreshed. Collection is off by
        default — enable with ``tpu_metrics=true`` (or
        ``lightgbm_tpu.obs.enable()``), else the snapshot is empty or
        partial."""
        from . import obs
        if self._engine is not None and hasattr(self._engine,
                                                "metrics_snapshot"):
            return self._engine.metrics_snapshot()
        # no engine (model-file booster) or an engine without the API
        # (StreamingGBDT): the registry is process-wide anyway
        return obs.snapshot()

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        self.engine.add_valid(data, name)
        if not hasattr(self, "_valid_sets"):
            self._valid_sets = []
        self._valid_sets.append(data)
        return self

    def update(self, train_set: Optional[Dataset] = None,
               fobj: Optional[Callable] = None) -> bool:
        """Run one boosting iteration; returns True if stopped early."""
        if train_set is not None and train_set is not self.train_set:
            raise LightGBMError("Replacing train_set mid-training is not "
                                "supported")
        if fobj is not None:
            preds = self._inner_raw_predict()
            grad, hess = fobj(preds, self.train_set)
            self.engine.train_one_iter(np.asarray(grad), np.asarray(hess))
        else:
            self.engine.train_one_iter()
        return False

    def _inner_raw_predict(self) -> np.ndarray:
        eng = self.engine
        raw = np.asarray(eng.score)[:eng.data.n]
        if eng.num_class == 1:
            return raw[:, 0].astype(np.float64)
        return raw.astype(np.float64).reshape(-1, order="F")

    def rollback_one_iter(self) -> "Booster":
        self.engine.rollback_one_iter()
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self.config.update(params)
        # rebuild jitted step so learning-rate etc. take effect
        self.engine.config = self.config
        self.engine._build_step()
        # a cached host model may bake the old params (e.g. sigmoid):
        # invalidate the booster-level cache AND the engine-level one
        # the linear-tree predict path keeps
        self._params_version = getattr(self, "_params_version", 0) + 1
        if hasattr(self.engine, "_invalidate_forest_cache"):
            self.engine._invalidate_forest_cache()
        return self

    # ------------------------------------------------------------------
    def eval_train(self, feval=None) -> List:
        return self._eval(-1, feval)

    def eval_valid(self, feval=None) -> List:
        out = []
        for i in range(len(self.engine.valid_data)):
            out.extend(self._eval(i, feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None) -> List:
        for i, n in enumerate(self.engine.valid_names):
            if n == name:
                return self._eval(i, feval)
        self.add_valid(data, name)
        return self._eval(len(self.engine.valid_names) - 1, feval)

    def _eval(self, which: int, feval=None) -> List:
        results = self.engine.eval_set(which)
        if feval is not None:
            eng = self.engine
            if which < 0:
                ds, raw = self.train_set, np.asarray(
                    eng.score)[:eng.data.n]
                name = "training"
            else:
                dd = eng.valid_data[which]
                raw = np.asarray(eng.valid_scores[which])[:dd.n]
                name = eng.valid_names[which]
                ds = getattr(self, "_valid_sets", [None] * (which + 1))[which]
            preds = raw[:, 0] if eng.num_class == 1 else raw
            fret = feval(preds.astype(np.float64), ds)
            if fret is not None:
                items = fret if isinstance(fret, list) else [fret]
                for metric_name, value, higher_better in items:
                    results.append((name, metric_name, value,
                                    higher_better))
        return results

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **_kwargs) -> np.ndarray:
        watch = getattr(self, "_model_watch", None)
        if watch is None:
            return self._predict_dispatch(
                data, start_iteration, num_iteration, raw_score,
                pred_leaf, pred_contrib, _kwargs)
        # serve-side hot-swap: the rate-limited poll AND the model read
        # both run under the watcher's swap lock, so any thread's
        # request sees the old or the new model atomically — the
        # THREADING CONTRACT serving.py documents, enforced here
        # instead of delegated to the caller
        with watch.swap_lock:
            watch.maybe_swap(self)
            return self._predict_dispatch(
                data, start_iteration, num_iteration, raw_score,
                pred_leaf, pred_contrib, _kwargs)

    def _predict_dispatch(self, data, start_iteration, num_iteration,
                          raw_score, pred_leaf, pred_contrib,
                          _kwargs) -> np.ndarray:
        if num_iteration is None:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else -1)
        es_kwargs = {k: _kwargs[k] for k in
                     ("pred_early_stop", "pred_early_stop_freq",
                      "pred_early_stop_margin", "contrib_force_f64")
                     if k in _kwargs}
        if self._from_model is not None:
            return self._host_predict(
                self._from_model, data, raw_score=raw_score,
                start_iteration=start_iteration,
                num_iteration=num_iteration, pred_leaf=pred_leaf,
                pred_contrib=pred_contrib, **es_kwargs)
        # upstream convention: extra predict kwargs act as per-call
        # parameter overrides — forward the serving knobs to the engine
        serving_kwargs = {k: v for k, v in _kwargs.items()
                          if k.startswith("tpu_predict_")}
        if pred_contrib and not es_kwargs.get("pred_early_stop"):
            # SHAP-capable configs take the engine path: cached device
            # path tables, bucketed zero-compile dispatch, tree
            # sharding. Demoted engines (capability table) explain
            # through the host model with a warned stand-down.
            from . import capabilities
            from .serve.shard import engine_kind
            eng = self.engine
            if bool(getattr(self.config, "linear_tree", False)):
                why = "linear_tree"
            else:
                why = engine_kind(eng)
            verdict = capabilities.sharded_shap_verdict(
                engine_kind(eng), self.config)
            if verdict == capabilities.SUPPORTED:
                return eng.predict_contrib(
                    data, start_iteration=start_iteration,
                    num_iteration=num_iteration or -1,
                    host_model=self._to_host_model(),
                    force_f64=es_kwargs.get("contrib_force_f64"),
                    **serving_kwargs)
            if not getattr(self, "_warned_shap_demote", False):
                self._warned_shap_demote = True
                log.warning(capabilities.SHARDED_SHAP_MESSAGES.get(
                    why, capabilities.SHARDED_SHAP_MESSAGES[
                        "streaming"]))
        if pred_contrib or es_kwargs.get("pred_early_stop"):
            return self._host_predict(
                self._to_host_model(), data, raw_score=raw_score,
                start_iteration=start_iteration,
                num_iteration=num_iteration, pred_leaf=pred_leaf,
                pred_contrib=pred_contrib, **es_kwargs)
        return self.engine.predict(
            data, raw_score=raw_score, start_iteration=start_iteration,
            num_iteration=num_iteration or -1, pred_leaf=pred_leaf,
            **serving_kwargs)

    def _host_predict(self, model, data, **kw) -> np.ndarray:
        """HostModel predicts under the SAME serve instrumentation the
        engine path uses (one shared ``obs.predict_instrumented``
        sequence): a model-file-loaded booster and the pred_contrib /
        pred_early_stop detours are serving paths too — /readyz,
        slo.predict_p99_ms and the request/error counters must see
        them, or a load-model-and-serve pod never turns ready."""
        from . import obs
        if not obs.any_enabled():
            return model.predict(data, **kw)
        return obs.predict_instrumented(
            lambda: model.predict(data, **kw), data)

    # ------------------------------------------------------------------
    def _to_host_model(self):
        """Engine trees -> HostModel, cached until the model changes.

        Repeated ``pred_contrib``/``pred_early_stop`` predicts (and
        ``dump_model``/``model_to_string`` reads) reuse one host model
        instead of rebuilding it from the engine's trees each call. The
        key tracks the engine's model count AND mutation version
        (DART/RF rescale leaves in place without changing the count)
        plus ``best_iteration`` and the booster's param version
        (``reset_parameter`` can change values the host model bakes
        in), all of which the built model depends on."""
        eng = self.engine
        key = (len(eng.models), getattr(eng, "_models_version", -1),
               self.best_iteration, getattr(self, "_params_version", 0))
        cached = getattr(self, "_host_model_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        from .io.model_text import HostModel
        hm = HostModel.from_engine(eng, self.config,
                                   best_iteration=self.best_iteration)
        self._host_model_cache = (key, hm)
        return hm

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict:
        """JSON-able model dict (GBDT::DumpModel semantics)."""
        from .io.model_text import dump_model_json
        hm = (self._from_model if self._from_model is not None
              else self._to_host_model())
        return dump_model_json(hm, num_iteration or -1, start_iteration)

    def trees_to_dataframe(self):
        """One row per node/leaf (mirrors lightgbm.Booster
        .trees_to_dataframe; requires pandas)."""
        import pandas as pd
        rows = []

        def walk(ti, node, parent_idx, depth):
            base = {"tree_index": ti, "node_depth": depth,
                    "parent_index": parent_idx}
            if "leaf_value" in node:
                rows.append({**base,
                             "node_index": f"{ti}-L{node['leaf_index']}",
                             "split_feature": None, "threshold": None,
                             "split_gain": None, "decision_type": None,
                             "missing_type": None,
                             "value": node["leaf_value"],
                             "weight": node.get("leaf_weight"),
                             "count": node.get("leaf_count")})
                return f"{ti}-L{node['leaf_index']}"
            me = f"{ti}-S{node['split_index']}"
            row = {**base, "node_index": me,
                   "split_feature": node["split_feature"],
                   "threshold": node["threshold"],
                   "split_gain": node["split_gain"],
                   "decision_type": node["decision_type"],
                   "missing_type": node["missing_type"],
                   "value": node["internal_value"],
                   "weight": None,
                   "count": node["internal_count"]}
            rows.append(row)
            row["left_child"] = walk(ti, node["left_child"], me,
                                     depth + 1)
            row["right_child"] = walk(ti, node["right_child"], me,
                                      depth + 1)
            return me

        for ti, info in enumerate(self.dump_model()["tree_info"]):
            walk(ti, info["tree_structure"], None, 1)
        return pd.DataFrame(rows)

    def model_to_c(self) -> str:
        """Standalone C prediction source (convert_model if-else)."""
        from .io.model_text import model_to_c
        hm = (self._from_model if self._from_model is not None
              else self._to_host_model())
        return model_to_c(hm)

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        from .io.model_text import save_model_string
        if (importance_type == "split"
                and int(self.params.get("saved_feature_importance_type",
                                        0) or 0) == 1):
            # config saved_feature_importance_type=1 -> gain importances
            importance_type = "gain"
        hm = (self._from_model if self._from_model is not None
              else self._to_host_model())
        return save_model_string(hm, importance_type=importance_type)

    def save_model(self, filename: str,
                   num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration,
                                         importance_type))
        return self

    # ------------------------------------------------------------------
    def num_trees(self) -> int:
        if self._from_model is not None:
            return len(self._from_model.trees)
        return self.engine.num_trees()

    def current_iteration(self) -> int:
        if self._from_model is not None:
            return len(self._from_model.trees) \
                // max(self._from_model.num_class, 1)
        return self.engine.current_iteration

    def num_model_per_iteration(self) -> int:
        if self._from_model is not None:
            return self._from_model.num_class
        return self.engine.num_class

    def num_feature(self) -> int:
        if self._from_model is not None:
            return self._from_model.max_feature_idx + 1
        return self.train_set.num_total_features

    def feature_name(self) -> List[str]:
        if self._from_model is not None:
            return list(self._from_model.feature_names)
        return list(self.train_set.feature_names)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """Split-count or total-gain importance (GBDT::FeatureImportance)."""
        if self._from_model is not None:
            trees = self._from_model.trees
            n_feat = self._from_model.max_feature_idx + 1
            used = list(range(n_feat))
        else:
            trees = self.engine.models
            n_feat = self.train_set.num_total_features
            used = self.train_set.used_features
        if iteration is not None and iteration > 0:
            trees = trees[:iteration * self.num_model_per_iteration()]
        imp = np.zeros(n_feat, dtype=np.float64)
        for t in trees:
            for i in range(t.num_nodes):
                f = used[int(t.split_feature[i])]
                if importance_type == "gain":
                    imp[f] += float(t.split_gain[i])
                else:
                    imp[f] += 1.0
        if importance_type == "split":
            return imp.astype(np.int64)
        return imp

    def _refit_config(self) -> Config:
        """Config for refit: user params, falling back to the loaded
        model's stored objective when params don't name one."""
        params = dict(self.params)
        has_obj = any(Config.canonical_name(k) == "objective"
                      for k in params)
        if not has_obj:
            hm = (self._from_model if self._from_model is not None
                  else self._to_host_model())
            toks = hm.objective_str.split()
            if toks:
                params["objective"] = toks[0]
                for t in toks[1:]:
                    k, _, v = t.partition(":")
                    if k in ("sigmoid", "num_class"):
                        params[k] = float(v) if k == "sigmoid" else int(v)
        return Config(params)

    def refit(self, data, label, weight=None, group=None,
              decay_rate: Optional[float] = None, **_kwargs) -> "Booster":
        """Refit the existing tree STRUCTURES' leaf values on new data
        (GBDT::RefitTree, src/boosting/gbdt.cpp, UNVERIFIED): boost
        sequentially from the init score — per iteration, compute
        gradients at the current refitted score, re-derive each leaf's
        optimal output from the rows it receives, blend ``decay_rate *
        old + (1 - decay_rate) * new``, and add the refitted tree to the
        score before the next iteration. Returns a new (prediction-only)
        Booster."""
        from .io.model_text import load_model_string, save_model_string
        from .objective import create_objective
        from .ops.split import calc_leaf_output
        import jax
        import jax.numpy as jnp
        cfg = self._refit_config()
        if decay_rate is None:
            decay_rate = cfg.refit_decay_rate
        hm = load_model_string(self.model_to_string())  # deep copy
        X = Dataset._to_matrix(data)
        label = np.asarray(label, dtype=np.float64)
        n = len(X)
        K = max(hm.num_tree_per_iteration, 1)
        obj = create_objective(cfg)
        if hasattr(obj, "prepare"):
            obj.prepare(label, weight)
        if obj.is_ranking:
            if group is None:
                raise LightGBMError("refit on a ranking objective needs "
                                    "the group argument")
            qb = np.concatenate([[0], np.cumsum(np.asarray(group))])
            obj.setup_queries(qb.astype(np.int64), n)
        # boost-from-average on the NEW data (the refit booster in the
        # reference is constructed fresh on the new dataset). The stored
        # model folds the bias into the first iteration's leaves, so the
        # running score is the plain sum of STORED leaf values; s0 only
        # seeds the gradient point before tree 0 exists.
        s0 = np.zeros(K)
        if K == 1:
            s0[0] = obj.init_score(label, weight)
        score = np.zeros((n, K))
        w_dev = None if weight is None else jnp.asarray(weight)
        label_dev = jnp.asarray(label)
        num_iters = len(hm.trees) // K
        leaf_idx = [t.predict_leaf_raw(X) for t in hm.trees]
        for it in range(num_iters):
            if hm.average_output:
                # RF: every tree is independent — gradients at init,
                # each tree carries its own bias
                grad_point = np.tile(s0, (n, 1))
            elif it == 0:
                grad_point = np.tile(s0, (n, 1))
            else:
                grad_point = score
            sc = jnp.asarray(grad_point[:, 0] if K == 1 else grad_point)
            if getattr(obj, "has_pos_state", False):
                # refit with neutral propensities (pos_state=None): the
                # training-time bias state is not serialized with the
                # model
                g, h, _ = obj.get_gradients(sc, label_dev, w_dev)
            elif getattr(obj, "needs_rng", False):
                g, h = obj.get_gradients(sc, label_dev, w_dev,
                                         key=jax.random.PRNGKey(it))
            else:
                g, h = obj.get_gradients(sc, label_dev, w_dev)
            g = np.asarray(g).reshape(n, -1)
            h = np.asarray(h).reshape(n, -1)
            for k in range(K):
                t = hm.trees[it * K + k]
                leaf = leaf_idx[it * K + k]
                nl = t.num_leaves
                gs = np.bincount(leaf, weights=g[:, k], minlength=nl)[:nl]
                hs = np.bincount(leaf, weights=h[:, k], minlength=nl)[:nl]
                cnt = np.bincount(leaf, minlength=nl)[:nl]
                new_out = np.asarray(calc_leaf_output(
                    jnp.asarray(gs), jnp.asarray(hs), cfg.lambda_l1,
                    cfg.lambda_l2, cfg.max_delta_step)) * t.shrinkage
                if hm.average_output or it == 0:
                    # keep the file self-contained: bias in iteration-0
                    # leaves (AddBias), or in every leaf for RF
                    new_out = new_out + s0[k]
                # leaves with no rows in the new data keep their old value
                new_out = np.where(cnt > 0, new_out, t.leaf_value)
                t.leaf_value = (decay_rate * t.leaf_value
                                + (1.0 - decay_rate) * new_out)
                t.leaf_count = cnt.astype(np.int64)
                score[:, k] += t.leaf_value[leaf]
        return Booster(params=self.params,
                       model_str=save_model_string(hm))

    def free_dataset(self) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self
