"""Model persistence: Booster <-> JSON text / file.

The reference deliberately avoids model checkpointing ("keep test predictions,
no model" — LightGBM R.ipynb:845) but LightGBM itself exposes
``save_model`` / ``model_to_string`` / ``Booster(model_file=...)``; SURVEY.md
§5 "Checkpoint / resume" mandates building it anyway.  Format: a single JSON
document (tensorized trees serialize naturally as arrays; bin bounds ride
along so a loaded model can bin raw inputs without the training data).
"""

from __future__ import annotations

import json
from typing import Optional

import numpy as np

_FORMAT_VERSION = 1


def _tree_to_dict(tree) -> dict:
    d = {
        "split_feature": np.asarray(tree.split_feature).tolist(),
        "split_bin": np.asarray(tree.split_bin).tolist(),
        "left": np.asarray(tree.left).tolist(),
        "right": np.asarray(tree.right).tolist(),
        "leaf_value": np.asarray(tree.leaf_value, dtype=np.float64).tolist(),
        "is_leaf": np.asarray(tree.is_leaf).astype(int).tolist(),
        "count": np.asarray(tree.count, dtype=np.float64).tolist(),
        "split_gain": np.asarray(tree.split_gain, dtype=np.float64).tolist(),
        # scalar for binary/regression; [K] list for multiclass rounds
        "num_leaves": np.asarray(tree.num_leaves).tolist(),
    }
    if tree.is_cat_split is not None:
        # sparse: only categorical split nodes carry their left-bin sets
        icb = np.asarray(tree.is_cat_split).reshape(-1)
        cm = np.asarray(tree.cat_mask)
        d["num_bins"] = int(cm.shape[-1])
        cm2 = cm.reshape(-1, cm.shape[-1])
        d["cat_splits"] = {
            str(i): np.flatnonzero(cm2[i]).tolist()
            for i in np.flatnonzero(icb)}
        d["cat_shape"] = list(np.asarray(tree.is_cat_split).shape)
    if tree.linear_feat is not None:
        d["linear_feat"] = np.asarray(tree.linear_feat).tolist()
        d["linear_coef"] = np.asarray(tree.linear_coef,
                                      np.float64).tolist()
    return d


def _tree_from_dict(d: dict):
    import jax.numpy as jnp
    from ..models.tree import Tree

    is_cat_split = cat_mask = None
    if "cat_splits" in d:
        shape = tuple(d["cat_shape"])
        b = int(d["num_bins"])
        icb = np.zeros(int(np.prod(shape)), bool)
        cm = np.zeros((int(np.prod(shape)), b), bool)
        for k, bins_left in d["cat_splits"].items():
            icb[int(k)] = True
            cm[int(k), np.asarray(bins_left, np.int64)] = True
        is_cat_split = jnp.asarray(icb.reshape(shape))
        cat_mask = jnp.asarray(cm.reshape(shape + (b,)))

    return Tree(
        split_feature=jnp.asarray(d["split_feature"], jnp.int32),
        split_bin=jnp.asarray(d["split_bin"], jnp.int32),
        left=jnp.asarray(d["left"], jnp.int32),
        right=jnp.asarray(d["right"], jnp.int32),
        leaf_value=jnp.asarray(d["leaf_value"], jnp.float32),
        is_leaf=jnp.asarray(d["is_leaf"], bool),
        count=jnp.asarray(d["count"], jnp.float32),
        split_gain=jnp.asarray(d["split_gain"], jnp.float32),
        num_leaves=jnp.asarray(d["num_leaves"], jnp.int32),
        is_cat_split=is_cat_split,
        cat_mask=cat_mask,
        linear_feat=(jnp.asarray(d["linear_feat"], jnp.int32)
                     if "linear_feat" in d else None),
        linear_coef=(jnp.asarray(d["linear_coef"], jnp.float32)
                     if "linear_coef" in d else None),
    )


def mapper_to_dict(mapper) -> dict:
    """BinMapper (+ attached EFB bundler) -> JSON-ready dict."""
    return {
        "upper_bounds": [ub.tolist() for ub in mapper.upper_bounds],
        "nan_bin": mapper.nan_bin.tolist(),
        "n_bins": mapper.n_bins.tolist(),
        "is_categorical": mapper.is_categorical.astype(int).tolist(),
        "bundler": (None if mapper.bundler is None else {
            "groups": mapper.bundler.groups,
            "default_bins": mapper.bundler.default_bins.tolist(),
        }),
    }


def mapper_from_dict(bm: dict):
    from ..dataset import BinMapper, FeatureBundler

    mapper = BinMapper(
        [np.asarray(ub, np.float64) for ub in bm["upper_bounds"]],
        np.asarray(bm["nan_bin"], np.int32),
        np.asarray(bm["n_bins"], np.int32),
        np.asarray(bm["is_categorical"], bool),
    )
    if bm.get("bundler"):
        mapper.bundler = FeatureBundler(
            bm["bundler"]["groups"], mapper.n_bins,
            np.asarray(bm["bundler"]["default_bins"], np.int64))
    return mapper


def booster_to_string(booster, num_iteration: Optional[int] = None,
                      start_iteration: int = 0) -> str:
    k = (len(booster.trees) if num_iteration is None or num_iteration <= 0
         else num_iteration)
    start = max(int(start_iteration), 0)
    mapper = booster._bin_mapper_for_predict()
    import dataclasses

    params_dict = dataclasses.asdict(booster.params)
    params_dict.pop("extra", None)
    # stored leaf values are normalized to the booster's BASE learning rate
    # (reset_parameter schedules bake lr_i/base in at append time), so the
    # reloaded predict-time shrink must be the base, not the final lr
    params_dict["learning_rate"] = float(
        getattr(booster, "_base_lr", booster.params.learning_rate))
    doc = {
        "format_version": _FORMAT_VERSION,
        "framework": "lightgbm_tpu",
        "params": params_dict,
        "init_score": np.asarray(booster.init_score_,
                                 dtype=np.float64).tolist(),
        "num_trees": int(min(k, len(booster.trees))),
        "best_iteration": int(booster.best_iteration),
        "feature_names": (booster.train_set.feature_names
                          if booster.train_set is not None
                          else getattr(booster, "_feature_names", None)),
        "bin_mapper": mapper_to_dict(mapper),
        "trees": [_tree_to_dict(t) for t in booster.trees[start:start + k]],
    }
    doc["num_trees"] = len(doc["trees"])
    return json.dumps(doc)


def save_booster(booster, filename: str,
                 num_iteration: Optional[int] = None,
                 start_iteration: int = 0) -> None:
    if filename.endswith(".npz"):
        # packed serving artifact (serving.packed): SoA tensor stack +
        # bin bounds, validated on ingest — the production predict path
        from ..serving.packed import pack_booster

        pack_booster(booster, num_iteration=num_iteration,
                     start_iteration=start_iteration).save(filename)
        return
    with open(filename, "w") as f:
        f.write(booster_to_string(booster, num_iteration=num_iteration,
                                  start_iteration=start_iteration))


def dump_booster_dict(booster, num_iteration: Optional[int] = None,
                      start_iteration: int = 0) -> dict:
    """LightGBM ``Booster.dump_model()`` equivalent: a nested-dict view of
    the model with RAW-VALUE thresholds (bin bounds resolved through the
    training bin mapper), traversable without any lightgbm_tpu code.

    Categorical splits dump ``decision_type: '=='`` with the LEFT set as
    LightGBM writes it, the raw category values joined by ``||`` (``"3||7||
    12"``; a value that is a whole number as an integer): a row goes left
    iff its raw value is one of them, and NaN, a value never seen or one
    of the rare categories that share the overflow bin goes right
    (``default_left: false``).  Numeric splits dump ``decision_type:
    '<='`` with the raw threshold.  Every split is on one ORIGINAL feature (EFB bundles are
    a training-time layout), so every threshold is a raw value.  Missing
    values are told as the codes route them: where fit saw NaN in a feature
    (``missing_type: "NaN"``) NaN has the last bin and goes right
    (``default_left: false``); where it saw none (``missing_type:
    "None"``) NaN is coded as 0.0 and goes where 0.0 goes.
    """
    start = max(int(start_iteration), 0)
    k = (len(booster.trees) if num_iteration is None or num_iteration <= 0
         else min(int(num_iteration), len(booster.trees) - start))
    mapper = booster._bin_mapper_for_predict()

    def nan_code(feat: int) -> int:
        """The bin NaN is coded to (the codes' own rule)."""
        if mapper.nan_bin[feat] >= 0:
            return int(mapper.nan_bin[feat])
        if mapper.is_categorical[feat]:
            return len(mapper.upper_bounds[feat])     # the overflow bin
        return int(np.searchsorted(mapper.upper_bounds[feat], 0.0,
                                   side="left"))

    def node_dict(tree, i: int, split_index: int):
        sf = np.asarray(tree.split_feature)
        sb = np.asarray(tree.split_bin)
        left = np.asarray(tree.left)
        right = np.asarray(tree.right)
        is_leaf = np.asarray(tree.is_leaf)
        vals = np.asarray(tree.leaf_value, np.float64)
        gains = np.asarray(tree.split_gain, np.float64)
        counts = np.asarray(tree.count, np.float64)
        icb = (np.asarray(tree.is_cat_split)
               if tree.is_cat_split is not None else None)
        cm = (np.asarray(tree.cat_mask)
              if tree.cat_mask is not None else None)

        def rec(node: int) -> dict:
            if is_leaf[node] or left[node] < 0:
                return {"leaf_index": int(node),
                        "leaf_value": float(vals[node]),
                        "leaf_count": int(counts[node])}
            feat = int(sf[node])
            thr_bin = int(sb[node])
            nan_at = nan_code(feat)
            cat = icb is not None and icb[node]
            out = {
                "split_index": int(node),
                "split_feature": feat,
                "split_gain": float(gains[node]),
                "internal_count": int(counts[node]),
                "missing_type": "NaN" if mapper.nan_bin[feat] >= 0
                else "None",
                "default_left": bool(cm[node][nan_at] if cat
                                     else nan_at <= thr_bin),
                "left_child": rec(int(left[node])),
                "right_child": rec(int(right[node])),
            }
            if cat:
                out["decision_type"] = "=="
                out["threshold"] = category_set_text(
                    mapper.upper_bounds[feat], np.flatnonzero(cm[node]))
            else:
                out["decision_type"] = "<="
                out["threshold"] = float(
                    mapper.bin_upper_bound(feat, thr_bin))
            return out

        import sys
        old_limit = sys.getrecursionlimit()
        sys.setrecursionlimit(max(old_limit, 2 * len(sf) + 100))
        try:
            return rec(0)
        finally:
            sys.setrecursionlimit(old_limit)

    trees_info = []
    idx = start * booster.num_model_per_iteration()
    for i, tree in enumerate(booster.trees[start:start + k]):
        ndim = np.asarray(tree.split_feature).ndim
        per_round = ([tree] if ndim == 1 else [
            type(tree)(*[None if f is None else
                         (np.asarray(f)[c] if np.asarray(f).ndim else f)
                         for f in tree])
            for c in range(np.asarray(tree.split_feature).shape[0])])
        for t in per_round:
            trees_info.append({
                "tree_index": idx,
                "num_leaves": int(np.asarray(t.num_leaves).max()),
                "shrinkage": float(
                    getattr(booster, "_base_lr",
                            booster.params.learning_rate)),
                "tree_structure": node_dict(t, idx, 0),
            })
            idx += 1
    return {
        "name": "tree",
        "version": "lightgbm_tpu",
        "objective": booster.params.objective,
        "num_class": booster.num_model_per_iteration(),
        "num_tree_per_iteration": booster.num_model_per_iteration(),
        "max_feature_idx": booster.num_feature() - 1,
        "feature_names": booster.feature_name(),
        "tree_info": trees_info,
    }


def category_set_text(categories, bins) -> str:
    """A category set as the dump writes it: the values of the set's bins
    (``categories``: a categorical column's kept values, its bins in
    order; the overflow bin, past them, holds no one value) joined by
    ``||``, each a whole number as an integer."""
    out = []
    for b in bins:
        if b < len(categories):
            v = float(categories[b])
            out.append(str(int(v)) if v.is_integer() else repr(v))
    return "||".join(out)


def load_booster_into(booster, model_file: Optional[str] = None,
                      model_str: Optional[str] = None) -> None:
    """Populate a bare Booster instance from a saved model (JSON text or a
    packed ``.npz`` serving artifact — the latter validates on ingest)."""
    import jax
    from ..config import parse_params
    from ..objectives import create_objective

    if model_file is not None and model_file.endswith(".npz"):
        _load_packed_into(booster, model_file)
        return
    if model_str is None:
        with open(model_file) as f:
            model_str = f.read()
    doc = json.loads(model_str)
    if doc.get("framework") != "lightgbm_tpu":
        raise ValueError("not a lightgbm_tpu model file")

    params_dict = {k: v for k, v in doc["params"].items() if v is not None}
    params_dict.pop("metric", None)
    booster.params = parse_params(params_dict, warn_unknown=False)
    booster.params.metric = doc["params"].get("metric") or []
    booster.obj = create_objective(booster.params)
    booster.train_set = None
    init = doc["init_score"]
    booster.init_score_ = (np.asarray(init, np.float32)
                           if isinstance(init, list) else float(init))
    booster.trees = [_tree_from_dict(t) for t in doc["trees"]]
    booster.best_iteration = int(doc.get("best_iteration", -1))
    booster.best_score = {}
    booster._valid = []
    booster._forest_cache = None
    booster._iter = len(booster.trees)
    booster._pred_train = None
    booster._bag = None
    booster._key = jax.random.PRNGKey(booster.params.seed)
    booster._feature_names = doc.get("feature_names")
    booster._bin_mapper = mapper_from_dict(doc["bin_mapper"])


def _load_packed_into(booster, path: str) -> None:
    """Populate a bare Booster from a packed ``.npz`` serving artifact.

    The packed loader already validated the forest structurally (child
    ranges, acyclicity, closed leaves), so a crafted model file raises
    PackedForestError here instead of hanging traversal later.  The packed
    format is prediction-only: per-node counts and split gains are not
    stored, so feature_importance on a packed-loaded booster is zeros.
    """
    import jax
    import jax.numpy as jnp
    from ..config import parse_params
    from ..models.tree import Tree
    from ..objectives import create_objective
    from ..serving.packed import PackedForest

    pf = PackedForest.load(path)
    params_dict = {k: v for k, v in pf.params.items() if v is not None}
    params_dict.pop("metric", None)
    booster.params = parse_params(params_dict, warn_unknown=False)
    booster.params.metric = pf.params.get("metric") or []
    booster.obj = create_objective(booster.params)
    booster.train_set = None
    booster.init_score_ = (np.asarray(pf.init_score, np.float32)
                           if pf.num_class > 1
                           else float(pf.init_score[0]))

    def per_round(a, t):
        return None if a is None else jnp.asarray(a[t])

    num_leaves = np.sum(pf.is_leaf, axis=-1).astype(np.int32)  # [T(,K)]
    booster.trees = [
        Tree(
            split_feature=jnp.asarray(pf.split_feature[t], jnp.int32),
            split_bin=jnp.asarray(pf.split_bin[t], jnp.int32),
            left=jnp.asarray(pf.left[t], jnp.int32),
            right=jnp.asarray(pf.right[t], jnp.int32),
            leaf_value=jnp.asarray(pf.leaf_value[t], jnp.float32),
            is_leaf=jnp.asarray(pf.is_leaf[t], bool),
            count=jnp.zeros(pf.split_feature[t].shape, jnp.float32),
            split_gain=jnp.zeros(pf.split_feature[t].shape, jnp.float32),
            num_leaves=jnp.asarray(num_leaves[t], jnp.int32),
            is_cat_split=per_round(pf.is_cat_split, t),
            cat_mask=per_round(pf.cat_mask, t),
        )
        for t in range(pf.num_trees)]
    booster.best_iteration = int(pf.best_iteration)
    booster.best_score = {}
    booster._valid = []
    booster._forest_cache = None
    booster._iter = len(booster.trees)
    booster._pred_train = None
    booster._bag = None
    booster._key = jax.random.PRNGKey(booster.params.seed)
    booster._feature_names = pf.feature_names
    booster._bin_mapper = pf.bin_mapper
