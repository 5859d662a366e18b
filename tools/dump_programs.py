"""What two trees of this repo lower, for a byte comparison (PR 30).

    python tools/dump_programs.py <checkout> <out dir> cpu   # ~4 min
    python tools/dump_programs.py <checkout> <out dir> v5e   # ~25 s
    cmp / diff the two out dirs (ir_index.txt, models.json, *.mlir)

``v5e``: ``Booster._fused_segment(1)`` of both benchmark cells' params,
their controls and the greedy-tail fault, lowered for a described v5e at
the cells' real shapes (kernels in), with the facts ``train.*``.  ``cpu``:
the same at 8,192 rows, then every module that ~40 small trainings lower
on four virtual CPU devices (``jax_dump_ir_to``: serial, GOSS, rf, dart,
multiclass, linear, constraints, categorical, the mesh learners, streamed,
fused CV) as (name, sha256) in order, and a digest of each model.  A
refactor that must not change a program shows it here before the chip
does.  Uses only what every tree has: Booster, Dataset, lgb.train / cv.
"""
import hashlib
import json
import os
import sys

root, out, mode = sys.argv[1], sys.argv[2], (sys.argv[3:] or ["cpu"])[0]
os.makedirs(out, exist_ok=True)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, root)

import numpy as np
import jax

jax.config.update("jax_enable_compilation_cache", False)

import lightgbm_tpu as lgb
from lightgbm_tpu.dataset import Dataset

assert os.path.realpath(lgb.__file__).startswith(os.path.realpath(root)), lgb.__file__
jax.config.update("jax_enable_compilation_cache", False)

CELLS = {
    "higgs-10m5": (10_500_096, 28),
    "epsilon-400k": (400_128, 2000),
}


def cell_params(name, extra=None):
    cfg = json.load(open(os.path.join(root, "benchmark", "configs",
                                      name + ".json")))
    return dict(cfg["params"], **(extra or {})), cfg


def problem(n, f, seed=0, classes=2):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    if classes == 2:
        y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.3 * rng.standard_normal(n)
             > 0).astype(np.float32)
    else:
        y = (np.abs(X[:, 0] * 3).astype(int) % classes).astype(np.float32)
    return X, y


def write(name, text):
    with open(os.path.join(out, name), "w") as fh:
        fh.write(text)
    print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text),
          flush=True)


if mode == "v5e":
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    real_backend = jax.default_backend
    for cell, (rows_padded, feats) in CELLS.items():
        for tag, extra in (("params", None), ("control", "control"),
                           ("greedy", {"wave_tail": "greedy"})):
            params, cfg = cell_params(cell)
            if extra == "control":
                params.update(cfg["control"]["params"])
            elif extra:
                params.update(extra)
            X, y = problem(2048, feats)
            booster = lgb.Booster(params, lgb.Dataset(X, label=y))
            ds = booster.train_set
            small = int(ds.row_mask.shape[0])
            ds.row_mask = jax.ShapeDtypeStruct((rows_padded,),
                                               ds.row_mask.dtype)
            jax.default_backend = lambda: "tpu"
            try:
                fn, args = booster._fused_segment(1)
                shapes = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        tuple(rows_padded if d == small else d
                              for d in a.shape),
                        a.dtype, sharding=one_chip), args)
                text = fn.lower(*shapes).as_text()
            finally:
                jax.default_backend = real_backend
            from lightgbm_tpu.utils import profiling
            facts = dict(profiling.snapshot()["facts"])
            write(f"v5e_{cell}_{tag}.mlir", text)
            write(f"v5e_{cell}_{tag}.facts.json",
                  json.dumps(facts, sort_keys=True, default=str))
    sys.exit(0)

# ---- CPU: the cells' params at reduced rows -------------------------------
for cell, (_, feats) in CELLS.items():
    params, cfg = cell_params(cell)
    X, y = problem(8192, feats)
    booster = lgb.Booster(params, lgb.Dataset(X, label=y))
    fn, args = booster._fused_segment(1)
    write(f"cpu_{cell}_segment1.mlir", fn.lower(*args).as_text())
    from lightgbm_tpu.utils import profiling
    write(f"cpu_{cell}_segment1.facts.json",
          json.dumps(dict(profiling.snapshot()["facts"]), sort_keys=True,
                     default=str))

# ---- CPU: every module a spread of trainings lowers -----------------------
ir_dir = os.path.join(out, "ir")
os.makedirs(ir_dir, exist_ok=True)
jax.config.update("jax_dump_ir_to", ir_dir)
jax.config.update("jax_dump_ir_modes", "stablehlo")

BASE = dict(objective="binary", num_leaves=31, learning_rate=0.1,
            max_bin=63, min_data_in_leaf=5, verbosity=-1, seed=3)
models = {}


def train(tag, params, n=6144, f=12, rounds=3, classes=2, valid=False,
          ds_kw=None, blocks=None, cat=None, group=None):
    X, y = problem(n, f, classes=classes)
    if cat is not None:
        X[:, cat] = np.floor(np.abs(X[:, cat]) * 4) % 7
    p = dict(BASE, **params)
    if blocks:
        parts = [(X[lo:lo + blocks], y[lo:lo + blocks])
                 for lo in range(0, n, blocks)]
        ds = Dataset.from_blocks(parts, params=dict(p))
    else:
        kw = dict(ds_kw or {})
        if cat is not None:
            kw["categorical_feature"] = [cat]
        if group is not None:
            kw["group"] = [group] * (n // group)
            y = np.floor(np.abs(X[:, 0]) * 2).clip(0, 4)
        ds = lgb.Dataset(X, label=y, params=dict(p), **kw)
    if valid:
        Xv, yv = problem(512, f, seed=9, classes=classes)
        b = lgb.train(p, ds, num_boost_round=rounds,
                      valid_sets=[lgb.Dataset(Xv, label=yv, reference=ds)])
    elif blocks:
        b = lgb.Booster(p, ds)
        for _ in range(rounds):
            b.update()
    else:
        b = lgb.train(p, ds, num_boost_round=rounds)
    models[tag] = hashlib.sha256(
        json.dumps(b.dump_model(), sort_keys=True).encode()).hexdigest()[:16]
    print("trained", tag, models[tag], flush=True)


train("serial_fused", {})
train("serial_valid", {}, valid=True)
train("serial_greedy", {"wave_tail": "greedy"})
train("serial_half", {"wave_tail": "half", "wave_width": 8})
train("serial_strict", {"grow_policy": "leafwise"})
train("serial_int8", {"hist_dtype": "int8"})
train("serial_bf16", {"hist_dtype": "bf16"})
train("serial_f32", {"hist_dtype": "f32"})
train("serial_bagged", {"bagging_fraction": 0.7, "bagging_freq": 1,
                        "feature_fraction": 0.6,
                        "feature_fraction_bynode": 0.8})
train("goss", {"boosting": "goss"})
train("goss_valid", {"boosting": "goss"}, valid=True)
train("rf", {"boosting": "rf", "bagging_fraction": 0.7, "bagging_freq": 1})
train("dart", {"boosting": "dart"}, rounds=4)
train("multiclass", {"objective": "multiclass", "num_class": 3}, classes=3)
train("linear", {"linear_tree": True}, ds_kw={"free_raw_data": False})
train("mono", {"monotone_constraints": [1, -1] + [0] * 10})
train("extra_trees", {"extra_trees": True})
train("interaction", {"interaction_constraints": [[0, 1, 2], [3, 4, 5]]})
train("categorical", {}, cat=3)
train("categorical_valid", {}, cat=3, valid=True)
train("regression_l1", {"objective": "regression_l1"})
train("dp", {"tree_learner": "data"})
train("dp_valid_psum", {"tree_learner": "data", "histogram_merge": "psum"},
      valid=True)
train("dp_voting", {"tree_learner": "voting"})
train("dp_goss", {"tree_learner": "data", "boosting": "goss"})
train("dp_mc", {"tree_learner": "data", "objective": "multiclass",
                "num_class": 3}, classes=3)
train("dp_linear", {"tree_learner": "data", "linear_tree": True},
      ds_kw={"free_raw_data": False})
train("dp_mono_cat", {"tree_learner": "data",
                      "monotone_constraints": [1, -1] + [0] * 10}, cat=3)
train("dp_rank", {"tree_learner": "data", "objective": "lambdarank"},
      group=32)
train("dp2", {"tree_learner": "data", "mesh_shape": "2x2"})
train("fp", {"tree_learner": "feature"})
train("fp_cat_mc", {"tree_learner": "feature", "objective": "multiclass",
                    "num_class": 3}, cat=3, classes=3)
train("stream_plain", {"stream_block_rows": 1024}, blocks=1024)
train("stream_strict", {"stream_block_rows": 1024, "num_leaves": 7},
      blocks=1024)
train("stream_goss", {"stream_block_rows": 1024, "boosting": "goss"},
      blocks=1024)
train("stream_dp", {"stream_block_rows": 512, "tree_learner": "data"},
      blocks=512)
train("stream_dp_goss", {"stream_block_rows": 512, "tree_learner": "data",
                         "boosting": "goss", "histogram_wire": "int8"},
      blocks=512)

X, y = problem(6144, 12)
res = lgb.cv(dict(BASE, metric="auc"), lgb.Dataset(X, label=y),
             num_boost_round=4, nfold=3, seed=1)
models["cv"] = hashlib.sha256(
    json.dumps({k: [float(v) for v in vs] for k, vs in res.items()},
               sort_keys=True).encode()).hexdigest()[:16]
print("cv", models["cv"], flush=True)
res = lgb.cv(dict(BASE, metric="auc", num_leaves=15, wave_width=8,
                  grow_policy="frontier"),
             lgb.Dataset(X, label=y), num_boost_round=3, nfold=2, seed=1)
models["cv_wave"] = hashlib.sha256(
    json.dumps({k: [float(v) for v in vs] for k, vs in res.items()},
               sort_keys=True).encode()).hexdigest()[:16]

jax.config.update("jax_dump_ir_to", None)
write("models.json", json.dumps(models, sort_keys=True, indent=1))

# the dump's file names carry a per-process counter; keep (order, name, hash)
import re
rows = []
for fn_ in sorted(os.listdir(ir_dir)):
    m = re.match(r"jax_ir(\d+)_(.*)", fn_)
    rows.append((int(m.group(1)) if m else -1, m.group(2) if m else fn_,
                 hashlib.sha256(open(os.path.join(ir_dir, fn_), "rb")
                                .read()).hexdigest()[:16]))
rows.sort()
write("ir_index.txt", "\n".join(f"{n} {h}" for _, n, h in rows) + "\n")
print("modules", len(rows))
