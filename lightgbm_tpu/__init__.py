"""lightgbm_tpu — a TPU-native gradient-boosted decision tree framework.

A from-scratch reimplementation of the capabilities exercised by the
`mayer79/lightGBM` reference snippets (see SURVEY.md): binned datasets,
histogram-based leaf-wise GBDT training, k-fold CV with early stopping,
grid-search sweeps with crash-safe ledgers, staged prediction, and a bagged
random-forest mode — designed TPU-first on JAX/XLA (MXU one-hot-matmul
histograms, static-shape best-first growth, psum-merged data parallelism over
a device mesh) rather than translated from LightGBM's C++/OpenMP design.

Drop-in usage mirroring the reference call sites:

    import lightgbm_tpu as lgb
    dtrain = lgb.Dataset(X, label=y)
    booster = lgb.train({"learning_rate": 0.1}, dtrain, num_boost_round=200,
                        objective="regression")          # r/gridsearchCV.R:57
    pred = booster.predict(X_test)                        # r/gridsearchCV.R:63
    fit = lgb.cv(params, dtrain, num_boost_round=1000, nfold=5,
                 early_stopping_rounds=5)                 # r/gridsearchCV.R:70
    fit.best_iter, fit.best_score   # R-binding fields, sign-flipped score
"""

__version__ = "0.1.0"

from .config import Params, parse_params
from .dataset import BinMapper, Dataset
from .callback import (
    EarlyStopException,
    early_stopping,
    log_evaluation,
    record_evaluation,
    reset_parameter,
)
from .engine import CVBooster, CVResult, cv, train
from .models.gbdt import Booster
from .models.tree import Tree
from .utils.compile_cache import compile_cache_dir as _compile_cache_dir

_compile_cache_dir()    # one compile-cache rule for train/cv/serve alike

__all__ = [
    "Booster",
    "BinMapper",
    "CVBooster",
    "CVResult",
    "Dataset",
    "EarlyStopException",
    "Params",
    "Tree",
    "cv",
    "early_stopping",
    "log_evaluation",
    "parse_params",
    "record_evaluation",
    "reset_parameter",
    "train",
]


def __getattr__(name):
    # sklearn-style estimators, plotting, and the serving runtime are
    # imported lazily to keep `import lightgbm_tpu` light.
    if name == "serving":
        from . import serving

        return serving
    if name in ("training", "faults"):
        import importlib

        return importlib.import_module(f".{name}", __name__)
    if name == "train_resumable":
        from .training import train_resumable

        return train_resumable
    if name in ("PackedForest", "PredictorRuntime", "MicroBatcher",
                "pack_booster"):
        from . import serving

        return getattr(serving, name)
    if name in ("LGBMRegressor", "LGBMClassifier", "LGBMRanker", "LGBMModel",
                "LGBMRandomForestRegressor"):
        from . import sklearn as _sk

        return getattr(_sk, name)
    if name in ("plot_importance", "plot_metric", "create_tree_digraph",
                "plot_split_value_histogram"):
        from . import plotting as _pl

        return getattr(_pl, name)
    raise AttributeError(f"module 'lightgbm_tpu' has no attribute '{name}'")
