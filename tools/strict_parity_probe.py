"""Probe: does TRUE strict best-first order close the parity AUC gap?

PERF_HISTORY.md r4 located the remaining 8.1e-4 parity gap in "grower semantics"
(half-tail residual departure from strict order + tie-breaks) but could
not isolate the strict term because strict+pallas crashes the worker.
The crash follows the PALLAS kernel (PERF_HISTORY.md fault pattern), and the
parity preset already pins hist_impl=jnp — so strict on the jnp path is
measurable.  This probe times it, then measures the paired AUC gap.

Usage: python tools/strict_parity_probe.py [n_rows] [n_rounds] [tail]
  tail in {leafwise, half, greedy}
"""
import sys
import time

import numpy as np


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 100
    tail = sys.argv[3] if len(sys.argv) > 3 else "leafwise"
    impl = sys.argv[4] if len(sys.argv) > 4 else "jnp"

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.datasets import make_higgs_like
    from sklearn.metrics import roc_auc_score

    X, y = make_higgs_like(n)
    Xv, yv = make_higgs_like(1_000_000, seed=9)

    params = {"objective": "binary", "num_leaves": 127,
              "learning_rate": 0.1, "verbosity": -1, "min_data_in_leaf": 20,
              "hist_dtype": "f32", "hist_impl": impl,
              "fused_segment_rounds": 5}
    if tail == "leafwise":
        params["grow_policy"] = "leafwise"
    else:
        params["wave_tail"] = tail

    ds = lgb.Dataset(X, label=y)
    ds.construct()
    b = lgb.Booster(params, ds)

    # timing estimate first: 2 rounds (compile) then 2 more (steady)
    t0 = time.perf_counter()
    b.update_many(2)
    _ = np.asarray(b._pred_train[:4])
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    b.update_many(2)
    _ = np.asarray(b._pred_train[:4])
    t_steady = time.perf_counter() - t0
    print(f"[probe] compile+2r {t_compile:.1f}s, steady 2r {t_steady:.1f}s "
          f"-> est {n_rounds}r = {t_steady / 2 * n_rounds:.0f}s", flush=True)

    b.update_many(n_rounds - 4)
    _ = np.asarray(b._pred_train[:4])
    p_tpu = np.concatenate([
        np.asarray(b.predict(Xv[i:i + 250_000], num_iteration=n_rounds))
        for i in range(0, len(Xv), 250_000)])
    auc_tpu = float(roc_auc_score(yv, p_tpu))
    print(f"[probe] tail={tail} n={n} rounds={n_rounds} "
          f"auc_tpu={auc_tpu:.6f}", flush=True)

    from sklearn.ensemble import HistGradientBoostingClassifier
    orc = HistGradientBoostingClassifier(
        max_iter=n_rounds, max_leaf_nodes=127, learning_rate=0.1,
        min_samples_leaf=20, max_bins=255, early_stopping=False,
        validation_fraction=None)
    orc.fit(X, y)
    p_cpu = orc.predict_proba(Xv)[:, 1]
    auc_cpu = float(roc_auc_score(yv, p_cpu))

    rng = np.random.default_rng(0)
    diffs = []
    for _ in range(20):
        idx = rng.integers(0, len(yv), len(yv))
        yb = yv[idx]
        if yb.min() == yb.max():
            continue
        diffs.append(roc_auc_score(yb, p_cpu[idx])
                     - roc_auc_score(yb, p_tpu[idx]))
    gap = auc_cpu - auc_tpu
    se = float(np.std(diffs, ddof=1))
    print(f"RESULT tail={tail} n={n} rounds={n_rounds} "
          f"auc_tpu={auc_tpu:.6f} auc_cpu={auc_cpu:.6f} "
          f"gap={gap:.6f} se={se:.6f}", flush=True)


if __name__ == "__main__":
    main()
