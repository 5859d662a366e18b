"""Decompose the MSLR LambdaRank round: lambdas vs histograms vs rest.

VERDICT r3 #6: LambdaRank must beat the pointwise CPU oracle >=2x on
throughput.  Slope timing (t(k2)-t(k1))/(k2-k1) over fused multi-round
dispatches cancels dispatch latency and device->host fetch, and the
lambdarank-minus-regression difference isolates the pairwise lambda pass
inside the real fused program.

Two shapes: the uniform 1,000 queries x 100 documents (one block, the
reshape shortcut) and the same number of documents in MSLR-shaped
variable-length groups (``make_mslr_like``: queries packed by length into
a dozen blocks, gathers in and out).  ``--lambda-pass`` also times the
jitted lambda pass ALONE at the published size (2,270,296 documents in
18,919 queries of 1 to 1,251), median of 5, with the layout's counts.
"""
import time

import numpy as np


def slope_rounds(b, k1=4, k2=14):
    import numpy as np

    def run(k):
        b.update_many(k)
        _ = np.asarray(b._pred_train[:4])
        t0 = time.perf_counter()
        b.update_many(k)
        _ = np.asarray(b._pred_train[:4])
        return time.perf_counter() - t0

    t1, t2 = run(k1), run(k2)
    return max((t2 - t1) / (k2 - k1), 1e-9)


def lambda_pass_alone(rows=2_270_296, queries=18_919, seed=5):
    """The jitted lambda pass alone at the published size: milliseconds
    (median of 5) and the packed layout's own counts."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.config import parse_params
    from lightgbm_tpu.models.gbdt import (_group_grad_fn,
                                          _objective_static_key)
    from lightgbm_tpu.ranking import LambdaRank
    from lightgbm_tpu.utils.datasets import mslr_query_sizes

    rng = np.random.default_rng(seed)
    sizes = mslr_query_sizes(rows, queries, rng)
    y = np.searchsorted(np.cumsum([0.52, 0.32, 0.13, 0.02]),
                        rng.random(rows)).astype(np.float32)
    obj = LambdaRank(parse_params(dict(objective="lambdarank")))
    t0 = time.perf_counter()
    obj.set_group(sizes, y, rows)
    pack_s = time.perf_counter() - t0
    fn = _group_grad_fn(_objective_static_key(obj, obj.params))
    args = (jnp.asarray(rng.normal(0, 0.3, rows), jnp.float32), None,
            jnp.ones(rows, jnp.float32), obj.groups)
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        took.append(time.perf_counter() - t0)
    f = obj.facts
    print(f"  lambda pass alone, {rows:,} documents in {queries:,} queries: "
          f"{np.median(took) * 1e3:.2f} ms; pack {pack_s:.2f} s; "
          f"{len(f['rank_blocks'])} blocks, pad ratio "
          f"{f['rank_doc_slots'] / rows:.3f}, pair slots / pairs visited "
          f"{f['rank_pair_slots'] / f['rank_pairs_visited']:.3f}",
          flush=True)


def main():
    import sys

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.datasets import make_mslr_like

    if "--lambda-pass" in sys.argv[1:]:
        lambda_pass_alone()
    rng = np.random.default_rng(5)
    n_queries, docs_per_q, n_features = 1000, 100, 136
    n = n_queries * docs_per_q
    for shape in ("uniform 1,000 x 100", "MSLR-shaped groups"):
        if shape.startswith("uniform"):
            X = rng.normal(0, 1, (n, n_features)).astype(np.float32)
            y = rng.integers(0, 5, n).astype(np.float32)
            sizes = np.full(n_queries, docs_per_q)
        else:
            X, y, sizes = make_mslr_like(n, n_features, n * 18_919
                                         // 2_270_296, seed=5)
        print(f"{shape}: {len(sizes):,} queries of {sizes.min()} to "
              f"{sizes.max()} documents", flush=True)
        # the uniform case counts every pair, as it always has; the
        # MSLR-shaped one LightGBM's default window
        profile_shape(lgb, X, y, sizes,
                      docs_per_q if shape.startswith("uniform") else 30)


def profile_shape(lgb, X, y, sizes, truncation):
    n = len(y)
    base = dict(num_leaves=63, learning_rate=0.1, min_data_in_leaf=20,
                verbosity=-1, hist_dtype="bf16", fused_segment_rounds=14)

    ds = lgb.Dataset(X, label=y, group=sizes)
    ds.construct()

    for label, extra in [
        ("lambdarank", dict(objective="lambdarank",
                            lambdarank_truncation_level=truncation)),
        ("regression (same data)", dict(objective="regression")),
        ("lambdarank greedy-tail", dict(objective="lambdarank",
                                        lambdarank_truncation_level=truncation,
                                        wave_tail="greedy")),
        ("regression greedy-tail", dict(objective="regression",
                                        wave_tail="greedy")),
    ]:
        params = dict(base)
        params.update(extra)
        b = lgb.Booster(params, ds)
        s = slope_rounds(b)
        print(f"  {label:>26}: {s * 1e3:8.2f} ms/round "
              f"({n / s:,.0f} rows/s)", flush=True)


if __name__ == "__main__":
    main()
