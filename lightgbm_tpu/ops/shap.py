"""Exact (path-dependent) TreeSHAP feature contributions.

TPU-native replacement for LightGBM's ``predict(..., pred_contrib=True)``
(upstream ``TreeSHAP`` in src/io/tree.cpp, after Lundberg et al. 2018).
Upstream walks each tree recursively per row, EXTENDing/UNWINDing a path
polynomial — control flow XLA cannot vectorize.  This module computes the
same quantity algebraically:

For one leaf ``l`` with value ``v`` and the set of *unique* features
``P = {1..D}`` on its root path, path-dependent TreeSHAP is the Shapley
value of the product game ``g(S) = v * prod_{j in P} z_j(S)`` where
``z_j = a_j = 1{x follows every j-edge}`` when ``j in S`` and
``z_j = b_j = prod of the j-edges' cover fractions`` otherwise.  Duplicate
features multiply their fractions — exactly upstream's duplicated-feature
UNWIND.  For a product game,

    phi_i = (a_i - b_i) * sum_k q_k * k! (D-1-k)! / D!

where ``q`` are the coefficients of ``prod_{j != i} (b_j + a_j t)`` —
computable for ALL leaves and rows at once with one polynomial-build scan
(O(D)) and one synthetic-division scan per slot (O(D) each, O(D^2) total),
every step a dense ``[rows, nodes]`` tensor op.  Padding a leaf's slot list
with dummy ``a = b = 1`` factors provably leaves every phi unchanged
(merging the dummy in/out of S telescopes the permutation weights), so all
leaves share one static slot count and the whole forest is one ``lax.scan``
over stacked per-tree tables.

Contributions are reported per ORIGINAL feature: every split is on one
(EFB bundles are a training-time layout), and rows are coded unbundled.

The checksum ``sum_i phi_i + phi_bias == raw prediction`` holds exactly
(the product game telescopes); tests enforce it.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def tree_path_tables(t: Dict[str, np.ndarray], max_depth: int,
                     ) -> Dict[str, np.ndarray]:
    """Host-side per-tree path decomposition (one pass over <= M nodes).

    Args:
      t: numpy tree arrays (split_feature, split_bin, left, right,
        leaf_value, is_leaf, count, optionally is_cat_split + cat_mask).
      max_depth: pad target for the slot/edge axes (forest-wide max).

    Returns arrays (D = E = max_depth):
      leaf_w    f32 [M]     leaf_value where is_leaf else 0
      b         f32 [M, D]  per-unique-feature "zero" fractions (pad 1)
      uniq_feat i32 [M, D]  original feature ids per slot (pad -1)
      edge_col  i32 [M, E]  training column gathered per edge (pad 0)
      edge_thr  i32 [M, E]  numeric threshold (pad huge -> always follow)
      edge_dir  bool[M, E]  True = the path goes LEFT at this edge
      edge_cat  i32 [M, E]  node id for cat-split mask lookup, -1 = numeric
      slot_of   f32 [M, E, D]  one-hot edge -> unique-slot map (pad 0)
      prob      f32 [M]     P(leaf) = prod of ALL edge fractions
    """
    M = len(t["split_feature"])
    D = max(int(max_depth), 1)
    has_cat = "is_cat_split" in t and t["is_cat_split"] is not None
    internal = (~t["is_leaf"]) & (t["left"] >= 0)
    parent = np.full(M, -1, np.int64)
    is_left_child = np.zeros(M, bool)
    for i in np.flatnonzero(internal):
        parent[int(t["left"][i])] = i
        is_left_child[int(t["left"][i])] = True
        parent[int(t["right"][i])] = i

    leaf_w = np.where(t["is_leaf"], t["leaf_value"], 0.0).astype(np.float32)
    b = np.ones((M, D), np.float32)
    uniq_feat = np.full((M, D), -1, np.int64)
    edge_col = np.zeros((M, D), np.int64)
    edge_thr = np.full((M, D), np.iinfo(np.int32).max - 1, np.int64)
    edge_dir = np.ones((M, D), bool)
    edge_cat = np.full((M, D), -1, np.int64)
    slot_of = np.zeros((M, D, D), np.float32)
    prob = np.zeros(M, np.float32)

    for l in np.flatnonzero(t["is_leaf"]):
        node = int(l)
        edges = []  # leaf-ward order is fine; slots are order-insensitive
        while parent[node] >= 0:
            p = int(parent[node])
            denom = max(float(t["count"][p]), 1e-12)
            frac = min(float(t["count"][node]) / denom, 1.0)
            edges.append((p, bool(is_left_child[node]), frac))
            node = p
        if len(edges) > D:
            raise ValueError(f"path length {len(edges)} > table depth {D}")
        feat_slot: Dict[int, int] = {}
        p_leaf = 1.0
        for e, (p, went_left, frac) in enumerate(edges):
            fid = col = int(t["split_feature"][p])
            thr = int(t["split_bin"][p])
            if fid not in feat_slot:
                feat_slot[fid] = len(feat_slot)
                uniq_feat[l, feat_slot[fid]] = fid
            d = feat_slot[fid]
            b[l, d] *= frac
            p_leaf *= frac
            edge_col[l, e] = col
            edge_dir[l, e] = went_left
            if has_cat and bool(t["is_cat_split"][p]):
                edge_cat[l, e] = p
            else:
                edge_thr[l, e] = thr
            slot_of[l, e, d] = 1.0
        prob[l] = p_leaf
    return {"leaf_w": leaf_w, "b": b, "uniq_feat": uniq_feat,
            "edge_col": edge_col, "edge_thr": edge_thr,
            "edge_dir": edge_dir, "edge_cat": edge_cat,
            "slot_of": slot_of, "prob": prob}


@functools.lru_cache(maxsize=None)
def _forest_shap_fn(num_features: int, M: int, D: int):
    """Build the jitted scan over stacked tree tables -> phi [n, F+1]."""
    from math import lgamma

    # Shapley permutation weights for the padded player count D
    w = np.asarray([
        np.exp(lgamma(k + 1) + lgamma(D - k) - lgamma(D + 1))
        for k in range(D)], np.float32)

    @jax.jit
    def forest_shap(bins, cat_masks, leaf_w, b, uniq_feat, edge_col,
                    edge_thr, edge_dir, edge_cat, slot_of, prob, shrink):
        """bins i32 [n, F_train]; cat_masks bool [T, M, B] (B=1 when the
        forest has no cat splits); tables stacked on a leading [T] axis;
        shrink f32 [T].  Returns phi f32 [n, num_features + 1]."""
        n = bins.shape[0]
        wj = jnp.asarray(w)

        def body(phi, tree):
            (t_cmask, t_leaf_w, t_b, t_uniq, t_col, t_thr, t_dir, t_cat,
             t_slot, t_prob, t_shrink) = tree
            val = bins[:, t_col]                          # [n, M, E]
            go_left = val <= t_thr[None]                  # numeric edges
            if t_cmask.shape[-1] > 1:                     # cat splits exist
                go_left = jnp.where(t_cat[None] >= 0,
                                    _cat_follow(t_cmask, t_cat, val),
                                    go_left)
            follow = go_left == t_dir[None]               # [n, M, E]
            miss = 1.0 - follow.astype(jnp.float32)
            miss_d = jnp.einsum("nme,med->nmd", miss, t_slot)
            a = (miss_d < 0.5).astype(jnp.float32)        # [n, M, D]

            # polynomial prod_d (b_d + a_d t): coeffs c [n, M, D+1]
            c0 = jnp.zeros((n, M, D + 1)).at[..., 0].set(1.0)

            def poly_step(c, d):
                shifted = jnp.concatenate(
                    [jnp.zeros_like(c[..., :1]), c[..., :-1]], axis=-1)
                return (t_b[:, d][None, :, None] * c
                        + a[..., d][..., None] * shifted), None

            c, _ = lax.scan(poly_step, c0, jnp.arange(D))

            def slot_step(_, i):
                ai = a[..., i]                            # [n, M]
                bi = t_b[:, i][None, :]                   # [1, M]

                # synthetic division of c by (bi + ai t): backward
                # recurrence when the row follows (ai=1, exact), forward
                # constant division when it does not (ai=0)
                def div_step(qnext, k):
                    q_bwd = c[..., k + 1] - bi * qnext
                    q_fwd = c[..., k] / bi
                    q = jnp.where(ai > 0.5, q_bwd, q_fwd)
                    return q, q * wj[k]

                _, terms = lax.scan(div_step, jnp.zeros((n, M)),
                                    jnp.arange(D - 1, -1, -1))
                return None, (ai - bi) * jnp.sum(terms, axis=0)

            _, slot_phi = lax.scan(slot_step, None, jnp.arange(D))
            slot_phi = jnp.moveaxis(slot_phi, 0, -1)      # [n, M, D]

            contrib = slot_phi * t_leaf_w[None, :, None]
            # pads (uniq = -1) have a = b = 1 -> exactly zero; dump on bias.
            # One-hot einsum instead of a 2-D-indexed scatter: feeds the MXU
            # and sidesteps XLA's scatter expander.
            idx = jnp.where(t_uniq >= 0, t_uniq, num_features)
            onehot = jax.nn.one_hot(idx, num_features + 1)   # [M, D, F+1]
            phi_t = jnp.einsum("nmd,mdf->nf", contrib, onehot)
            phi_t = phi_t.at[:, num_features].add(
                jnp.sum(t_leaf_w * t_prob))               # E[f] bias
            return phi + t_shrink * phi_t, None

        phi0 = jnp.zeros((n, num_features + 1))
        phi, _ = lax.scan(body, phi0, (cat_masks, leaf_w, b, uniq_feat,
                                       edge_col, edge_thr, edge_dir,
                                       edge_cat, slot_of, prob, shrink))
        return phi

    return forest_shap


def _cat_follow(cmask: jnp.ndarray, edge_cat: jnp.ndarray,
                val: jnp.ndarray) -> jnp.ndarray:
    """cmask bool [M, B], edge_cat i32 [M, E], val i32 [n, M, E] ->
    bool [n, M, E]: does the bin code fall in the edge node's LEFT set.

    Pure broadcast gather — no [n, M, E, B] materialization (the per-row
    repeat would be ~32 GB on 100k-row categorical predicts)."""
    node = jnp.maximum(edge_cat, 0)                       # [M, E]
    return cmask[node[None], val]                         # [n, M, E]


def _tree_depth(t: Dict[str, np.ndarray]) -> int:
    M = len(t["split_feature"])
    internal = (~t["is_leaf"]) & (t["left"] >= 0)
    depth = np.zeros(M, np.int64)
    # children are created after parents, so one forward sweep resolves
    # every depth
    for i in np.flatnonzero(internal):
        depth[int(t["left"][i])] = depth[i] + 1
        depth[int(t["right"][i])] = depth[i] + 1
    leaves = np.flatnonzero(t["is_leaf"])
    return int(depth[leaves].max()) if len(leaves) else 1


def forest_pred_contrib(trees: List[Dict[str, np.ndarray]],
                        bins: jnp.ndarray, num_features: int,
                        shrink: np.ndarray) -> np.ndarray:
    """SHAP contributions for a list of numpy-ified trees.

    Args:
      trees: dicts of numpy tree arrays (same capacity M across the list).
      bins: u8/i32 [n, F] binned rows, the original features' codes.
      num_features: width of the contribution matrix (ORIGINAL features).
      shrink: f32 [T] per-tree multiplier.

    Returns f32 [n, num_features + 1]; last column is the expected value.
    """
    if not trees:
        return np.zeros((bins.shape[0], num_features + 1), np.float32)
    depth = max(max(_tree_depth(t) for t in trees), 1)
    tabs = [tree_path_tables(t, depth) for t in trees]
    has_cat = any("is_cat_split" in t and t["is_cat_split"] is not None
                  and np.any(t["is_cat_split"]) for t in trees)
    if has_cat:
        cat_masks = np.stack([np.asarray(t["cat_mask"], bool)
                              for t in trees])
    else:
        M = len(trees[0]["split_feature"])
        cat_masks = np.zeros((len(trees), M, 1), bool)
    stacked = {k: jnp.asarray(np.stack([tb[k] for tb in tabs]))
               for k in tabs[0]}
    fn = _forest_shap_fn(num_features, tabs[0]["b"].shape[0], depth)
    phi = fn(jnp.asarray(bins).astype(jnp.int32), jnp.asarray(cat_masks),
             stacked["leaf_w"], stacked["b"], stacked["uniq_feat"],
             stacked["edge_col"], stacked["edge_thr"], stacked["edge_dir"],
             stacked["edge_cat"], stacked["slot_of"], stacked["prob"],
             jnp.asarray(shrink, jnp.float32))
    return np.array(phi)  # writable copy (callers add the init score)
