"""Feature-parallel GBDT training over a device mesh.

TPU-native replacement for LightGBM's ``tree_learner=feature`` (upstream
``FeatureParallelTreeLearner`` + ``network/`` split exchange — SURVEY.md §2C
"feature-parallel" row): when the histogram tensor, not the row count, is the
memory/compute bottleneck (wide post-EFB data, huge ``max_bin``), shard the
FEATURE axis instead of rows:

  * every device holds ALL rows but only its slice of feature columns;
  * each shard builds histograms and scans splits for its own features only
    — per-device histogram work and memory drop by the shard count with NO
    histogram allreduce at all;
  * the per-shard best splits are combined with one tiny ``all_gather`` +
    argmax (models.tree._fp_reduce_best), and the winning shard broadcasts
    the split column with one ``psum`` (models.tree._fp_column) — the [n]
    "split bitmap" exchange of the upstream design;
  * the grown tree is replicated by construction.

Contrast with ``data_parallel``: rows sharded, full histograms psum-merged.
The two compose in principle (2-D mesh) but are exposed separately, matching
upstream's tree_learner options.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.gbdt import HyperScalars, _rebuild_objective
from ..ops.lookup import lookup_values
from ..ops.split import CatColumns
from ..models.spec import GrowSpec
from ..models.tree import grower_from_spec
from .data_parallel import jit_with_cats

FEATURE_AXIS = "feature"


# ---------------------------------------------------------------------------
# Shared BestSplit reduction helpers.
#
# Both distributed split-finding topologies end the same way: every shard
# holds the best split over SOME feature slice (feature-parallel: its owned
# column shard; data-parallel reduce-scatter/voting: the slice the histogram
# merge delivered) and the winners combine with one tiny O(D) all-gather +
# argmax — upstream's split exchange (``SyncUpGlobalBestSplit``), a few
# dozen scalars per shard instead of re-allreducing histograms.
# ---------------------------------------------------------------------------


def reduce_best_split(bs, axis_name: str, f_local: int, feature_map=None):
    """Combine per-shard ``BestSplit`` candidates into the global winner.

    ``bs.feature`` is LOCAL to this shard's feature slice.  With contiguous
    slices (feature-parallel sharding, reduce-scatter merge) the global id
    is ``feature + shard * f_local``; a voting merge scans a gathered
    candidate subset instead and passes ``feature_map`` (i32 ``[f_local]``,
    local slot -> global feature id).  All-gathering AFTER globalization
    keeps the combine one argmax over ``[D]`` gains; ties resolve to the
    lowest shard, which under contiguous ascending slices reproduces the
    serial scan's first-occurrence tie-break exactly.
    """
    from jax import lax

    shard = lax.axis_index(axis_name)
    if feature_map is None:
        gfeat = bs.feature + shard * f_local
    else:
        gfeat = feature_map[bs.feature]
    globalized = bs._replace(feature=gfeat)
    stacked = jax.tree.map(
        lambda x: lax.all_gather(x, axis_name), globalized)  # [D, ...]
    win = jnp.argmax(stacked.gain)
    return jax.tree.map(lambda x: x[win], stacked)


def broadcast_feature_column(bins_local, feat_global, axis_name: str,
                             f_local: int):
    """Fetch the GLOBAL feature column under feature sharding: only the
    owning shard has it, so it contributes the codes and a psum broadcasts
    them (the [n] bitmap exchange of upstream's feature-parallel split).
    Data-parallel shards hold every column locally and never need this.
    """
    from jax import lax

    shard = lax.axis_index(axis_name)
    local_idx = feat_global - shard * f_local
    mine = (local_idx >= 0) & (local_idx < f_local)
    col = jnp.take(bins_local, jnp.clip(local_idx, 0, f_local - 1), axis=1)
    return lax.psum(jnp.where(mine, col, 0), axis_name)


def make_feature_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D feature-sharding mesh (data_parallel.make_mesh under the
    feature axis name)."""
    from .data_parallel import make_mesh

    return make_mesh(n_devices, devices, axis_name=FEATURE_AXIS)


def pad_features(codes: np.ndarray, n_shards: int) -> np.ndarray:
    """Pad the feature axis to a shard multiple with constant-zero columns
    (masked out of every split scan by the feature mask)."""
    f = codes.shape[1]
    f_pad = -(-f // n_shards) * n_shards
    if f_pad == f:
        return codes
    return np.concatenate(
        [codes, np.zeros((codes.shape[0], f_pad - f), codes.dtype)], axis=1)


def shard_features(mesh: Mesh, bins, fmask):
    """Place [n, F] bins and [F] masks feature-sharded on the mesh."""
    col_sharding = NamedSharding(mesh, P(None, FEATURE_AXIS))
    vec_sharding = NamedSharding(mesh, P(FEATURE_AXIS))
    return (jax.device_put(bins, col_sharding),
            jax.device_put(fmask, vec_sharding))


@functools.lru_cache(maxsize=None)
def make_fp_train_step(mesh: Mesh, obj_key: tuple, spec: GrowSpec,
                       is_rf: bool = False, num_class: int = 1):
    """Build the jitted feature-parallel round step for a mesh.

    step(bins_fsharded, y, w, bag, pred, fmask_fsharded, hyper, key,
    cats) -> (tree [replicated], new_pred [replicated]); ``cats``
    (``ops.split.CatColumns``, replicated) the table's categorical columns,
    ``None`` for none.

    ``num_class > 1`` vmaps the class axis over the grower INSIDE the
    shard_map (one tree per class per round, exactly like the dp
    learner's step_mc — the per-class split-exchange all_gathers batch
    into one collective).  ``spec.cat_key`` enables categorical k-vs-rest
    splits: the global categorical columns are sliced to each shard's
    column range (:func:`_local_cats`), the winning subset mask rides the
    split exchange like any other BestSplit field, and the partition's
    category-membership test runs on the psum-broadcast global column.
    """
    obj = _rebuild_objective(obj_key)
    n_shards = mesh.shape[FEATURE_AXIS]
    # wave growth composes with the split exchange since r5 (categorical
    # datasets drop to the strict fp path inside grow_tree)
    grow = grower_from_spec(spec, fp_axis=FEATURE_AXIS)

    def step(bins_l, y, w, bag, pred, fmask_l, hyper: HyperScalars, key,
             cats):
        g, h = obj.grad_hess(pred, y, w)          # [n] or [n, K]
        cats = _local_cats(cats, bins_l.shape[1], n_shards)

        def grow_one(gc, hc, kc):
            stats = jnp.stack([gc * bag, hc * bag,
                               (bag > 0).astype(jnp.float32)], axis=-1)
            # the Booster gate guarantees bynode == 1.0 on the fp path (it
            # would sample per SHARD): no per-node threefry draw
            return grow(bins_l, stats, fmask_l, hyper.ctx(),
                        hyper.max_depth, None, kc, cats=cats)[:2]

        if num_class > 1:
            from ..models.gbdt import mc_round_update
            return mc_round_update(grow_one, g, h,
                                   jax.random.split(key, num_class), pred,
                                   hyper.learning_rate)
        tree, row_leaf = grow_one(g, h, key)
        shrink = jnp.where(is_rf, 1.0, hyper.learning_rate)
        new_pred = pred + shrink * lookup_values(row_leaf, tree.leaf_value)
        return tree, new_pred

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(None, FEATURE_AXIS), P(), P(), P(), P(),
                  P(FEATURE_AXIS), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,  # tree replicated by construction via all_gather
    )
    return jit_with_cats(sharded)


def _local_cats(cats, f_local: int, n_shards: int):
    """This feature shard's slice of the table's categorical columns
    (``ops.split.CatColumns`` over the global columns, padded here to the
    mesh's ``f_local * n_shards``); ``None`` for none."""
    if cats is None:
        return None
    shard = jax.lax.axis_index(FEATURE_AXIS)

    def local(a):
        a = jnp.pad(a, (0, f_local * n_shards - a.shape[0]))
        return jax.lax.dynamic_slice(a, (shard * f_local,), (f_local,))

    return CatColumns(*(local(a) for a in cats))


def make_mesh_2d(n_data: int, n_feature: int, devices=None) -> Mesh:
    """2-D (rows x features) mesh: Mesh([n_data, n_feature],
    ('data', 'feature')) — the composition of the dp and fp learners
    (SURVEY.md §2C parallelism rows; upstream has no direct analogue —
    its tree_learner options are mutually exclusive)."""
    from .data_parallel import DATA_AXIS

    if devices is None:
        devices = jax.devices()
    need = n_data * n_feature
    if len(devices) < need:
        raise ValueError(f"need {need} devices, the {devices[0].platform} "
                         f"backend has {len(devices)}")
    arr = np.array(devices[:need]).reshape(n_data, n_feature)
    return Mesh(arr, (DATA_AXIS, FEATURE_AXIS))


@functools.lru_cache(maxsize=None)
def make_dp_fp_train_step(mesh: Mesh, obj_key: tuple, spec: GrowSpec,
                          is_rf: bool = False):
    """2-D composed round step: each device holds an [n/dr, F/dc] block;
    per-block histograms psum-merge over the DATA axis (the dp allreduce),
    per-column-slice best splits exchange over the FEATURE axis (the fp
    allgather + argmax), and the winning split column broadcasts with one
    psum — both collectives ride the same mesh.

    step(bins_2dsharded, y, w, bag, pred [all row-sharded],
    fmask_fsharded, hyper, key, cats [replicated]) -> (tree [replicated],
    new_pred [row-sharded]).

    r10 promotes this topology to the data learner's default at D>=8,
    F>=64 (Booster._dp2_shape); wave growth composes with both
    collectives.
    """
    from .data_parallel import DATA_AXIS

    obj = _rebuild_objective(obj_key)
    grow = grower_from_spec(spec, axis_name=DATA_AXIS, fp_axis=FEATURE_AXIS)

    def step(bins_b, y_l, w_l, bag_l, pred_l, fmask_l, hyper: HyperScalars,
             key, cats):
        g, h = obj.grad_hess(pred_l, y_l, w_l)
        cats = _local_cats(cats, bins_b.shape[1], mesh.shape[FEATURE_AXIS])
        stats = jnp.stack([g * bag_l, h * bag_l,
                           (bag_l > 0).astype(jnp.float32)], axis=-1)
        # (Booster._dp2_shape admits only bynode == 1.0: no per-node draw)
        tree, row_leaf, _ = grow(bins_b, stats, fmask_l, hyper.ctx(),
                                 hyper.max_depth, None, key, cats=cats)
        shrink = jnp.where(is_rf, 1.0, hyper.learning_rate)
        new_pred = pred_l + shrink * lookup_values(row_leaf, tree.leaf_value)
        return tree, new_pred

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(P("data", FEATURE_AXIS), P("data"), P("data"), P("data"),
                  P("data"), P(FEATURE_AXIS), P(), P(), P()),
        out_specs=(P(), P("data")),
        check_vma=False,  # tree replicated via psum + all_gather
    )
    return jit_with_cats(sharded)
