"""Data-parallel GBDT training over a device mesh.

The TPU-native replacement for LightGBM's data-parallel tree learner
(upstream ``tree_learner=data`` + ``network/`` socket/MPI allreduce, and the
CUDA/NCCL path its "GPU support" refers to — SURVEY.md §2C, §5):

  * rows are sharded over a 1-D ``Mesh(('data',))`` (ICI within a slice,
    DCN across slices — same mesh abstraction either way);
  * each shard builds histograms for its rows only;
  * per-shard partials combine through ``ops.histogram.histogram_merge``
    (``merge_mode``): the r0 baseline is one full ``psum`` (split finding
    then redundant-but-identical per shard), while ``reduce_scatter``
    delivers each shard only its ``F/D`` feature slice — split finding is
    scanned over the slice and the per-shard ``BestSplit`` winners combine
    with a tiny O(D) all-gather + argmax (upstream's Reduce-Scatter
    data-parallel learner; 1/D the comm bytes, serial-parity-exact trees);
  * ``merge_mode="voting"`` adds the PV-Tree voting-parallel topology:
    shards nominate local top-k features and only the voted candidate
    union's columns are merged (approximate, cheapest — ``tree_learner=
    voting``);
  * either way the grown tree is replicated by construction and no
    broadcast step is needed.

Scaling note (SURVEY.md §5 "long-context"): a GBDT has no sequence axis; the
scale axis is rows (this module) and features/bins.  Upstream's ``feature``
learner distributes columns instead (see ``feature_parallel``); ``data`` and
``voting`` route HERE with distinct merge topologies since r9 (they
previously all aliased the same full ``psum`` — see README and
``analysis.budgets`` for the per-round comm-bytes model).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.gbdt import HyperScalars, _rebuild_objective
from ..models.spec import GrowSpec
from ..models.tree import grower_from_spec
from ..ops.lookup import lookup_values

DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None,
              devices=None, axis_name: str = DATA_AXIS) -> Mesh:
    """1-D row-sharding mesh over the first ``n_devices`` devices of the
    default backend.

    Asking for more devices than that backend has raises: a mesh is never
    built from another platform's devices, so "four chips" cannot quietly
    mean four host threads.  The virtual-mesh tests and dry runs get
    their devices by making the CPU the default backend
    (``JAX_PLATFORMS=cpu`` +
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if len(devices) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, the {devices[0].platform} "
                f"backend has {len(devices)}")
        devices = devices[:n_devices]
    import numpy as np

    return Mesh(np.array(devices), (axis_name,))


def shard_rows(mesh: Mesh, *arrays):
    """Place row-leading arrays row-sharded on the mesh (rows must divide
    evenly — Dataset pads to ROW_PAD_MULTIPLE=256 which covers 2/4/8-device
    meshes)."""
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out if len(out) > 1 else out[0]


def jit_with_cats(sharded):
    """``sharded`` (a mesh step whose last operand is the table's
    categorical columns, ``ops.split.CatColumns``) jitted with that operand
    given by keyword: ``cats=None`` for a table with none."""
    @functools.wraps(sharded)       # its name and operands name the program
    def step(*args, cats=None):
        return sharded(*args, cats)

    return jax.jit(step)


def _mesh_grower(spec: GrowSpec, mesh: Mesh, merge_mode: str,
                 voting_k: int, wire_dtype: str, merge_chunks: int, **kw):
    """``grower_from_spec`` placed on the row mesh: histograms merge over
    ``DATA_AXIS`` as ``merge_mode`` says.  The row-mesh learners have never
    taken the static bynode skip (every shard draws the same per-node mask
    from the shared key), so their programs keep the draw."""
    return grower_from_spec(
        dataclasses.replace(spec, bynode_off=False), axis_name=DATA_AXIS,
        hist_merge=merge_mode, n_shards=mesh.shape[DATA_AXIS],
        voting_k=voting_k, hist_wire=wire_dtype, merge_chunks=merge_chunks,
        **kw)


@functools.lru_cache(maxsize=None)
def make_dp_train_step(mesh: Mesh, obj_key: tuple, spec: GrowSpec,
                       is_rf: bool = False, goss_k_shard=None,
                       num_class: int = 1,
                       merge_mode: str = "psum", voting_k: int = 0,
                       wire_dtype: str = "f32", merge_chunks: int = 4):
    """Build the jitted data-parallel round step for a mesh.

    Returns step(bins, y, w, bag, pred, feature_mask, hyper, key, cats) ->
    (tree [replicated], new_pred [row-sharded]); ``cats``
    (``ops.split.CatColumns``, replicated) the table's categorical columns,
    ``None`` for none.

    The entire per-round body — gradients, bagged stats, the full best-first
    growth loop with merged histograms, and the train-score update — runs
    inside ONE ``shard_map``-ed program per round.

    ``goss_k_shard``: static PER-SHARD (k_top, k_other) enabling GOSS —
    each shard compacts its own rows (matching upstream's data-parallel
    GOSS, which samples per machine) and the compacted shards' histograms
    merge as usual.

    ``merge_mode``: histogram merge topology — ``"psum"`` |
    ``"reduce_scatter"`` | ``"reduce_scatter_ring"`` |
    ``"reduce_scatter_pipelined"`` | ``"voting"``
    (``voting_k`` = per-shard ballot size); see the module docstring and
    ``models.tree.grow_tree(hist_merge=...)``.  ``wire_dtype`` /
    ``merge_chunks`` configure the r10 pipelined ring (per-hop wire
    compression and the sub-chunk count whose hops overlap the per-chunk
    split scans); both are inert outside the ring modes.
    """
    obj = _rebuild_objective(obj_key)
    # categorical k-vs-rest splits work unchanged under the mesh: the scan
    # runs on psum-MERGED histograms (replicated, so every shard picks the
    # same subset mask) and the partition gathers per-shard rows.
    # The class axis vmaps over the grower: no in-kernel partition there
    grow = _mesh_grower(spec, mesh, merge_mode, voting_k, wire_dtype,
                        merge_chunks, fuse_partition=num_class == 1)

    def step_mc(bins, y, w, bag, pred, feature_mask, hyper: HyperScalars,
                key, cats):
        """Multiclass: one tree per class per round, the class axis vmapped
        over the grower INSIDE the shard_map — the per-class histogram
        psums batch into one collective.  GOSS (when requested) becomes
        per-shard row re-weighting keyed by the summed |grad| across
        classes (upstream's per-machine sampling)."""
        g, h = obj.grad_hess(pred, y, w)                  # [n_shard, K]
        if goss_k_shard is not None:
            from ..ops.sampling import goss_weights
            from jax import lax

            skey = jax.random.fold_in(
                jax.random.fold_in(key, 0x7FFFFFFF),
                lax.axis_index(DATA_AXIS))
            bag = goss_weights(skey, jnp.sum(jnp.abs(g), axis=-1), bag,
                               hyper.top_rate, hyper.other_rate,
                               jnp.sum(bag))

        def grow_one(gc, hc, kc):
            stats = jnp.stack([gc * bag, hc * bag,
                               (bag > 0).astype(jnp.float32)], axis=-1)
            return grow(bins, stats, feature_mask, hyper.ctx(),
                        hyper.max_depth, hyper.feature_fraction_bynode,
                        kc, cats=cats)[:2]

        from ..models.gbdt import mc_round_update
        return mc_round_update(grow_one, g, h,
                               jax.random.split(key, num_class), pred,
                               hyper.learning_rate)

    def step(bins, y, w, bag, pred, feature_mask, hyper: HyperScalars, key,
             cats):
        g, h = obj.grad_hess(pred, y, w)
        if goss_k_shard is not None:
            from ..models.gbdt import _goss_compact_round
            from jax import lax

            # ONLY the row-sampling stream differs per shard (upstream's
            # per-machine sampling); the tree-growth key must stay SHARED
            # or per-node feature sampling would pick different masks per
            # shard and the "replicated" tree would silently diverge
            sample_key = jax.random.fold_in(
                key, lax.axis_index(DATA_AXIS))
            tree, new_pred, _ = _goss_compact_round(
                grow, bins, y, w, bag, pred, feature_mask, hyper, key,
                g, h, goss_k_shard, None, sample_key=sample_key, cats=cats)
            return tree, new_pred
        stats = jnp.stack([g * bag, h * bag, bag], axis=-1)
        tree, row_leaf, _ = grow(bins, stats, feature_mask, hyper.ctx(),
                                 hyper.max_depth,
                                 hyper.feature_fraction_bynode, key,
                                 cats=cats)
        shrink = jnp.where(is_rf, 1.0, hyper.learning_rate)
        new_pred = pred + shrink * lookup_values(row_leaf, tree.leaf_value)
        return tree, new_pred

    sharded = shard_map(
        step_mc if num_class > 1 else step,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P(DATA_AXIS)),
        check_vma=False,  # tree is replicated by construction via psum
    )
    return jit_with_cats(sharded)


@functools.lru_cache(maxsize=None)
def make_dp_linear_train_step(mesh: Mesh, obj_key: tuple, spec: GrowSpec,
                              linear_k: int = 8,
                              merge_mode: str = "psum", voting_k: int = 0,
                              wire_dtype: str = "f32",
                              merge_chunks: int = 4):
    """Data-parallel ``linear_tree`` round (r5 breadth): constant-leaf
    growth shards rows with psum-merged histograms as usual, then every
    leaf's ridge system accumulates per shard and merges with ONE psum of
    the [capacity, K+1, K+1] Gram tensors (tree.fit_linear_leaves
    axis_name) — the solve is replicated, so coefficients match serial
    training exactly (tested vs serial on the CPU mesh).

    step(bins_sh, y_sh, w_sh, bag_sh, pred_sh, xraw_sh, fmask, hyper,
    key, cats) -> (tree [replicated], new_pred [row-sharded]).
    """
    from ..models.tree import fit_linear_leaves

    obj = _rebuild_objective(obj_key)
    grow = _mesh_grower(spec, mesh, merge_mode, voting_k, wire_dtype,
                        merge_chunks, fuse_partition=True)

    def step(bins, y, w, bag, pred, xraw, feature_mask,
             hyper: HyperScalars, key, cats):
        g, h = obj.grad_hess(pred, y, w)
        stats = jnp.stack([g * bag, h * bag, bag], axis=-1)
        tree, row_leaf, _ = grow(bins, stats, feature_mask, hyper.ctx(),
                                 hyper.max_depth,
                                 hyper.feature_fraction_bynode, key,
                                 cats=cats)
        tree, delta = fit_linear_leaves(
            tree, row_leaf, xraw, g, h, bag, hyper.linear_lambda,
            linear_k, spec.row_chunk, axis_name=DATA_AXIS)
        new_pred = pred + hyper.learning_rate * delta
        return tree, new_pred

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P(DATA_AXIS)),
        check_vma=False,  # tree replicated by construction via psum
    )
    return jit_with_cats(sharded)


@functools.lru_cache(maxsize=None)
def make_dp_grow_step(mesh: Mesh, spec: GrowSpec,
                      merge_mode: str = "psum", voting_k: int = 0,
                      wire_dtype: str = "f32", merge_chunks: int = 4):
    """Data-parallel growth from PRECOMPUTED per-row stats.

    The ranking path: LambdaRank gradients need whole queries (the packed
    pairwise pass), so they are computed replicated — cheap next to the
    histogram work — and only the grower runs sharded with psum-merged
    histograms (upstream's data-parallel ranking keeps whole queries per
    machine; here the query pass is replicated instead, same result).

    step(bins_sharded, stats_sharded, feature_mask, hyper, key, cats) ->
    (tree [replicated], row_leaf [row-sharded]) — callers update train
    predictions with one ``leaf_value[row_leaf]`` gather instead of
    re-traversing the tree (code-review r2).
    """
    grow = _mesh_grower(spec, mesh, merge_mode, voting_k, wire_dtype,
                        merge_chunks, fuse_partition=True)

    def step(bins, stats, feature_mask, hyper: HyperScalars, key, cats):
        return grow(bins, stats, feature_mask, hyper.ctx(), hyper.max_depth,
                    hyper.feature_fraction_bynode, key, cats=cats)[:2]

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P(DATA_AXIS)),
        check_vma=False,  # tree replicated by construction via psum
    )
    return jit_with_cats(sharded)
