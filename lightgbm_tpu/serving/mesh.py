"""Pod-scale serving mesh: shard the traffic, not just the training.

The r6→r12 runtime stack (bucket ladder, MicroBatcher, ModelBank) is a
single-device affair while training has been multi-chip since r9/r10 —
ROADMAP item 1's gating gap.  This module closes it with two sharding
routes over the same 1-D device mesh the training learners use:

* **dp — data-parallel replication.**  The PackedForest is replicated on
  every device (``shard_map`` closes over the resident arrays, XLA
  replicates them with the program) and the padded bucket is row-sharded
  ``P(axis)``.  There are NO collectives: every row's traversal is the
  exact single-device program over its shard, so dp output is
  **bit-identical** to the single-device route at f32 — the property the
  chaos tests pin with ``np.array_equal``.  Near-linear QPS: D devices
  each traverse ``bucket/D`` rows.
* **tp — tree-parallel splitting.**  The forest's TREE axis is sharded
  ``P(axis)`` (padded to a device multiple with zero trees that
  self-loop at node 0), every device traverses the FULL batch over its
  tree slice, and the per-shard raw margins combine with one
  ``lax.psum``.  Latency for deep forests on small batches: traversal
  depth stays, but each device walks T/D trees.  The psum reorders the
  f32 tree-sum reduction, so tp is parity-gated within a few ulp rather
  than bit-identical (mirrors the r9 ``psum`` merge-mode contract).
* **auto route chooser** — mirrors the r10 ``mesh_shape=auto``
  promotion: small buckets on big forests go tp (the batch can't feed D
  devices but the tree axis can); buckets that give every device a full
  ``DP_MIN_ROWS_PER_SHARD``-row tile go dp; everything else stays
  single.  The chooser is a pure
  function of (bucket, num_trees, D), so ``warm()`` can precompile
  exactly the programs traffic will resolve — deterministic routing is
  what makes zero-traffic-path-compiles provable.

The bucket ladder composes unchanged: routes are a third compile-cache
key component ``(bucket, raw_score, route)``, padding/masking semantics
are identical (dp shards the mask with the rows; tp applies it on the
replicated psum result), and ``num_iteration`` stays a traced argument
in every route (tp converts the global truncation window into local
tree coordinates with a traced per-shard offset — no recompiles).

Device counts are powers of two, matching the power-of-two bucket
ladder: every bucket >= D divides evenly, so dp needs no ragged-shard
handling (ragged TAILS were already padded into the bucket upstream).
"""

from __future__ import annotations

from typing import Optional

SERVE_AXIS = "serve"
SHARD_POLICIES = ("auto", "dp", "tp")
ROUTES = ("single", "dp", "tp")

# auto-route thresholds (see choose_route): buckets at or below the
# ceiling are latency-bound (the MXU is nowhere near fed) -> tp when the
# forest is deep enough to split; above it, throughput-bound -> dp
TP_BUCKET_CEILING = 64
TP_MIN_TREES_PER_DEVICE = 2

# dp engages only when every shard holds a full row tile.  Below this the
# backend is free to re-tile the per-row tree reduction for the skinny
# shape (measured on the CPU dryrun backend: <16-row programs flip the
# vectorization axis and drift a few ulp from the monolithic program),
# which would silently void the dp bit-identity contract; and the
# dispatch-overhead model says sharding sub-tile buckets loses to the
# fixed fan-out cost anyway.  The floor is part of choose_route, so
# warm() and dispatch agree and the contract stays provable.
DP_MIN_ROWS_PER_SHARD = 16


class ServingMesh:
    """A 1-D serving mesh over the first ``devices`` chips.

    Thin wrapper over ``parallel.data_parallel.make_mesh`` with its own
    axis name, so serving programs and training programs never collide
    on axis identifiers when both run in one process.
    """

    def __init__(self, devices: int, axis_name: str = SERVE_AXIS):
        devices = int(devices)
        if devices < 1 or (devices & (devices - 1)):
            raise ValueError(
                f"mesh_devices must be a power of two >= 1, got {devices}"
                " (the power-of-two bucket ladder is what guarantees dp"
                " shards divide evenly)")
        from ..parallel.data_parallel import make_mesh

        self.devices = devices
        self.axis_name = axis_name
        self.mesh = make_mesh(devices, axis_name=axis_name)

    def __repr__(self) -> str:
        return f"ServingMesh(devices={self.devices})"


def choose_route(policy: str, bucket: int, num_trees: int,
                 n_devices: int) -> str:
    """Deterministic dispatch route for one bucket — ``single`` | ``dp``
    | ``tp``.

    Pure function of the operating point, shared verbatim by dispatch
    AND ``warm()``: warming the chosen route per bucket therefore covers
    every program traffic can resolve.

    * ``policy="dp"``: dp whenever every device gets a full
      ``DP_MIN_ROWS_PER_SHARD``-row tile, else single (sub-tile shards
      lose to dispatch overhead AND void the bit-identity contract).
    * ``policy="tp"``: tp whenever the forest has a tree per device,
      else single.
    * ``policy="auto"``: tp for small buckets over splittable forests
      (latency route), dp when the bucket feeds every device a full
      tile (throughput route), single otherwise.
    """
    if policy not in SHARD_POLICIES:
        raise ValueError(
            f"shard_policy must be one of {SHARD_POLICIES}, got {policy!r}")
    if n_devices <= 1:
        return "single"
    dp_ok = bucket >= n_devices * DP_MIN_ROWS_PER_SHARD
    if policy == "dp":
        return "dp" if dp_ok else "single"
    if policy == "tp":
        return "tp" if num_trees >= n_devices else "single"
    if (bucket <= TP_BUCKET_CEILING
            and num_trees >= TP_MIN_TREES_PER_DEVICE * n_devices):
        return "tp"
    if dp_ok:
        return "dp"
    return "single"


def dp_shard(smesh: ServingMesh, fn, check_vma: bool = True):
    """Row-shard a single-device predict program ``fn(bins, mask,
    num_it)`` across the mesh.

    ``bins``/``mask`` shard on rows, ``num_it`` is replicated, the
    output shards on rows (axis 0 — covers both ``[n]`` and ``[n, K]``
    multiclass outputs).  The body contains no collectives and no
    cross-row arithmetic (traversal, the rf adjust, and the objective
    transform are all row-elementwise), so each row's result is computed
    by the identical instruction sequence the single-device program
    runs: bit-identity at f32 is by construction, not by tolerance.

    ``check_vma=False`` is required when the body contains a
    ``pallas_call`` (the fused r18 path): shard_map's replication
    checker has no rule for custom kernels.  The contract is unchanged
    — the kernel body is still row-elementwise per shard.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    ax = smesh.axis_name
    return shard_map(fn, mesh=smesh.mesh,
                     in_specs=(P(ax), P(ax), P()),
                     out_specs=P(ax), check_vma=check_vma)


def pad_forest_for_tp(forest, leaf_scale, n_devices: int):
    """Pad the forest's tree axis to a device multiple.

    Zero trees are inert: node 0 self-loops (``is_leaf=False``,
    ``left=right=0``) with ``leaf_value=0``, and the traced round mask
    excludes their global indices anyway (``num_iteration`` never
    exceeds the REAL tree count).  ``leaf_scale`` pads with 1.0.
    Returns ``(forest, leaf_scale, trees_per_device)``.
    """
    import jax
    import jax.numpy as jnp

    t = forest.leaf_value.shape[0]
    t_pad = -(-t // n_devices) * n_devices
    pad = t_pad - t
    if pad:
        forest = jax.tree.map(
            lambda a: jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]), forest)
        if leaf_scale is not None:
            leaf_scale = jnp.concatenate(
                [leaf_scale,
                 jnp.ones((pad,) + leaf_scale.shape[1:],
                          leaf_scale.dtype)])
    return forest, leaf_scale, t_pad // n_devices


def pad_soa_for_tp(soa, n_devices: int):
    """Pad a ``ForestSoA``'s tree axis for tree-parallel sharding.

    The target is a multiple of (sublane chunk x devices): each shard's
    slice must itself be a legal fused-kernel operand, so trees pad to
    ``lcm(chunk, chunk * D) = chunk * D``.  Padded trees are inert
    exactly like the packer's own padding — every node self-loops as a
    zero leaf, scale pads with 1.0, and the traced round mask excludes
    their global indices anyway.  Returns ``(soa, trees_per_device)``.
    """
    import jax.numpy as jnp

    from ..ops.predict import soa_tree_chunk

    t, m = soa.split_feature.shape
    mult = soa_tree_chunk(soa) * n_devices
    t_pad = -(-t // mult) * mult
    pad = t_pad - t
    if pad:
        self_loop = jnp.broadcast_to(jnp.arange(m), (pad, m))

        def pad_field(a, name):
            if name == "scale":
                return jnp.concatenate([a, jnp.ones(pad, a.dtype)])
            if name in ("left", "right"):
                return jnp.concatenate([a, self_loop.astype(a.dtype)])
            if name == "is_leaf":
                return jnp.concatenate(
                    [a, jnp.ones((pad, m), a.dtype)])
            return jnp.concatenate(
                [a, jnp.zeros((pad, m), a.dtype)])

        soa = type(soa)(*(pad_field(a, name) for name, a
                          in zip(soa._fields, soa)))
    return soa, t_pad // n_devices


def tp_raw_margins_fused(smesh: ServingMesh, soas, trees_per_device: int,
                         shrink, depth_cap: int, num_class: int = 1):
    """Fused-path tree-parallel raw margins: shard every per-class
    ``ForestSoA`` on its tree axis, run the mega-kernel per shard, and
    ``psum`` the per-shard raw sums.

    Same contract as :func:`tp_raw_margins` (replicated ``[n]`` /
    ``[n, K]`` output without init_score; traced global truncation
    window mapped into local tree coordinates via ``start_iteration =
    -axis_index * trees_per_device``), but each shard traverses its
    quantized SoA slice directly — no widening, per-shard scale folded
    into the kernel's round mask.  ``soas`` must already be padded with
    :func:`pad_soa_for_tp`.
    """
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.predict import predict_forest_pallas

    ax = smesh.axis_name

    def body(soas_loc, bins, num_it):
        offset = lax.axis_index(ax) * trees_per_device
        start = -jnp.asarray(offset, jnp.int32)
        cols = [predict_forest_pallas(
            soas_loc[c], bins, shrink, 0.0, num_it, depth_cap,
            start_iteration=start) for c in range(num_class)]
        local = jnp.stack(cols, axis=1) if num_class > 1 else cols[0]
        return lax.psum(local, ax)

    sharded = shard_map(body, mesh=smesh.mesh,
                        in_specs=(P(ax), P(), P()),
                        out_specs=P(), check_vma=False)

    def fn(bins, num_it):
        return sharded(soas, bins, num_it)

    return fn


def tp_raw_margins(smesh: ServingMesh, forest, leaf_scale,
                   trees_per_device: int, shrink, depth_cap: int,
                   num_class: int = 1, widen: bool = False):
    """Build ``fn(bins, num_it) -> raw margins`` with the forest sharded
    on its tree axis and a ``psum`` combine.

    ``forest``/``leaf_scale`` must already be padded to a device
    multiple (:func:`pad_forest_for_tp`).  The returned callable is
    meant to be traced inside the runtime's jitted program; its output
    is replicated (every device holds the full ``[n]``/``[n, K]`` raw
    sums WITHOUT init_score — the caller adds init, the rf adjust and
    the objective transform on the replicated value).

    The global truncation window ``[0, num_it)`` maps into each shard's
    local tree coordinates via ``start_iteration = -axis_index *
    trees_per_device``: the predict kernel's round mask ``(t >= start) &
    (t < start + num)`` then selects exactly the local trees whose
    GLOBAL index falls inside the window — traced, so staged prediction
    still never recompiles.  When ``widen`` is set each shard widens its
    LOCAL compact (quantized) slice inside the program, keeping the
    widened copy transient per-device compute.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.predict import predict_forest_binned
    from ..ops.quantize import widen_tree

    ax = smesh.axis_name
    scales = () if leaf_scale is None else (leaf_scale,)

    def body(forest_loc, scales_loc, bins, num_it):
        offset = lax.axis_index(ax) * trees_per_device
        start = -jnp.asarray(offset, jnp.int32)

        def raw_one(tree_loc, scale_loc):
            if widen:
                tree_loc = widen_tree(tree_loc, scale_loc)
            return predict_forest_binned(
                tree_loc, bins, shrink, 0.0, num_it, depth_cap,
                start_iteration=start)

        if num_class > 1:
            cols = []
            for c in range(num_class):
                tree_c = jax.tree.map(lambda a, c=c: a[:, c], forest_loc)
                scale_c = (scales_loc[0][:, c] if scales_loc else None)
                cols.append(raw_one(tree_c, scale_c))
            local = jnp.stack(cols, axis=1)                   # [n, K]
        else:
            local = raw_one(forest_loc,
                            scales_loc[0] if scales_loc else None)
        return lax.psum(local, ax)

    sharded = shard_map(body, mesh=smesh.mesh,
                        in_specs=(P(ax), P(ax), P(), P()),
                        out_specs=P())

    def fn(bins, num_it):
        return sharded(forest, scales, bins, num_it)

    return fn
