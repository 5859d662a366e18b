"""What growing one tree is decided to be, before anything is traced.

Two frozen, hashable values and the rules that resolve them from
``Params`` and a row count.  ``Booster._grow_spec`` asks once per
effective row count; every round builder (``models/gbdt.py``,
``models/fused.py``, ``parallel/``, ``data/``) takes the ``GrowSpec``,
keys its program cache on it and hands it to
``models.tree.grower_from_spec``.  Imports only ``config``: the growers and
the learners import this module, never the reverse.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from ..config import Params

WAVE_TAILS = ("strict", "exact", "greedy", "half")
# past 512 segments a wave's one-hot is far beyond the MXU tile sweet spot
MAX_WAVE_WIDTH = 512


@dataclasses.dataclass(frozen=True)
class WaveSchedule:
    """How many splits one histogram pass retires and how the schedule
    spends the tail of the leaf budget (see :func:`resolve_wave`).

    ``width`` 1 is strict best-first, one split a pass, whatever the tail.
    ``cap_leaves`` is the exact tail's overgrowth cap and is given exactly
    when the tail is exact; that it exceeds ``num_leaves`` is checked by
    the growers, which know both.  ``narrow_width`` (0 = none, else a
    power of two below ``width``) is the width of the passes that have at
    most that many leaves to expand (:func:`narrow_width_for`): those of a
    tree with at most that many leaves and, under the exact tail, those
    whose replay still needs at most that many (``tree._replay_needed``);
    a grower without the partition-fused kernel ignores it.
    """

    width: int = 1
    tail: str = "strict"
    cap_leaves: Optional[int] = None
    narrow_width: int = 0

    def __post_init__(self):
        nw = self.narrow_width
        if nw and not (0 < nw < self.width and nw & (nw - 1) == 0):
            raise ValueError(
                f"narrow_width must be 0 or a power of two below the wave "
                f"width {self.width}, got {nw}")
        if self.tail not in WAVE_TAILS:
            raise ValueError(
                f"wave tail must be one of {WAVE_TAILS}, got {self.tail!r}")
        if not 1 <= self.width <= MAX_WAVE_WIDTH:
            raise ValueError(
                f"wave width must be in [1, {MAX_WAVE_WIDTH}], "
                f"got {self.width}")
        if self.tail == "strict" and self.width != 1:
            raise ValueError(
                f"the strict schedule splits one leaf a pass, got width "
                f"{self.width}")
        if (self.cap_leaves is not None) != (self.tail == "exact"):
            raise ValueError(
                "cap_leaves (the overgrowth cap) is given exactly when the "
                f"tail is 'exact', got tail={self.tail!r} "
                f"cap_leaves={self.cap_leaves!r}")


STRICT = WaveSchedule()


@dataclasses.dataclass(frozen=True)
class GrowSpec:
    """The static decisions of growing ONE tree, whatever learner grows it.

    Nothing traced and nothing per-learner: ``is_rf``, ``num_class``,
    ``linear_k``, ``goss_k``, the round counts and the mesh learners' merge
    settings stay arguments of the builders that alone use them.
    ``cat_key`` = (cat_smooth, cat_l2, max_cat_threshold,
    max_cat_to_onehot, min_data_per_group) (:func:`categorical_key`), the
    scan's scalars where the table has a categorical column: which columns
    they are, and their bins, are an operand of every program that grows
    on the table (``ops.split.CatColumns``), so every order of its columns
    is one program; ``mono_key`` per-column monotone signs;
    ``nbins_key`` per-column used-bin counts (bounds the extra_trees
    draw); ``ic_key`` interaction-group membership rows; ``bynode_off``
    statically true when ``feature_fraction_bynode == 1.0``, so the growers
    skip the per-node threefry draw.  ``onehot_rows``, ascending, the
    heights of the partition-fused kernels' one-hots over the TRAINING
    columns (an EFB table's bundle columns): each column's used bins
    rounded up to 16 (``ops.histogram_pallas.onehot_heights``), SORTED, so
    that every order of a table's columns keys one program (the rows'
    columns, ``onehot_order``, are an operand); ``None`` where every
    column takes ``num_bins``.
    """

    num_leaves: int
    num_bins: int
    hist_impl: str = "auto"
    row_chunk: int = 131072
    hist_dtype: str = "f32"
    wave: WaveSchedule = STRICT
    cat_key: Optional[tuple] = None
    mono_key: Optional[tuple] = None
    nbins_key: Optional[tuple] = None
    ic_key: Optional[tuple] = None
    extra_trees: bool = False
    bynode_off: bool = False
    onehot_rows: Optional[tuple] = None


def resolve_hist_dtype(p: Params, n_rows: int) -> str:
    """Histogram matmul precision (static).

    "auto" picks bf16 one-hot matmuls (full-rate MXU, f32 accumulation) once
    the data is large enough that (a) the histogram pass dominates wall time
    and (b) per-bin sums average over enough rows that the ~0.4% bf16
    quantization of per-row grad/hess washes out of the split scores
    (validated against f32 AUC on the Higgs bench).  Small data under
    "auto" resolves to "f32", which the fused TPU kernel serves as a hi/lo
    bf16 split (2 passes, ~1e-5 relative).  An EXPLICIT
    ``hist_dtype="f32"`` request is a contract for exactness (ADVICE r3):
    it resolves to "f32x", which bypasses the fused kernel for the true
    Precision.HIGHEST path unless ``hist_impl="pallas"`` is also forced.
    """
    if p.use_quantized_grad:
        # upstream's quantized-gradient training: reduced-precision
        # histogram accumulation.  bf16 MXU inputs are the FAST reduced
        # mode on this chip: a true int8 path exists (hist_dtype="int8",
        # stochastic rounding + exact int32 accumulation) but Mosaic's
        # int8 relayouts force a 4x smaller row chunk and it measured
        # 17.8 ms/pass vs bf16's 10.5 at the Higgs shape
        return "bf16"
    d = p.extra.get("hist_dtype", "auto")
    if d != "auto":
        return "f32x" if d == "f32" else d
    return "bf16" if n_rows >= (1 << 19) else "f32"


def check_int8_row_limit(p: Params, n_rows: int, n_shards: int = 1) -> None:
    """Fail fast when ``hist_dtype='int8'`` cannot accumulate exactly.

    The kernel-level guard (``hist_fused_pallas``) catches this too, but
    only at trace time inside the compiled round — by which point the
    user has paid dataset binning and sharding.  This check runs once per
    ``update()`` with the Booster's own shard count, so oversized int8
    configs die with a clear message before any lowering.
    """
    if resolve_hist_dtype(p, n_rows) != "int8":
        return
    from ..ops.histogram_pallas import INT8_ACC_ROW_LIMIT

    per_shard = -(-n_rows // max(int(n_shards), 1))
    if per_shard > INT8_ACC_ROW_LIMIT:
        raise ValueError(
            f"hist_dtype='int8' with {per_shard:,} rows per device shard "
            f"(n={n_rows:,} over {n_shards} shard(s)) exceeds the exact "
            f"int32 accumulation limit of {INT8_ACC_ROW_LIMIT:,} rows — "
            f"histograms would silently wrap.  Use hist_dtype='bf16' or "
            f"train on more devices.")


def _exact_overgrow_target(num_leaves: int, width: int, over: float) -> int:
    """Wave-aligned overgrowth CAP for the exact tail (the leaf count a
    tree grows to when its replay is never certified earlier:
    ``tree._replay_certified``).

    Every full-width histogram pass costs the same whether it retires 2 or
    ``width`` splits, so a cap that lands mid-wave buys its last few
    candidate nodes at the price of a full pass.  Walk the greedy wave
    schedule (same recurrence as the grower: wave size = min(frontier
    doubling, width)) and pick the wave boundary closest to
    ``num_leaves * over`` in log space, bounded to (num_leaves, 2.5x].
    """
    target = max(num_leaves * over, num_leaves + 1)
    leaves, cand = 1, 1
    best = None
    while leaves < 2.5 * num_leaves:
        s = min(cand, width)
        leaves += s
        cand = min(cand * 2, leaves)
        if leaves > num_leaves:
            if best is None or (abs(math.log(leaves / target))
                                < abs(math.log(best / target))):
                best = leaves
    return best or int(math.ceil(target))


def narrow_width_for(width: int) -> int:
    """The narrow phase's width for a tree whose waves are ``width`` wide:
    the largest power of two of segments whose partition-fused pass the
    kernel still turns (``ops.histogram_pallas.TURNED_MAX_K`` statistics
    columns, 3 a segment), if that is below ``width``; else 0, no narrow
    phase (every pass of so narrow a tree is turned already).

    The rule behind the constant: the largest power of two whose turned
    pass costs at most 0.75 of the full-width pass at both benchmark
    shapes.  Measured (v5e, PR 32; ms a pass at 10,500,096 x 28 and at
    400,128 x 2,000, full width 42: 113.1 and 645.2): width 16 turned
    62.6 and 360.2 (0.55, 0.56), width 32 turned 88.7 and 509.9 (0.78,
    0.79): 16.  A 255-leaf tree's first five passes (1, 2, 4, 8, 16
    leaves) run at it and, under the exact tail, the certification passes
    that need at most 16 leaves expanded (PR 35); never a user parameter.
    """
    from ..ops.histogram_pallas import TURNED_MAX_K

    narrow = 1 << ((TURNED_MAX_K // 3).bit_length() - 1)
    return narrow if narrow < width else 0


def resolve_wave(p: Params, n_rows: int) -> WaveSchedule:
    """Pick the grower's splits-per-histogram-pass and its tail (static).

    ``grow_policy="leafwise"`` forces strict best-first — use it when
    LightGBM-exact split ORDER matters (wave growth picks each wave's split
    set before scoring that wave's children, which can allocate the leaf
    budget differently when it binds mid-wave; predictive quality is
    equivalent in tests).  "frontier" forces wave growth.  "auto" defaults
    to waves for any non-toy workload (>= 4096 rows and >= 16 leaves):
    every histogram pass has a large fixed cost on the TPU runtime, and a
    wave retires up to ``width`` splits per pass instead of one (the strict
    grower's ``num_leaves - 1`` passes are the round-time ceiling — VERDICT
    r1 item 3).  Default width 42 keeps the segment-folded one-hot matmul
    at 3*42=126 lanes, inside one 128-lane MXU tile: in the orientation
    ``onehot [B, chunk] x operand [3W, chunk]^T`` the MXU streams the
    one-hot's 255 rows per weight tile whatever ``3W <= 128`` is, so a
    pass of 42 splits costs what a pass of one does (113.1 ms for 109.1 at
    10.5M x 28, v5e, PR 32).  That is NOT the least a narrow pass can
    cost: with the dot turned a pass of up to 16 splits takes 0.55 of it,
    which is what the schedule's ``narrow_width`` is for
    (:func:`narrow_width_for`).
    """
    if p.grow_policy == "leafwise":
        return STRICT
    width = int(p.extra.get("wave_width", 0)) or min(42, p.num_leaves - 1)
    width = max(1, min(width, MAX_WAVE_WIDTH))
    if width == 1 or (p.grow_policy != "frontier"
                      and not (n_rows >= 4096 and p.num_leaves >= 16)):
        return STRICT
    # wave_tail — how the wave schedule spends the tail of the leaf
    # budget, where wave and strict best-first order can diverge:
    #   "exact"  — overgrow past num_leaves in pathmin order until the
    #     replay is provably the strict tree (models/tree.py
    #     _replay_certified; at most to the ~2x cap below), then replay
    #     strict best-first selection over the realized gains and prune
    #     (_exact_prune).  LightGBM-exact split ORDER at the larger of
    #     greedy's pass count and the strict tree's depth (a pass grows
    #     one level): 11-16 passes at 255 leaves, width 42, for greedy's
    #     11 and the cap's 17 (PERF.md PR 29); r4's gap decomposition
    #     proved split order was the ENTIRE residual quality gap of the
    #     old near-strict tail (PERF_HISTORY.md), so this is the default
    #     wherever order can matter: large data (the AUC-parity north
    #     star), budget-saturating small data, and every ranking
    #     objective (rank lambdas are tail-order-sensitive: the greedy
    #     tail costs ~6e-2 NDCG@10 on the MSLR bench).
    #   "greedy" — whole remaining budget per wave, fewest passes.
    #     Default only for mid-size pointwise tasks whose budget is far
    #     from saturating the rows AND whose tree closes before the wave
    #     width binds (num_leaves - 1 <= width: every wave but the last
    #     splits every leaf that can split) — r4 measured the diamonds
    #     shape (46k rows, nl=31, ~1.5k rows/leaf) quality-NEUTRAL across
    #     half/greedy/strict while greedy is 1.44x faster.  Where the
    #     width binds, a wave takes the 42 best leaves it HAS and strict
    #     order would have taken their children: at 400,000 x 2,000, 255
    #     leaves (1,568 rows a leaf, which this rule sent to greedy until
    #     PR 28) the benchmark's reference read a best-first excess of
    #     0.12 and 0.37 of a split's gain on two seeds against -0.0007
    #     and 0.002 under "exact" (limit 0.04; chip, PR 28), as it had at
    #     10.5M x 28 (0.056-0.17, PR 25).
    #   "half"   — at most half the remaining budget per wave
    #     (near-strict tail, r3's compromise; kept for compatibility).
    rows_per_leaf = n_rows // max(p.num_leaves, 1)
    # objective "none" = user-supplied fobj whose tail-order sensitivity
    # is unknown (a custom ranking loss would silently eat the greedy
    # tail's ~6e-2 NDCG cost) — classify it conservatively (ADVICE r4)
    pointwise = p.objective not in ("lambdarank", "rank_xendcg", "none")
    default_tail = ("greedy" if pointwise and rows_per_leaf >= 1024
                    and n_rows < (1 << 19) and p.num_leaves - 1 <= width
                    else "exact")
    tail = str(p.extra.get("wave_tail", default_tail))
    narrow = narrow_width_for(width)
    if tail != "exact":
        return WaveSchedule(width, tail, narrow_width=narrow)
    # wave_overgrow is the CAP of the overgrowth, for trees whose replay
    # is not certified earlier.  Default 2.0: history sized it, when every
    # tree ran to it (the r5 on-chip gap-vs-overgrow sweep converged at
    # ~2x: Higgs-1M 1.5x -> +8.6e-4 vs oracle, 2.0x -> +0.3..2.1e-4 across
    # oracle draws, 2.5x no better; PERF_HISTORY.md r5)
    over = float(p.extra.get("wave_overgrow", 2.0))
    return WaveSchedule(width, "exact",
                        _exact_overgrow_target(p.num_leaves, width, over),
                        narrow)


def categorical_key(mapper, p: Params) -> Optional[tuple]:
    """``GrowSpec.cat_key`` of a table's ``BinMapper``: the categorical
    scan's scalars, ``None`` where no column is categorical."""
    if not np.any(mapper.is_categorical):
        return None
    return (float(p.cat_smooth), float(p.cat_l2), int(p.max_cat_threshold),
            int(p.max_cat_to_onehot), float(p.min_data_per_group))


def resolve_grow_spec(p: Params, n_rows: int, num_bins: int, *,
                      cat_key: Optional[tuple] = None,
                      mono_key: Optional[tuple] = None,
                      nbins_key: Optional[tuple] = None,
                      ic_key: Optional[tuple] = None,
                      col_bins: Optional[tuple] = None) -> GrowSpec:
    """The one place ``Params`` and a row count become a :class:`GrowSpec`
    (``n_rows`` = the rows a tree is grown on: GOSS grows on its
    ``k_top + k_other`` sample).  The keys come from the dataset;
    ``col_bins`` = the bins each training column uses, its codes below
    them, which set ``onehot_rows``."""
    onehot_rows = None
    if col_bins is not None:
        from ..ops.histogram_pallas import onehot_heights

        heights = onehot_heights(col_bins, num_bins)
        onehot_rows = None if heights is None else tuple(sorted(heights))
    return GrowSpec(
        num_leaves=p.num_leaves, num_bins=num_bins,
        hist_impl=p.extra.get("hist_impl", "auto"),
        row_chunk=int(p.extra.get("row_chunk", 131072)),
        hist_dtype=resolve_hist_dtype(p, n_rows),
        wave=resolve_wave(p, n_rows),
        cat_key=cat_key, mono_key=mono_key, nbins_key=nbins_key,
        ic_key=ic_key, extra_trees=p.extra_trees,
        bynode_off=p.feature_fraction_bynode >= 1.0, onehot_rows=onehot_rows)
