"""graftlint Layer 2 — VMEM footprint estimates for the Pallas kernels.

Every Pallas kernel in the workbench keeps its accumulator resident in
VMEM; a v5e core has ~16 MB of it.  The r3/r4 OOMs (criteo efb_off 54 MB
accumulator, int8 relayout blowup) were all of the same species: a buffer
sized from NOMINAL dims when the hardware pads to (8, 128) tiles.  These
estimators therefore model the PADDED bytes of every VMEM-resident block
at representative production shapes (Higgs F=28, MSLR F=136, B=256) and
assert headroom against the 16 MB budget.

The hist-fused estimate calls the kernel's own ``_vmem_blocking`` so the
check can never drift from what the kernel actually allocates: if someone
retunes the blocking, the estimate follows automatically and this gate
re-validates the result.

Pure math — no compilation, no device; runs in the default ``lint`` pass.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

VMEM_BUDGET_BYTES = 16 * 1024 * 1024          # v5e per-core VMEM
LANE = 128                                    # minor-dim tile
SUBLANE = 8                                   # second-minor tile (32-bit)


def padded_bytes(shape: Tuple[int, ...], itemsize: int = 4) -> int:
    """Bytes a buffer occupies in VMEM after (8, 128) tiling.

    The minor dim pads to 128 lanes; the second-minor to 8 sublanes (the
    32-bit sublane count — bf16/int8 pack denser, but estimating with 8
    over-counts, which is the safe direction for a budget check)."""
    dims = list(shape)
    if not dims:
        return itemsize
    dims[-1] = -(-dims[-1] // LANE) * LANE
    if len(dims) >= 2:
        dims[-2] = -(-dims[-2] // SUBLANE) * SUBLANE
    total = itemsize
    for d in dims:
        total *= int(d)
    return total


def hist_fused_bytes(num_features: int, num_bins: int, k: int) -> int:
    """Estimated peak VMEM of one ``hist_fused_pallas`` grid step.

    Accumulator block [f_blk, B, k] (lane-padded k) + the per-chunk tile
    model the kernel's own blocking enforces (one-hot, folded stats,
    staged bins, masks, double-buffered inputs)."""
    from ..ops.histogram_pallas import _vmem_blocking

    f_blk, _, _, chunk = _vmem_blocking(num_features, num_bins, k)
    out_bytes = padded_bytes((f_blk, num_bins, k))
    # per-row tile model, same accounting _vmem_blocking budgets against
    per_row = 2 * num_bins + 10 * k + 8 * f_blk + 128
    return out_bytes + chunk * per_row


def split_iter_bytes(num_features: int, num_bins: int,
                     capacity: int, nc: int = 24) -> int:
    """Estimated peak VMEM of one ``split_iter_pallas`` call: whole-array
    blocks (no grid) for 5 inputs + 2 outputs, plus 2x headroom for the
    kernel's in-VMEM intermediates (per-feature gain scan rows, cumsum
    temporaries)."""
    hist2_t = padded_bytes((2, num_features, 3, num_bins))
    table = padded_bytes((capacity, nc))
    fmask = padded_bytes((1, num_features))
    aux = padded_bytes((1, 8))
    scal = padded_bytes((1, 16))
    io = hist2_t + table + fmask + aux + scal + table + aux
    return 2 * io


def predict_forest_bytes(node_slots: int, num_features: int,
                         precision: str = "int8",
                         row_block: int = 128) -> int:
    """Estimated peak scoped VMEM of one ``predict_forest_pallas`` grid
    step: the double-buffered operand blocks (node tables in storage
    dtype, bins, scale*mask, output), the widened f32 table scratch and
    leaf scratch, THREE live node one-hot buffers ``[sub, node_chunk,
    R]`` and one feature one-hot ``[sub, Fp, R]`` — what the chip's
    compiler keeps of the working set (r21, bisected with
    ``vmem_limit_bytes`` on the described v5e: the compiler's own
    figure is between 0.72x and 0.97x of this estimate over 253..2045
    slots, F=28/136, all three precisions;
    tests/test_tpu_compile.py holds it inside [0.5x, 1x]).  Reads the
    kernel's own blocking constants, so a retuned kernel moves the
    estimate with it."""
    from ..ops.predict import (PREDICT_NODE_PAD, PREDICT_SUB_TREES,
                               PREDICT_TREE_CHUNKS, predict_node_chunk)
    from ..ops.quantize import PACKED_NODE_BYTES

    tc = PREDICT_TREE_CHUNKS[precision]
    mp = max(PREDICT_NODE_PAD,
             -(-node_slots // PREDICT_NODE_PAD) * PREDICT_NODE_PAD)
    fp = max(8, -(-num_features // 8) * 8)
    # the is_leaf parity byte of the layout contract never enters the kernel
    table_bytes = tc * mp * (PACKED_NODE_BYTES[precision] - 1)
    blocks = 2 * (table_bytes + padded_bytes((fp, row_block))
                  + padded_bytes((tc, 1)) + padded_bytes((1, row_block)))
    scratch = 5 * tc * mp * 4 + tc * row_block * 4
    onehot = PREDICT_SUB_TREES * predict_node_chunk(mp) * row_block * 4
    feat_onehot = PREDICT_SUB_TREES * fp * row_block * 4
    return blocks + scratch + 3 * onehot + feat_onehot


@dataclass(frozen=True)
class VmemSpec:
    """One kernel at one representative shape vs the 16 MB budget."""

    name: str
    estimator: Callable[[], int]
    note: str = ""

    def check(self) -> Dict[str, object]:
        est = int(self.estimator())
        return {"name": self.name, "estimated_bytes": est,
                "estimated_mb": round(est / (1024 * 1024), 2),
                "budget_mb": VMEM_BUDGET_BYTES // (1024 * 1024),
                "ok": est <= VMEM_BUDGET_BYTES, "note": self.note}


# k = num_segments * S (S=3 grad/hess/count); wave-regime kernels run 42
# segments per wave (fused-CV production shape), the root pass runs 1.
VMEM_SPECS: Tuple[VmemSpec, ...] = (
    VmemSpec("hist_fused_higgs_root",
             lambda: hist_fused_bytes(28, 256, 3),
             note="Higgs F=28 B=256, root pass (k=3, lane-pads to 128)"),
    VmemSpec("hist_fused_higgs_wave",
             lambda: hist_fused_bytes(28, 256, 126),
             note="Higgs F=28 B=256, 42-segment wave (k=126)"),
    VmemSpec("hist_fused_mslr_wave",
             lambda: hist_fused_bytes(136, 256, 126),
             note="MSLR F=136 B=256 — the shape that forced feature "
                  "blocking (18 MB unblocked)"),
    VmemSpec("split_iter_cv31",
             lambda: split_iter_bytes(28, 256, capacity=61),
             note="r7 mega-kernel, num_leaves=31 (capacity 61), Higgs"),
    VmemSpec("split_iter_mslr",
             lambda: split_iter_bytes(136, 256, capacity=61),
             note="r7 mega-kernel at the MSLR feature width"),
    VmemSpec("predict_forest_higgs_f32",
             lambda: predict_forest_bytes(253, 28, "f32"),
             note="fused predict, 127-leaf trees (253 slots), Higgs F=28"),
    VmemSpec("predict_forest_ref_int8",
             lambda: predict_forest_bytes(509, 32, "int8"),
             note="fused predict at the serving reference forest: 255-leaf "
                  "trees (509 slots, two node chunks), int8, F=32"),
)


def check_vmem_specs() -> List[Dict[str, object]]:
    return [s.check() for s in VMEM_SPECS]
