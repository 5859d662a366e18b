"""The main path's kernels, compiled for the v5e without a chip (r21).

The TPU compiler is installed here and compiles for a chip that is
described, not attached, so what Mosaic refuses (an unsupported cast or
primitive, a misaligned slice, too much scoped VMEM) fails HERE instead
of on the chip.  Interpret-mode tests cannot see any of it: r18's
``predict_forest_pallas`` and r7's ``split_iter_pallas`` passed every
CPU test and were both refused by the chip's compiler.

Nothing runs — shapes only, at the real widths (Higgs F=28 / MSLR F=136,
B=256, 42-segment waves, 1M rows, 127-leaf trees).  The topology is
described inside a module-scoped fixture of this file and nowhere else:
only the worker that is handed this file loads the TPU library, and a
machine that cannot describe the topology skips these tests and no
others.  The persistent compile cache is off around them (an entry
compiled for a described chip cannot be read back without one).
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N_ROWS = 1_000_192            # Higgs-1M padded to the Dataset's 256 rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:                    # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        if had_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def S(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` on shapes placed on the described chip; the compiled
    program must contain a Mosaic kernel."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_hist_fused_bf16_higgs_wave(one_chip):
    from lightgbm_tpu.ops.histogram_pallas import hist_fused_pallas

    _compile(lambda b, s, g: hist_fused_pallas(
        b, s, g, 42, 256, hist_dtype="bf16", interpret=False), one_chip,
        S((N_ROWS, 28), jnp.uint8), S((N_ROWS, 3), jnp.float32),
        S((N_ROWS,), jnp.int32))


@pytest.mark.parametrize("num_features", [28, 136],
                         ids=["single_block_f28", "multi_block_f136"])
def test_hist_partition_fused(one_chip, num_features):
    from lightgbm_tpu.ops.histogram_pallas import (
        _vmem_blocking, hist_partition_fused_pallas, prepare_wave_operands)

    w, b = 42, 256
    assert (_vmem_blocking(num_features, b, w * 3, chunk_align=512)[1]
            > 1) == (num_features == 136)

    def wave(bins, stats, pv, wfeat):
        bins_t, stats_t, chunk = prepare_wave_operands(bins, stats, b, w)
        pv_t = jnp.pad(pv, ((0, 0), (0, bins_t.shape[1] - pv.shape[1])))
        return hist_partition_fused_pallas(
            bins_t, stats_t, pv_t, w, b, chunk, interpret=False,
            hist_dtype="bf16", wfeat=wfeat, num_features=num_features)

    _compile(wave, one_chip,
             S((N_ROWS, num_features), jnp.uint8),
             S((N_ROWS, 3), jnp.float32), S((8, N_ROWS), jnp.float32),
             S((w,), jnp.int32))


@pytest.mark.parametrize("num_features,width", [(32, 42), (32, 16),
                                                 (136, 42)],
                         ids=["single_block_f32", "narrow_f32",
                              "multi_block_f136"])
def test_hist_partition_fused_category_sets(one_chip, num_features, width):
    """The fused kernels with the wave's category sets as an operand (the
    one-hot dot that lays each row's set down its lane, the bit read off
    along the sublanes), as a table with categorical columns builds
    them."""
    from lightgbm_tpu.ops.histogram_pallas import (
        hist_partition_fused_pallas, prepare_wave_operands)

    b = 256

    def wave(bins, stats, pv, wfeat, sets):
        bins_t, stats_t, chunk = prepare_wave_operands(bins, stats, b, 42)
        pv_t = jnp.pad(pv, ((0, 0), (0, bins_t.shape[1] - pv.shape[1])))
        return hist_partition_fused_pallas(
            bins_t, stats_t, pv_t, width, b, chunk, interpret=False,
            hist_dtype="bf16", wfeat=wfeat, num_features=num_features,
            f_blk=None if width == 42 else bins_t.shape[0],
            cat_sets=sets)

    rows = 2_000_128
    _compile(wave, one_chip,
             S((rows, num_features), jnp.uint8),
             S((rows, 3), jnp.float32), S((8, rows), jnp.float32),
             S((width,), jnp.int32), S((width, b), jnp.float32))


@pytest.mark.parametrize("num_bins", [255, 256])
def test_split_iter(one_chip, num_bins):
    # 255 is what max_bin=255 data has: the wrapper pads the bin axis to
    # the lane tile and the kernel masks the padding
    from lightgbm_tpu.models.tree import _PK
    from lightgbm_tpu.ops.histogram_pallas import split_iter_pallas

    cap = 2 * 127 - 1
    _compile(lambda h, t, fm, aux, sc: split_iter_pallas(
        h, t, fm, aux, sc, pk=_PK, interpret=False), one_chip,
        S((2, 28, 3, num_bins), jnp.float32), S((cap, _PK.NC), jnp.float32),
        S((1, 28), jnp.float32), S((1, 8), jnp.float32),
        S((1, 16), jnp.float32))


def test_kernel_payload_ignores_call_path_and_trace_history(one_chip):
    """A Pallas kernel is serialized into its program with its source
    locations and that payload is part of the persistent-cache key, so
    the one compile-cache rule (``utils/compile_cache.py``) keeps
    locations out of the IR.  Without it the second lowering below
    differs twice over: it comes from another call path, and the
    ``jnp`` helper both kernels use was first traced on another line."""
    from lightgbm_tpu.models.tree import _PK
    from lightgbm_tpu.ops.histogram_pallas import (hist_fused_pallas,
                                                   split_iter_pallas)

    def lowered(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).as_text()

    def split_iter():
        return lowered(lambda h, t, fm, aux, sc: split_iter_pallas(
            h, t, fm, aux, sc, pk=_PK, interpret=False),
            S((2, 28, 3, 256), jnp.float32), S((253, _PK.NC), jnp.float32),
            S((1, 28), jnp.float32), S((1, 8), jnp.float32),
            S((1, 16), jnp.float32))

    lowered(lambda b, s, g: hist_fused_pallas(
        b, s, g, 8, 256, hist_dtype="f32", interpret=False),
        S((4096, 28), jnp.uint8), S((4096, 3), jnp.float32),
        S((4096,), jnp.int32))
    after_another_kernel = split_iter()
    jax.clear_caches()
    from_a_fresh_trace = (lambda: split_iter())()
    assert "tpu_custom_call" in after_another_kernel
    assert after_another_kernel == from_a_fresh_trace


def _soa_shapes(precision, num_trees, node_slots):
    from lightgbm_tpu.ops.predict import PREDICT_TREE_CHUNKS, ForestSoA

    tc = PREDICT_TREE_CHUNKS[precision]
    tp = -(-num_trees // tc) * tc
    mp = -(-node_slots // 128) * 128
    idx_t, thr_t, leaf_t = {
        "f32": (jnp.int32, jnp.int32, jnp.float32),
        "bf16": (jnp.int16, jnp.uint8, jnp.bfloat16),
        "int8": (jnp.int16, jnp.uint8, jnp.int8)}[precision]
    tbl = (tp, mp)
    return ForestSoA(S(tbl, idx_t), S(tbl, thr_t), S(tbl, idx_t),
                     S(tbl, idx_t), S(tbl, leaf_t), S(tbl, jnp.bool_),
                     S((tp,), jnp.float32))


def _predict(depth_cap):
    from lightgbm_tpu.ops.predict import predict_forest_pallas

    return lambda soa, bins, num_it: predict_forest_pallas(
        soa, bins, jnp.float32(0.1), 0.0, num_it, depth_cap,
        interpret=False)


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_predict_forest(one_chip, precision):
    # 300 trees of 127 leaves at the bucket ladder's ends, then the
    # serving reference forest (255 leaves -> two node chunks, F=136)
    for bucket in (8, 16384):
        _compile(_predict(14), one_chip, _soa_shapes(precision, 300, 253),
                 S((bucket, 28), jnp.uint8), S((), jnp.int32))
    _compile(_predict(12), one_chip, _soa_shapes(precision, 800, 509),
             S((16384, 136), jnp.uint8), S((), jnp.int32))


@pytest.mark.parametrize("precision,node_slots,num_features",
                         [("f32", 253, 28), ("int8", 253, 28),
                          ("int8", 509, 136)])
def test_predict_vmem_estimate_brackets_the_compiler(
        one_chip, monkeypatch, precision, node_slots, num_features):
    """``analysis.vmem.predict_forest_bytes`` against the compiler's own
    scoped-VMEM accounting: the kernel compiles inside the estimate and
    is refused inside half of it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.analysis.vmem import (VMEM_BUDGET_BYTES,
                                            predict_forest_bytes)

    est = predict_forest_bytes(node_slots, num_features, precision)
    assert est <= VMEM_BUDGET_BYTES
    real_call = pl.pallas_call

    def compile_with_limit(limit):
        monkeypatch.setattr(
            pl, "pallas_call", lambda *a, **k: real_call(
                *a, compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=int(limit)), **k))
        try:
            _compile(_predict(12), one_chip,
                     _soa_shapes(precision, 64, node_slots),
                     S((4096, num_features), jnp.uint8), S((), jnp.int32))
        finally:
            monkeypatch.setattr(pl, "pallas_call", real_call)

    compile_with_limit(est)
    with pytest.raises(Exception, match="vmem"):
        compile_with_limit(est // 2)


def _named_cases():
    """``(case, build)``: ``build(name)`` gives ``(fn, shapes)`` of one
    wrapper at a small size, with the wrapper's own name (``{}``) or the
    role a grower passes."""
    from lightgbm_tpu.models.tree import _PK
    from lightgbm_tpu.ops import histogram_pallas as hp
    from lightgbm_tpu.ops.predict import predict_forest_pallas

    n, f, b, w = 4096, 28, 256, 8
    rows = (S((n, f), jnp.uint8), S((n, 3), jnp.float32),
            S((n,), jnp.int32))

    def partition(num_features, width=42):
        # a narrow pass (width 16: the dot turned) reads the operands and
        # the feature block of the tree's full-width pass
        def build(named):
            def wave(bins, stats, pv, wfeat):
                bins_t, stats_t, chunk = hp.prepare_wave_operands(
                    bins, stats, b, 42)
                pv_t = jnp.pad(pv, ((0, 0),
                                    (0, bins_t.shape[1] - pv.shape[1])))
                return hp.hist_partition_fused_pallas(
                    bins_t, stats_t, pv_t, width, b, chunk, interpret=False,
                    hist_dtype="bf16", wfeat=wfeat,
                    num_features=num_features,
                    f_blk=hp._vmem_blocking(num_features, b, 3 * 42)[0],
                    **named)
            return wave, (S((n, num_features), jnp.uint8),
                          S((n, 3), jnp.float32), S((8, n), jnp.float32),
                          S((width,), jnp.int32))
        return build

    return {
        "hist_fused": lambda named: (
            lambda bi, st, sg: hp.hist_fused_pallas(
                bi, st, sg, w, b, hist_dtype="bf16", interpret=False,
                **named), rows),
        "hist_fused_batched": lambda named: (
            lambda bi, st, sg: hp.hist_fused_pallas_batched(
                bi, st, sg, 42, b, hist_dtype="bf16", interpret=False,
                **named),
            (rows[0], S((2, n, 3), jnp.float32), S((2, n), jnp.int32))),
        "hist_partition_fused": partition(28),
        "hist_partition_fused_mb": partition(136),
        "hist_partition_fused_narrow": partition(28, 16),
        "hist_partition_fused_mb_narrow": partition(136, 16),
        # two features and a small chunk: this kernel unrolls one matmul
        # per feature and is not on the main path (28 compile for minutes)
        "hist_segstats": lambda named: (
            lambda bi, ss: hp.hist_from_segstats_pallas(
                bi, ss, b, chunk=256, hist_dtype="bf16", interpret=False,
                **named),
            (S((1024, 2), jnp.uint8), S((1024, 3 * w), jnp.float32))),
        "split_iter": lambda named: (
            lambda h, t, fm, aux, sc: hp.split_iter_pallas(
                h, t, fm, aux, sc, pk=_PK, interpret=False, **named),
            (S((2, f, 3, b), jnp.float32), S((253, _PK.NC), jnp.float32),
             S((1, f), jnp.float32), S((1, 8), jnp.float32),
             S((1, 16), jnp.float32))),
        "predict_forest": lambda named: (
            lambda soa, bi, it: predict_forest_pallas(
                soa, bi, jnp.float32(0.1), 0.0, it, 12, interpret=False,
                **named),
            (_soa_shapes("f32", 64, 253), S((n, f), jnp.uint8),
             S((), jnp.int32))),
    }


@pytest.mark.parametrize("case,name,instruction", [
    ("hist_fused", None, "%lgbtpu_hist_fused"),
    ("hist_fused_batched", None, "%lgbtpu_hist_fused"),
    ("hist_partition_fused", None, "%lgbtpu_hist_partition_fused"),
    ("hist_partition_fused_mb", None, "%lgbtpu_hist_partition_fused"),
    ("hist_segstats", None, "%lgbtpu_hist_segstats"),
    ("split_iter", None, "%lgbtpu_split_iter"),
    ("predict_forest", None, "%lgbtpu_predict_forest"),
    ("hist_fused", "lgbtpu_hist_root", "%lgbtpu_hist_root"),
    ("hist_fused", "lgbtpu_hist_wave", "%lgbtpu_hist_wave"),
    ("hist_partition_fused", "lgbtpu_hist_wave", "%lgbtpu_hist_wave"),
    ("hist_partition_fused_mb", "lgbtpu_hist_wave", "%lgbtpu_hist_wave"),
    ("hist_partition_fused_narrow", "lgbtpu_hist_narrow",
     "%lgbtpu_hist_narrow"),
    ("hist_partition_fused_mb_narrow", "lgbtpu_hist_narrow",
     "%lgbtpu_hist_narrow"),
])
def test_kernel_instruction_names(one_chip, case, name, instruction):
    """The compiled Mosaic call's HLO instruction is named by the program:
    the kernel's own name by default, the ROLE where a grower passes one
    (what the device trace shows and the benchmark's metrics match)."""
    fn, shapes = _named_cases()[case]({} if name is None else {"name": name})
    text = _compile(fn, one_chip, *shapes).as_text()
    calls = re.findall(
        r"^\s*(%[^ ]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text, re.M)
    assert calls and all(
        re.fullmatch(re.escape(instruction) + r"(\.\d+)?", c)
        for c in calls), calls


@pytest.mark.parametrize("num_features,rows_padded", [
    (28, 10_500_096), (6, 4_000_000), (137, 2_270_208), (700, 11_000_064)],
    ids=["higgs", "narrow", "mslr", "expo"])
def test_bin_code_block_program(one_chip, num_features, rows_padded):
    """The device's bin-code pass (PR 27): plain XLA, no kernel.  A block
    handed over as a flat array and reshaped in the program took the chip's
    compiler 22 s (F=137) to 147 s (F=6) and 0.7 GB of temporaries; as a
    2-D block it builds in about a second with none to speak of."""
    import time

    from lightgbm_tpu import dataset

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    block = dataset.code_block_rows(num_features)
    t0 = time.perf_counter()
    compiled = dataset._write_code_block.lower(
        on_chip((rows_padded, num_features), jnp.uint8),
        on_chip((block, num_features), jnp.int32), on_chip((), jnp.int32),
        on_chip((num_features, 254), jnp.int32),
        on_chip((num_features,), jnp.int32)).compile()
    assert time.perf_counter() - t0 < 15.0
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes > 0          # codes written in place
    assert memory.temp_size_in_bytes < 128 << 20


# -- the whole round (PR 28) -------------------------------------------------

_HLO_SHAPE = re.compile(r"(f32)\[([0-9,]+)\]\{([0-9,]+):T\(([0-9,]+)\)")


def _narrow_minor_f32(text, least_bytes):
    """``[(padded bytes, shape)]`` of the float32 arrays in a compiled
    program's text whose MINOR axis (the first of ``minor_to_major``) is
    under 8 wide and whose tiled size is over ``least_bytes``: the chip
    stores the two minor axes in (8, 128) tiles, so such an array takes
    up to 128x its bytes (``f32[84,2000,256,3]``: 22 GB for 0.5)."""
    found = set()
    for m in _HLO_SHAPE.finditer(text):
        dims = [int(d) for d in m.group(2).split(",")]
        order = [int(d) for d in m.group(3).split(",")]
        tile = [int(d) for d in m.group(4).split(",")]
        if len(order) != len(dims) or dims[order[0]] >= 8:
            continue
        phys = [dims[i] for i in reversed(order)]          # major .. minor
        for j, t in enumerate(reversed(tile)):
            if j < len(phys):
                phys[-1 - j] = -(-phys[-1 - j] // t) * t
        size = 4
        for d in phys:
            size *= d
        if size > least_bytes:
            found.add((size, m.group(0)))
    return sorted(found, reverse=True)


def _round_compiled(one_chip, monkeypatch, rows_padded, num_features,
                    **params):
    """``Booster._fused_segment(1)`` lowered for the described chip with
    the row axis set to ``rows_padded`` (:func:`_round_segment`)."""
    fn, shapes, facts = _round_segment(monkeypatch, rows_padded,
                                       num_features, one_chip, **params)
    return fn.lower(*shapes).compile(), facts


def _round_segment(monkeypatch, rows_padded, num_features, sharding=None,
                   table=None, **params):
    """``(fn, shapes, facts)`` of ``Booster._fused_segment(1)`` with the
    row axis set to ``rows_padded``: a booster on a small table of the
    real width (255 bins, 255 leaves), its dataset's row count replaced
    by a shape so the program resolves its precision, wave tail and
    blocking as at the real size, ``jax.default_backend`` patched so the
    round takes the chip's route (the Pallas kernels)."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils import profiling

    rng = np.random.default_rng(0)
    X = rng.standard_normal((2048, num_features)).astype(np.float32)
    y = (rng.random(2048) < 0.5).astype(np.float32)
    if table is not None:       # ``(X, y)`` of another shape of table
        X, y = table
    booster = lgb.Booster(
        dict(objective="binary", num_leaves=255, learning_rate=0.1,
             max_bin=255, min_data_in_leaf=1, min_sum_hessian_in_leaf=100.0,
             verbosity=-1, **params), lgb.Dataset(X, label=y))
    ds = booster.train_set
    small = int(ds.row_mask.shape[0])
    ds.row_mask = jax.ShapeDtypeStruct((rows_padded,), ds.row_mask.dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = booster._fused_segment(1)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            tuple(rows_padded if d == small else d for d in a.shape),
            a.dtype, sharding=sharding), args)
    return fn, shapes, dict(profiling.snapshot()["facts"])


# (rows padded, features, parameters, facts the program resolves to, the most
# temporaries).  Wide: LightGBM's GPU benchmark on Epsilon, 400,000 x 2,000:
# what the program resolves to by itself (hi/lo float32, the exact tail: a
# cache of 526 leaves, 3.2 GB, which the update writes a second time), then
# the control's precision and the other tail; the parent stopped here with
# RESOURCE_EXHAUSTED (22.0 GB for one f32[84,2000,3,256]).  Narrow:
# Higgs-1M, where the parent's round took 1,457,935,872 B of temporaries,
# most of it the same lane padding.
_ROUNDS = {
    "epsilon_default": (400_128, 2000, {},
                        {"hist_dtype": "f32", "wave_tail": "exact"},
                        13 << 30),
    "epsilon_bf16": (400_128, 2000, {"hist_dtype": "bf16"},
                     {"hist_dtype": "bf16", "wave_tail": "exact"}, 13 << 30),
    "epsilon_greedy_tail": (400_128, 2000, {"wave_tail": "greedy"},
                            {"hist_dtype": "f32", "wave_tail": "greedy"},
                            11 << 30),
    "higgs_1m": (N_ROWS, 28, {}, {"hist_dtype": "bf16",
                                  "wave_tail": "exact"}, 1_457_935_872),
}


@pytest.mark.parametrize("case", list(_ROUNDS))
def test_whole_round(one_chip, monkeypatch, case):
    from lightgbm_tpu.models.spec import narrow_width_for

    rows_padded, num_features, params, resolved, most_temp = _ROUNDS[case]
    compiled, facts = _round_compiled(one_chip, monkeypatch, rows_padded,
                                      num_features, **params)
    for fact, value in resolved.items():
        assert facts["train." + fact] == value
    assert facts["train.rows_padded"] == rows_padded
    assert facts["train.features"] == num_features
    text = compiled.as_text()
    assert "%lgbtpu_hist_root" in text and "%lgbtpu_hist_wave" in text
    # the doubling passes' own role, at the width the schedule resolved
    assert "%lgbtpu_hist_narrow" in text
    assert facts["train.wave_narrow_width"] == narrow_width_for(42) > 0
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= most_temp
    assert _narrow_minor_f32(text, 64 << 20) == []
    if num_features == 2000:
        # the blocking of _vmem_blocking as the facts state it: the last
        # block's loop runs over its 16 real features of 32
        assert (facts["train.feature_blocks"], facts["train.features_padded"],
                facts["train.feature_rows_looped"],
                facts["train.chunk_rows"]) == (63, 2016, 2000, 3072)
        assert facts["train.hist_calls_per_pass"] == (
            2 if resolved["hist_dtype"] == "f32" else 1)
    else:
        assert (facts["train.feature_blocks"], facts["train.features_padded"],
                facts["train.feature_rows_looped"]) == (1, 28, 28)


def test_whole_round_of_a_bundled_table(one_chip, monkeypatch):
    """The sparse cell's round (1,000,192 rows of a Bosch-shaped table,
    968 features bundled into a few hundred columns by EFB) lowers for the
    described chip: the member view, the range routing and the kernels
    over the bundle columns, under a bound on the temporaries."""
    from benchmark.datagen_sparse import bosch_like

    fn, shapes, facts = _round_segment(
        monkeypatch, 1_000_192, 968, one_chip,
        table=bosch_like(60_000, 968, 2142000001))
    compiled = fn.lower(*shapes).compile()
    assert facts["train.features_raw"] == 968
    assert facts["train.features"] == facts["dataset.bundle_columns"] < 968
    assert facts["train.hist_dtype"] == "bf16"
    # the kernels' one-hots as tall as the columns' bins: about two thirds
    # of the table's 256 over the bundle columns
    assert 0.5 < facts["train.onehot_bin_share"] < 0.8
    text = compiled.as_text()
    assert "%lgbtpu_hist_root" in text and "%lgbtpu_hist_narrow" in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 8 << 30
    assert _narrow_minor_f32(text, 64 << 20) == []


def test_whole_round_of_a_categorical_table(one_chip, monkeypatch):
    """The categorical cell's round (13,184,384 rows of an Allstate-shaped
    table, 16 of its 32 columns categorical) lowers for the described
    chip with the category sets routed inside the partition-fused
    kernels, the narrow passes and the one-hot heights engaged, under a
    bound on the temporaries."""
    import numpy as np

    import lightgbm_tpu as lgb
    from benchmark.datagen_cat import CATEGORICAL, allstate_like
    from lightgbm_tpu.utils import profiling

    rows_padded = 13_184_384
    X, y = allstate_like(60_000, 2144000001)
    booster = lgb.Booster(
        dict(objective="binary", num_leaves=255, learning_rate=0.1,
             max_bin=255, min_data_in_leaf=1, min_sum_hessian_in_leaf=100.0,
             verbosity=-1),
        lgb.Dataset(X, label=y, categorical_feature=list(CATEGORICAL)))
    ds = booster.train_set
    small = int(ds.row_mask.shape[0])
    ds.row_mask = jax.ShapeDtypeStruct((rows_padded,), ds.row_mask.dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = booster._fused_segment(1)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            tuple(rows_padded if d == small else d for d in a.shape),
            a.dtype, sharding=one_chip), args)
    lowered = fn.lower(*shapes)
    # the grower notes where it routes category sets as it is traced
    facts = dict(profiling.snapshot()["facts"])
    assert facts["train.cat_route"] == "fused"
    assert facts["train.cat_features"] == 16
    assert facts["train.hist_dtype"] == "bf16"
    assert facts["train.wave_narrow_width"] == 16
    assert 0.3 < facts["train.onehot_bin_share"] < 0.8
    compiled = lowered.compile()
    text = compiled.as_text()
    for kernel in ("%lgbtpu_hist_root", "%lgbtpu_hist_wave",
                   "%lgbtpu_hist_narrow"):
        assert kernel in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 6 << 30
    assert _narrow_minor_f32(text, 64 << 20) == []


def test_bin_code_block_program_of_categorical_columns(one_chip):
    """The device's bin-code pass with the categorical columns' exact
    match (``dataset.BinMapper.device_tables``) at the categorical cell's
    width."""
    from lightgbm_tpu import dataset

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f = 32
    block = dataset.code_block_rows(f)
    compiled = dataset._write_code_block.lower(
        on_chip((13_184_384, f), jnp.uint8), on_chip((block, f), jnp.int32),
        on_chip((), jnp.int32), on_chip((f, 254), jnp.int32),
        on_chip((f,), jnp.int32),
        {"keys": on_chip((f, 254), jnp.int32),
         "overflow": on_chip((f,), jnp.int32)}).compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes > 0          # codes written in place
    assert memory.temp_size_in_bytes < 128 << 20


@pytest.mark.parametrize("rows_padded,num_features,blocking", [
    (10_500_096, 28, (1, 28, 28)),
    (400_128, 2000, (63, 2016, 2000)),
    (2_270_208, 136, (5, 160, 136))], ids=["higgs", "epsilon", "mslr"])
def test_round_feature_rows(monkeypatch, rows_padded, num_features,
                            blocking):
    """The facts of the benchmark cells' widths (nothing is compiled):
    feature blocks, the feature rows they cover and the rows a pass's
    feature loops run over, which skip the last block's padding."""
    facts = _round_segment(monkeypatch, rows_padded, num_features)[2]
    assert facts["train.features"] == num_features
    assert facts["train.wave_width"] == 42
    assert (facts["train.feature_blocks"], facts["train.features_padded"],
            facts["train.feature_rows_looped"]) == blocking
    # every column of these tables uses the 255 bins
    assert facts["train.onehot_bin_share"] == 1.0


def test_narrow_minor_reader_sees_the_parents_buffer():
    line = ("%copy.342 = f32[84,2000,3,256]{2,3,1,0:T(8,128)} copy(%x), "
            "f32[84,3,2000,255]{3,2,1,0:T(8,128)} f32[8,3]{1,0:T(8,128)}")
    assert _narrow_minor_f32(line, 64 << 20) == [
        (84 * 2000 * 256 * 128 * 4, "f32[84,2000,3,256]{2,3,1,0:T(8,128)")]
