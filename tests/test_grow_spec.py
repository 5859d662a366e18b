"""``models/spec.py``: one GrowSpec, resolved once, and the one place it is
mapped onto the grower."""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import parse_params
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.models.spec import (STRICT, GrowSpec, WaveSchedule,
                                      resolve_grow_spec, resolve_hist_dtype)


def _booster(extra=None, n=4608, f=6):
    rng = np.random.default_rng(0)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    params = dict(objective="binary", num_leaves=16, max_bin=31,
                  verbosity=-1, **(extra or {}))
    return lgb.Booster(params, lgb.Dataset(X, label=y))


@pytest.mark.parametrize("params,rows,want", [
    ({}, 1 << 19, "bf16"),                      # auto, histogram-bound
    ({}, (1 << 19) - 256, "f32"),               # auto, small: hi/lo split
    ({"hist_dtype": "f32"}, 1 << 20, "f32x"),   # explicit = exactness
    ({"hist_dtype": "int8"}, 4096, "int8"),
    ({"use_quantized_grad": True, "hist_dtype": "f32"}, 4096, "bf16"),
])
def test_resolve_hist_dtype(params, rows, want):
    p = parse_params(dict(objective="binary", **params))
    assert resolve_hist_dtype(p, rows) == want
    assert resolve_grow_spec(p, rows, 255).hist_dtype == want


def test_resolve_grow_spec_reads_every_static_once():
    p = parse_params({"objective": "binary", "num_leaves": 63,
                      "hist_impl": "jnp", "row_chunk": 4096,
                      "extra_trees": True, "feature_fraction_bynode": 0.5,
                      "wave_tail": "greedy", "wave_width": 8})
    spec = resolve_grow_spec(p, 1 << 20, 64, cat_key=((1,), 10.0, 10.0, 32),
                             nbins_key=(64, 7))
    assert spec == GrowSpec(
        63, 64, hist_impl="jnp", row_chunk=4096, hist_dtype="bf16",
        wave=WaveSchedule(8, "greedy"), cat_key=((1,), 10.0, 10.0, 32),
        nbins_key=(64, 7), extra_trees=True, bynode_off=False)
    assert hash(spec) == hash(dataclasses.replace(spec))
    # what a test writes: everything after num_bins has the builders' default
    assert GrowSpec(15, 16) == GrowSpec(15, 16, "auto", 131072, "f32", STRICT,
                                        None, None, None, None, False, False)


def test_fused_segment_finds_its_program_again():
    """Two dispatches of one booster ask the memoised spec and find ONE
    program: equal specs hash equal, so ``compiles_in_window`` stays 0."""
    b = _booster()
    fn1, _ = b._fused_segment(1)
    misses = gbdt._multi_round_fn.cache_info().misses
    spec = b._grow_spec(int(b.train_set.row_mask.shape[0]))
    fn2, _ = b._fused_segment(1)
    assert fn2 is fn1
    assert gbdt._multi_round_fn.cache_info().misses == misses
    assert b._grow_spec(int(b.train_set.row_mask.shape[0])) is spec
    # a second booster of the same params resolves an EQUAL spec
    fn3, _ = _booster()._fused_segment(1)
    assert fn3 is fn1


def test_reset_parameter_drops_the_memoised_spec():
    """A reset ``hist_dtype`` or ``feature_fraction_bynode`` takes effect
    on the next round, as when every call re-read ``self.params``; a reset
    traced scalar resolves an equal spec and keeps the program."""
    b = _booster()
    rows = int(b.train_set.row_mask.shape[0])
    fn1, _ = b._fused_segment(1)
    before = b._grow_spec(rows)
    assert (before.hist_dtype, before.bynode_off) == ("f32", True)
    b.reset_parameter({"learning_rate": 0.05})
    assert b._grow_spec(rows) == before and b._grow_spec(rows) is not before
    assert b._fused_segment(1)[0] is fn1
    b.reset_parameter({"hist_dtype": "bf16"})
    assert b._grow_spec(rows).hist_dtype == "bf16"
    assert b._fused_segment(1)[0] is not fn1
    b.reset_parameter({"feature_fraction_bynode": 0.5})
    assert b._grow_spec(rows).bynode_off is False
    b.update()                       # the per-round path asks the same memo
    assert b.num_trees() == 1


def test_goss_spec_is_memoised_per_effective_rows():
    """GOSS grows on its ``k_top + k_other`` sample: the spec is asked for
    THAT row count (4,608 rows would grow in waves, 1,382 grow strict)."""
    b = _booster({"boosting": "goss"})
    n = b.train_set.num_data_
    k = int(0.2 * n) + int(0.1 * n)
    b.update()
    assert set(b._grow_specs) == {k}
    assert b._grow_spec(k).wave == STRICT
    assert b._grow_spec(int(b.train_set.row_mask.shape[0])).wave.width == 15


def test_dart_round_is_the_plain_round_program():
    """``_dart_round`` and ``update`` spell ``_round_fn``'s arguments alike,
    so a dart booster finds the program a gbdt booster with a valid set
    built (``lru_cache`` keys on the call as written)."""
    rng = np.random.default_rng(1)
    Xv = rng.standard_normal((256, 6)).astype(np.float32)
    plain = _booster()
    plain.add_valid(lgb.Dataset(Xv, label=(Xv[:, 0] > 0).astype(np.float32),
                                reference=plain.train_set), "v")
    plain.update()
    misses = gbdt._round_fn.cache_info().misses
    _booster({"boosting": "dart"}).update()
    assert gbdt._round_fn.cache_info().misses == misses


@pytest.mark.parametrize("wave", [STRICT, WaveSchedule(4, "half"),
                                  WaveSchedule(4, "exact", 24)],
                         ids=lambda w: w.tail)
def test_grower_from_spec_is_the_explicit_call(wave):
    """The one mapping of a spec onto ``grow_tree``: same tree as the call
    written out, constraint arrays and the static bynode skip included."""
    from lightgbm_tpu.models.tree import (grow_tree, grower_from_spec,
                                          tree_to_arrays)
    from lightgbm_tpu.ops.split import SplitContext

    rng = np.random.default_rng(2)
    n, f, bins_n = 2048, 5, 16
    bins = jnp.asarray(rng.integers(0, bins_n, (n, f)).astype(np.uint8))
    g = jnp.asarray((np.asarray(bins[:, 0]) * 0.2 - np.asarray(bins[:, 1])
                     * 0.1 + rng.normal(0, 0.3, n)).astype(np.float32))
    stats = jnp.stack([g, jnp.ones(n), jnp.ones(n)], axis=-1)
    fmask = jnp.ones(f, jnp.float32)
    ctx = SplitContext(jnp.float32(0.0), jnp.float32(1.0), jnp.float32(5.0),
                       jnp.float32(1e-3), jnp.float32(0.0))
    mono = (1, -1, 0, 0, 0)
    spec = GrowSpec(12, bins_n, hist_impl="jnp", wave=wave, mono_key=mono,
                    bynode_off=True)
    t_spec, rl_spec, _ = grower_from_spec(spec)(
        bins, stats, fmask, ctx, -1, jnp.float32(0.5), None)
    t_call, rl_call = grow_tree(
        bins, stats, fmask, ctx, 12, bins_n, -1, ff_bynode=None,
        hist_impl="jnp", wave=wave, mono=jnp.asarray(mono, jnp.int32))
    a, b = tree_to_arrays(t_spec), tree_to_arrays(t_call)
    for field in a:
        np.testing.assert_array_equal(a[field], b[field], err_msg=field)
    np.testing.assert_array_equal(np.asarray(rl_spec), np.asarray(rl_call))
