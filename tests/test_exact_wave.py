"""Exact-order wave growth (wave_tail="exact"): overgrow + strict replay.

The claim under test (models/tree.py _exact_prune): priority-first
extraction order over the realized gain tree equals descending pathmin
order, so pruning an overgrown wave tree to the top-(num_leaves-1)
expandable nodes by (pathmin desc, id asc) reproduces the STRICT grower's
tree exactly — the r4 gap decomposition showed split ORDER was the entire
residual quality gap of wave growth (PERF_HISTORY.md), so exactness here is the
north-star AUC-parity mechanism.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.models.spec import STRICT, WaveSchedule
from lightgbm_tpu.models.tree import grow_tree
from lightgbm_tpu.ops.lookup import lookup_values
from lightgbm_tpu.ops.split import SplitContext


def _ctx(min_data=20.0):
    return SplitContext(
        lambda_l1=jnp.float32(0.0), lambda_l2=jnp.float32(0.0),
        min_data_in_leaf=jnp.float32(min_data),
        min_sum_hessian=jnp.float32(1e-3),
        min_gain_to_split=jnp.float32(0.0), max_delta_step=jnp.float32(0.0),
        path_smooth=jnp.float32(0.0))


def _make(seed, n=20000, F=10, B=64):
    rng = np.random.default_rng(seed)
    X = rng.integers(0, B, (n, F)).astype(np.uint8)
    ylat = (X[:, 0] * 0.1 + np.sin(X[:, 1] * 0.3) + X[:, 2] * X[:, 3] * 0.01
            + rng.normal(0, 0.5, n))
    g = (0.0 - ylat).astype(np.float32)
    stats = jnp.stack([jnp.asarray(g), jnp.ones(n), jnp.ones(n)], axis=-1)
    return jnp.asarray(X), stats


def _splits(t):
    m = np.asarray(~t.is_leaf & (t.left >= 0))
    return sorted(zip(np.asarray(t.split_feature)[m].tolist(),
                      np.asarray(t.split_bin)[m].tolist()))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_exact_replay_matches_strict_grower(seed):
    """With full coverage (overgrow to 4x), the exact-mode tree is
    IDENTICAL to the strict grower's: same split multiset, same leaf
    count, same per-row leaf values."""
    nl, B = 31, 64
    bins, stats = _make(seed)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t_s, rl_s = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                          wave=STRICT, hist_impl="jnp")
    t_e, rl_e = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                          wave=WaveSchedule(16, "exact", 4 * nl),
                          hist_impl="jnp")
    assert int(t_s.num_leaves) == int(t_e.num_leaves) == nl
    assert _splits(t_s) == _splits(t_e)
    v_s = np.asarray(lookup_values(rl_s, t_s.leaf_value))
    v_e = np.asarray(lookup_values(rl_e, t_e.leaf_value))
    np.testing.assert_allclose(v_s, v_e, rtol=2e-4, atol=2e-6)


def test_exact_default_overgrow_near_strict():
    """At moderate (1.5x) overgrowth, coverage misses are rare: the
    split multiset differs from strict in at most a few tail splits.
    (The production default is 2.0x — gap-converged on-chip,
    PERF_HISTORY.md r5.)"""
    from lightgbm_tpu.models.spec import _exact_overgrow_target

    nl, B = 31, 64
    bins, stats = _make(0)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t_s, _ = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                       wave=STRICT, hist_impl="jnp")
    l_over = _exact_overgrow_target(nl, 16, 1.5)
    t_e, _ = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                       wave=WaveSchedule(16, "exact", l_over),
                       hist_impl="jnp")
    from collections import Counter

    s_s, s_e = _splits(t_s), _splits(t_e)
    common = sum((Counter(s_s) & Counter(s_e)).values())
    assert int(t_e.num_leaves) == nl
    assert common >= len(s_s) - 3, (s_s, s_e)


def test_exact_row_leaf_consistent():
    """row_leaf returned by exact mode routes every row to the leaf the
    pruned tree structure itself routes it to (remap through the
    overgrown frontier is coherent)."""
    nl, B = 31, 64
    bins, stats = _make(3)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t, rl = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                      wave=WaveSchedule(16, "exact", 47), hist_impl="jnp")
    via_rl = np.asarray(lookup_values(rl, t.leaf_value))
    # traverse the tree directly for every row
    sf = np.asarray(t.split_feature)
    sb = np.asarray(t.split_bin)
    lt = np.asarray(t.left)
    rt = np.asarray(t.right)
    lv = np.asarray(t.leaf_value)
    isl = np.asarray(t.is_leaf)
    Xb = np.asarray(bins)
    out = np.zeros(Xb.shape[0], np.float32)
    for i in range(Xb.shape[0]):
        nd = 0
        while not isl[nd]:
            nd = lt[nd] if Xb[i, sf[nd]] <= sb[nd] else rt[nd]
        out[i] = lv[nd]
    np.testing.assert_allclose(via_rl, out, rtol=1e-5, atol=1e-6)


def test_exact_respects_num_leaves_budget():
    """Exact mode never exceeds the leaf budget and its final capacity is
    the standard 2*num_leaves-1 (stackable into the forest)."""
    nl, B = 16, 32
    bins, stats = _make(5, n=5000, F=6, B=32)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    t, rl = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                      wave=WaveSchedule(8, "exact", 40), hist_impl="jnp")
    assert t.capacity == 2 * nl - 1
    assert int(t.num_leaves) <= nl
    assert int(np.asarray(rl).max()) < t.capacity


_WIDE_CAP = 526      # the wave boundary nearest 2 x 255 leaves at width 42

# (params, rows the tree grows on) -> the schedule the round is built with;
# each row names the round or PR that fixed it
_WAVE_TABLE = {
    "r5_large_data_exact":
        ({"objective": "binary", "num_leaves": 127}, 1 << 20,
         WaveSchedule(42, "exact", 274)),
    "r4_mid_size_pointwise_greedy":
        ({"objective": "regression", "num_leaves": 31}, 46_000,
         WaveSchedule(30, "greedy")),
    # ... only while the tree closes before the wave width binds: 255
    # leaves at 1,568 rows a leaf grow with the exact tail (PR 28: greedy
    # read a best-first excess of 0.12 and 0.37 there, limit 0.04)
    "pr28_width_binds_exact":
        ({"objective": "binary", "num_leaves": 255}, 400_128,
         WaveSchedule(42, "exact", _WIDE_CAP)),
    "pr28_tree_closes_in_width_greedy":
        ({"objective": "binary", "num_leaves": 43}, 400_128,
         WaveSchedule(42, "greedy")),
    "r5_ranking_exact":
        ({"objective": "lambdarank", "num_leaves": 63}, 100_000,
         WaveSchedule(42, "exact", 148)),
    "r5_explicit_tail_wins":
        ({"objective": "binary", "num_leaves": 127, "wave_tail": "greedy"},
         1 << 20, WaveSchedule(42, "greedy")),
    "r5_explicit_half":
        ({"objective": "binary", "num_leaves": 127, "wave_tail": "half",
          "wave_width": 16}, 1 << 20, WaveSchedule(16, "half")),
    "r1_leafwise_is_strict":
        ({"objective": "binary", "num_leaves": 127,
          "grow_policy": "leafwise"}, 1 << 20, STRICT),
    # r4's auto tail regimes (test_round4_fixes)
    "r4_diamonds_greedy":
        ({"objective": "regression", "num_leaves": 31}, 46_080,
         WaveSchedule(30, "greedy")),
    "r4_budget_saturating_small_exact":
        ({"objective": "regression", "num_leaves": 31}, 8_192,
         WaveSchedule(30, "exact", 62)),
    "r4_ranking_mid_exact":
        ({"objective": "lambdarank", "num_leaves": 63}, 100_096,
         WaveSchedule(42, "exact", 148)),
    "r4_ranking_any_size_exact":
        ({"objective": "lambdarank", "num_leaves": 63}, 1 << 22,
         WaveSchedule(42, "exact", 148)),
    "r4_large_binary_exact":
        ({"objective": "binary", "num_leaves": 127}, 1 << 20,
         WaveSchedule(42, "exact", 274)),
}


@pytest.mark.parametrize("case", sorted(_WAVE_TABLE))
def test_resolve_wave(case):
    """Default tails: exact for large/rank/small-saturating shapes, greedy
    only for mid-size pointwise tasks far from leaf-budget saturation
    (measured quality-neutral at the diamonds shape; greedy costs ~6e-2
    NDCG@10 on the MSLR bench); the exact tail's cap is wave-aligned and
    past ``num_leaves``."""
    import dataclasses

    from lightgbm_tpu.config import parse_params
    from lightgbm_tpu.models.spec import narrow_width_for, resolve_wave

    params, rows, want = _WAVE_TABLE[case]
    # the narrow phase's width is resolved with the width (PR 32): 16 under
    # every wave wider than that, none otherwise
    want = dataclasses.replace(want,
                               narrow_width=narrow_width_for(want.width))
    assert want.narrow_width == (16 if want.width > 16 else 0)
    got = resolve_wave(parse_params(params), rows)
    assert got == want and hash(got) == hash(want)
    if got.tail == "exact":
        nl = params["num_leaves"]
        assert nl < got.cap_leaves <= 2 * nl + 64


def test_exact_stalled_growth_no_ghost_leaves():
    """When splittable structure exhausts below num_leaves, unused table
    slots must NOT masquerade as leaves (their default parent is the
    root): leaf count, is_leaf sum, and reachability must stay coherent
    (code review r5)."""
    rng = np.random.default_rng(9)
    n = 4096
    # one informative binary feature -> the tree stalls after ~3 splits
    X = rng.integers(0, 2, (n, 3)).astype(np.uint8)
    g = (X[:, 0] * 2.0 - 1.0 + 0.01 * rng.normal(size=n)).astype(np.float32)
    stats = jnp.stack([jnp.asarray(g), jnp.ones(n), jnp.ones(n)], axis=-1)
    fmask = jnp.ones(3, jnp.float32)
    t, rl = grow_tree(jnp.asarray(X), stats, fmask, _ctx(min_data=1),
                      31, 4, -1, wave=WaveSchedule(16, "exact", 62),
                      hist_impl="jnp")
    n_leaves = int(t.num_leaves)
    isl = np.asarray(t.is_leaf)
    assert isl.sum() == n_leaves, (isl.sum(), n_leaves)
    # every is_leaf slot must be reachable from the root
    lt, rt = np.asarray(t.left), np.asarray(t.right)
    reach = {0}
    stack = [0]
    while stack:
        i = stack.pop()
        if lt[i] >= 0:
            reach.update((lt[i], rt[i]))
            stack.extend((lt[i], rt[i]))
    assert set(np.flatnonzero(isl)) <= reach
    assert set(np.unique(np.asarray(rl))) <= set(np.flatnonzero(isl))


def test_partition_fused_kernel_matches_unfused():
    """The partition-fused wave kernel (histogram + row routing in one
    pallas call, r5) must produce the same tree as the unfused path —
    same splits, same row routing — in every wave tail mode."""
    nl, B = 31, 64
    bins, stats = _make(4, n=12000, F=8)
    fmask = jnp.ones(bins.shape[1], jnp.float32)
    for wave in (WaveSchedule(16, "half"), WaveSchedule(16, "greedy"),
                 WaveSchedule(16, "exact", 48)):
        t_u, rl_u = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                              wave=wave, hist_impl="pallas",
                              hist_dtype="bf16", fuse_partition=False)
        t_f, rl_f = grow_tree(bins, stats, fmask, _ctx(), nl, B, -1,
                              wave=wave, hist_impl="pallas",
                              hist_dtype="bf16", fuse_partition=True)
        assert _splits(t_u) == _splits(t_f), wave
        np.testing.assert_array_equal(np.asarray(rl_u), np.asarray(rl_f),
                                      err_msg=str(wave))
        np.testing.assert_allclose(np.asarray(t_u.leaf_value),
                                   np.asarray(t_f.leaf_value),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# The certified replay (PR 29): the exact tail stops overgrowing once
# _replay_certified proves the replay is the strict best-first tree.
# ---------------------------------------------------------------------------


def _table(expanded_pm, leaves):
    """Hand-made packed node table: one expanded node per pathmin of
    ``expanded_pm``, one unexpanded leaf per ``(pathmin, cand_gain)``."""
    from lightgbm_tpu.models.tree import _PK as K, _empty_packed_table

    P = np.array(_empty_packed_table(2 * (len(expanded_pm) + len(leaves))))
    for i, pm in enumerate(expanded_pm):
        P[i, [K.LEFT, K.RIGHT, K.IS_LEAF, K.PM, K.CAND_GAIN]] = (
            1, 2, 0.0, pm, pm)
    for j, (pm, gain) in enumerate(leaves, start=len(expanded_pm)):
        P[j, [K.IS_LEAF, K.PM, K.CAND_GAIN]] = (1.0, pm, gain)
    return jnp.asarray(P)


_NEG = -np.inf


@pytest.mark.parametrize("name,expanded_pm,leaves,fires", [
    # T = 2.0 (the best unexpanded candidate's pathmin), num_leaves = 5
    ("exactly_num_leaves_minus_1_above", [9., 7., 5., 3., 1.],
     [(2., 4.), (.5, .5)], True),
    ("one_fewer", [9., 7., 5., 1.5, 1.], [(2., 4.), (.5, .5)], False),
    ("tie_with_T_is_not_counted", [9., 7., 5., 2., 1.],
     [(2., 4.), (.5, .5)], False),
    # a leaf that can never split has no say, whatever its pathmin reads
    ("dead_leaf_does_not_set_T", [9., 7., 5., 3.],
     [(8., _NEG), (2., 4.)], True),
    ("no_candidate_left", [9., 7., 5., 3.], [(8., _NEG), (2., _NEG)], True),
    ("all_stump", [], [(6., 6.)], False),
    ("all_stump_dead_root", [], [(_NEG, _NEG)], False),
])
def test_replay_certified_on_hand_made_tables(name, expanded_pm, leaves,
                                              fires):
    from lightgbm_tpu.models.tree import _replay_certified

    assert bool(_replay_certified(_table(expanded_pm, leaves), 5)) is fires


# PR 35: how many leaves the replay still needs expanded (the width of
# the next pass on the partition-fused path: tests/test_narrow_pass.py)


@pytest.mark.parametrize("name,expanded_pm,leaves,needed", [
    # num_leaves = 5: theta = the 4th largest pathmin among the expanded
    ("certified_needs_none", [9., 7., 5., 3., 1.], [(2., 4.), (.5, .5)], 0),
    ("one_fewer_needs_the_best_leaf", [9., 7., 5., 1.5, 1.],
     [(2., 4.), (.5, .5)], 1),
    ("tie_with_theta_is_needed", [9., 7., 5., 2., 1.],
     [(2., 4.), (.5, .5)], 1),
    ("all_above_theta", [9., 7., 5., 1., 1.], [(2., 4.), (1.5, 3.), (1., 1.)],
     3),
    ("dead_leaf_is_not_needed", [9., 7., 5., 3.], [(8., _NEG), (2., 4.)], 0),
    ("short_of_splits_every_candidate", [9., 7., 5.],
     [(8., _NEG), (2., 4.), (.5, .5)], 2),
    ("all_stump", [], [(6., 6.)], 1),
    ("all_stump_dead_root", [], [(_NEG, _NEG)], 0),
])
def test_replay_needed_on_hand_made_tables(name, expanded_pm, leaves, needed):
    from lightgbm_tpu.models.tree import _replay_needed

    assert int(_replay_needed(_table(expanded_pm, leaves), 5)) == needed


@pytest.mark.parametrize("seed", range(8))
def test_replay_needed_against_brute_force(seed):
    """Random node tables, pathmins from a handful of values so that ties
    with theta are common: the count equals a numpy sort's, and it is 0
    exactly when the table is certified or no leaf has a candidate (the
    loop stops on either), so the loop never asks for a pass with nothing
    to expand."""
    from lightgbm_tpu.models.tree import _replay_certified, _replay_needed

    rng = np.random.default_rng(seed)
    zeros = 0
    for _ in range(25):
        num_leaves = int(rng.integers(2, 9))
        expanded_pm = rng.integers(0, 7, rng.integers(0, 13)).astype(float)
        n_leaves = int(rng.integers(max(1, num_leaves // 2), 11))
        leaves = [(float(rng.integers(0, 7)),
                   float(rng.integers(0, 9)) if rng.random() < 0.7 else _NEG)
                  for _ in range(n_leaves)]
        P = _table(expanded_pm.tolist(), leaves)
        top = np.sort(expanded_pm)[::-1]
        theta = top[num_leaves - 2] if len(top) >= num_leaves - 1 else _NEG
        cands = [pm for pm, gain in leaves if np.isfinite(gain)]
        want = sum(pm >= theta for pm in cands)
        got = int(_replay_needed(P, num_leaves))
        assert got == want, (num_leaves, expanded_pm, leaves)
        certified = bool(_replay_certified(P, num_leaves))
        assert (got == 0) == (certified or not cands)
        assert not (certified and got)
        zeros += got == 0
    assert 0 < zeros < 25


def _rank_stats(seed, n_queries=300, docs=16, F=8, B=64):
    """Bin codes and lambdarank (grad, hess, 1) of a zero score."""
    from lightgbm_tpu.config import parse_params
    from lightgbm_tpu.ranking import LambdaRank

    rng = np.random.default_rng(seed)
    n = n_queries * docs
    X = rng.integers(0, B, (n, F)).astype(np.uint8)
    u = X[:, 0] * 0.05 + np.sin(X[:, 1] * 0.2) + rng.normal(0, 0.7, n)
    y = np.clip(np.floor((u - u.min()) / (np.ptp(u) + 1e-9) * 5), 0, 4)
    obj = LambdaRank(parse_params({"objective": "lambdarank"}))
    obj.set_group(np.full(n_queries, docs), y, n)
    g, h = obj.grad_hess(jnp.zeros(n, jnp.float32),
                         jnp.asarray(y, jnp.float32), jnp.ones(n, jnp.float32))
    return jnp.asarray(X), jnp.stack([g, h, jnp.ones(n)], axis=-1)


# name -> (bins, stats, ctx, num_leaves, width)
_IDENTITY_CASES = {
    # no leaf runs out of rows: every wave is full
    "dense": lambda: (*_make(0, n=20000, F=10), _ctx(), 63, 16),
    # PR 28's saturating repro (40,000 x 200: leaves of the doubling waves
    # with no split to offer) at a CPU size: growth ends under the cap
    "saturating": lambda: (*_make(2, n=4000, F=20), _ctx(min_data=60),
                           31, 8),
    # growth stalls under num_leaves: the certificate has no say
    "stalled": lambda: (*_make(1, n=4000, F=20), _ctx(min_data=200), 31, 8),
    "lambdarank": lambda: (*_rank_stats(3), _ctx(min_data=5), 31, 8),
}


def _grow_exact(case, monkeypatch, never_certified=False):
    """Exact-tail tree of a case, and the overgrown table its replay saw."""
    from lightgbm_tpu.models import tree as T
    from lightgbm_tpu.models.spec import _exact_overgrow_target

    bins, stats, ctx, nl, width = _IDENTITY_CASES[case]()
    cap = _exact_overgrow_target(nl, width, 2.0)
    seen = {}
    prune = T._exact_prune

    def spy(P, *args):
        seen["P"] = np.asarray(P)
        return prune(P, *args)

    with monkeypatch.context() as m:
        m.setattr(T, "_exact_prune", spy)
        if never_certified:
            m.setattr(T, "_replay_certified",
                      lambda P, num_leaves: jnp.bool_(False))
        t, rl = grow_tree(bins, stats, jnp.ones(bins.shape[1], jnp.float32),
                          ctx, nl, 64, -1,
                          wave=WaveSchedule(width, "exact", cap),
                          hist_impl="jnp")
    return t, np.asarray(rl), seen["P"], (bins, stats, ctx, nl, width, cap)


def _overgrown_leaves(P):
    from lightgbm_tpu.models.tree import _PK as K

    return int((P[:, K.IS_LEAF] > 0.5).sum())


@pytest.mark.parametrize("case", sorted(_IDENTITY_CASES))
def test_certified_stop_grows_the_same_tree(case, monkeypatch):
    """Stopping at the certificate changes nothing but the passes: the
    tree and every row's leaf equal, array for array, those of the loop
    that runs on to the cap (the helper patched to never fire); and where
    the table is certified, the splits are the strict grower's."""
    from lightgbm_tpu.models.tree import _replay_certified, tree_to_arrays

    t_c, rl_c, P_c, (bins, stats, ctx, nl, _, cap) = _grow_exact(
        case, monkeypatch)
    t_n, rl_n, P_n, _ = _grow_exact(case, monkeypatch, never_certified=True)
    assert _overgrown_leaves(P_c) <= _overgrown_leaves(P_n) <= cap
    a_c, a_n = tree_to_arrays(t_c), tree_to_arrays(t_n)
    assert a_c.keys() == a_n.keys()
    for field in a_c:
        np.testing.assert_array_equal(a_c[field], a_n[field], err_msg=field)
    np.testing.assert_array_equal(rl_c, rl_n)
    if bool(_replay_certified(jnp.asarray(P_c), nl)):
        t_s, rl_s = grow_tree(bins, stats,
                              jnp.ones(bins.shape[1], jnp.float32), ctx, nl,
                              64, -1, wave=STRICT, hist_impl="jnp")
        assert int(t_s.num_leaves) == int(t_c.num_leaves)
        assert _splits(t_s) == _splits(t_c)
        np.testing.assert_allclose(
            np.asarray(lookup_values(rl_s, t_s.leaf_value)),
            np.asarray(lookup_values(jnp.asarray(rl_c), t_c.leaf_value)),
            rtol=2e-4, atol=2e-6)
    else:
        assert case == "stalled"


def _passes_to(leaves, width):
    """Trips of the wave schedule (full waves) that end on ``leaves``."""
    n, cand, passes = 1, 1, 0
    while n < leaves:
        s = min(cand, width)
        n, cand, passes = n + s, min(2 * cand, n + s), passes + 1
    assert n == leaves, (n, leaves)
    return passes


def test_certified_stop_makes_fewer_passes(monkeypatch):
    """On a table whose waves are all full the certificate fires before
    the cap: the loop ends on an earlier wave boundary of the schedule."""
    _, _, P_c, (_, _, _, nl, width, cap) = _grow_exact("dense", monkeypatch)
    _, _, P_n, _ = _grow_exact("dense", monkeypatch, never_certified=True)
    assert _overgrown_leaves(P_n) == cap
    assert nl <= _overgrown_leaves(P_c) < cap
    assert (_passes_to(_overgrown_leaves(P_c), width)
            < _passes_to(cap, width))
