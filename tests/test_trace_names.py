"""Names the trace can read: every Pallas kernel of the default round
carries a role name, every stage of the round a ``jax.named_scope`` of one
vocabulary in both growers, and names are metadata: the trees are the
parent commit's, bit for bit."""

import hashlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt
from lightgbm_tpu.models.spec import resolve_grow_spec
from lightgbm_tpu.models.tree import HIST_NARROW, HIST_ROOT, HIST_WAVE

LEAVES, ROWS, FEATURES = 127, 6144, 10
ROUND = ["lgbtpu.grad", "lgbtpu.root", "lgbtpu.wave.hist",
         "lgbtpu.wave.scan", "lgbtpu.pred_update"]
WAVE = ["lgbtpu.wave.rank", "lgbtpu.wave.sibling", "lgbtpu.wave.commit",
        "lgbtpu.replay"]
# grow_policy -> the stage scopes of its default round (the strict grower's
# split-iteration kernel picks, scans and commits in one call: its round
# has no rank or commit stage of XLA's, and no sibling or replay at all)
GROWERS = {"frontier": ROUND + WAVE, "leafwise": ROUND}
# sha256 of json.dumps(dump_model(), sort_keys=True) after the rounds below,
# taken at the parent commit (f688a13) on the CPU: names and scopes may not
# move a split, a threshold, a count or a leaf value
PARENT_DIGEST = {
    "frontier": (3, "08fd86cdb7d8a73343b858042cce6c32"
                    "bd85db45bc8749dde716e743e82051fc"),
    "leafwise": (2, "8e13121fc903c99963e3018b9ae85a24"
                    "30790bc949f9c9e05f6e50ba04ebd224"),
}


def _params(policy):
    return {"objective": "binary", "num_leaves": LEAVES, "verbosity": -1,
            "min_data_in_leaf": 1, "grow_policy": policy}


def _round(policy):
    """The one-round program ``update_many`` dispatches and its operands
    as shapes, at the default settings of a 127-leaf binary booster (a
    fresh ``jit`` per test: a trace made under one backend answer must not
    be found again under the other)."""
    p = lgb.config.parse_params(_params(policy))
    obj = gbdt.create_objective(p)
    fn = gbdt._multi_round_fn.__wrapped__(
        gbdt._objective_static_key(obj, p), resolve_grow_spec(p, ROWS, 255),
        False, 1, 0, False)
    S = jax.ShapeDtypeStruct
    rows, key = S((ROWS,), jnp.float32), S((2,), jnp.uint32)
    hyper = jax.tree.map(lambda x: S(jnp.shape(x), jnp.asarray(x).dtype),
                         gbdt.HyperScalars.from_params(p))
    scalar = S((), jnp.float32)
    return fn, (S((ROWS, FEATURES), jnp.uint8), rows, rows, rows, rows,
                hyper, key, key, key, rows, scalar, S((), jnp.int32),
                scalar, scalar)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("policy", list(GROWERS))
def test_round_kernels_carry_role_names(policy, monkeypatch):
    """Interpret mode leaves no custom call to read, so read the jaxpr of
    the round as the chip would trace it (the backend question answered
    ``tpu`` for the trace only; nothing is lowered or run)."""
    fn, args = _round(policy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    names = [e.params["name"] for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert HIST_ROOT in names and HIST_WAVE in names
    assert all(n and n.startswith("lgbtpu_") for n in names), names
    if policy == "leafwise":        # the split-iteration kernel by its own
        assert "lgbtpu_split_iter" in names
    else:       # ... and the doubling passes under theirs (PR 32)
        assert set(names) == {HIST_ROOT, HIST_NARROW, HIST_WAVE}


@pytest.mark.parametrize("policy", list(GROWERS))
def test_round_stages_are_named_scopes(policy):
    """The name stack of an equation is what the compiled instruction
    carries as ``metadata={op_name=...}`` (the lowered text's locations
    are cut short by the compile-cache rule, ``utils/compile_cache.py``)."""
    fn, args = _round(policy)
    stacks = {str(e.source_info.name_stack)
              for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)}
    found = {part for st in stacks for part in st.split("/")
             if part.startswith("lgbtpu.")}
    assert found == set(GROWERS[policy])


def as_the_parent_dumped(node):
    """A dump as the commits before the missing-value fields wrote it:
    every split ``default_left: true`` and no ``missing_type``.  The
    trees and values are compared; the two fields are
    ``tests/test_efb_members.py``'s."""
    if isinstance(node, dict):
        out = {k: as_the_parent_dumped(v) for k, v in node.items()
               if not ("split_index" in node and k == "missing_type")}
        if "split_index" in node:
            out["default_left"] = True
        return out
    if isinstance(node, list):
        return [as_the_parent_dumped(v) for v in node]
    return node


@pytest.mark.parametrize("policy", list(GROWERS))
def test_names_move_no_tree(policy):
    rounds, digest = PARENT_DIGEST[policy]
    rng = np.random.default_rng(11)
    X = rng.normal(size=(6000, FEATURES)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=6000)
         > 0).astype(np.float32)
    booster = lgb.Booster(_params(policy), lgb.Dataset(X, label=y))
    booster.update_many(rounds)
    dump = json.dumps(as_the_parent_dumped(booster.dump_model()),
                      sort_keys=True)
    assert hashlib.sha256(dump.encode()).hexdigest() == digest
