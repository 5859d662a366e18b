"""EFB bundles as the split scan and the row routing see them.

A bundled table's training columns hold MERGED codes
(``dataset.FeatureBundler``): in a column of several members, code 0 is
"every member at its default bin" and member ``f``'s other bins sit at
``[off_f, off_f + nb_f - 2]``, its default bin ``d_f`` squeezed out.  The
kernels histogram those columns; a tree still splits on ONE original
feature, as LightGBM's bundles do:

* the scan reads a MEMBER VIEW ``[..., 3, F, B]`` out of the bundle planes
  ``[..., 3, C, B]`` (:func:`member_view`): each member's non-default bins
  are its slice of its column, and its default bin is the node total less
  the rest (LightGBM's ``FixHistogram``).  With no conflicting row the view
  is the histogram the unbundled table would have given;
* a split ``(f, t)`` (go left iff ``f``'s own bin ``<= t``) routes a row by
  the code of ``f``'s column (:func:`split_route`): for ``t < d_f`` the left
  side is the range ``[off_f, off_f + t]``; for ``t >= d_f`` the rows at
  ``f``'s default go left, and the left side is the complement of ``f``'s
  range above ``t``.  :func:`go_left` is the one rule every grower and
  walker applies; a plain column is ``lo = 0, hi = t``, not inverted.

The tables are device arrays, operands of the programs that take them, so
every column order of one table runs one program.  ``None`` stands for a
table with no bundle: nothing is built and every caller keeps its plain
``code <= t``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np


class Members(NamedTuple):
    """Per original feature: where its bins sit among the merged codes."""

    col: jnp.ndarray    # i32[F] its training column
    off: jnp.ndarray    # i32[F] merged code of its first non-default bin
    nb: jnp.ndarray     # i32[F] its own bin count
    dflt: jnp.ndarray   # i32[F] its default bin; num_bins alone in a column

    @property
    def num_features(self) -> int:
        return self.col.shape[-1]


def member_tables(bundler, n_bins, num_bins: int) -> dict:
    """Host numpy tables of :class:`Members` for a ``FeatureBundler``."""
    n_bins = np.asarray(n_bins, np.int64)
    num_features = len(n_bins)
    col = np.zeros(num_features, np.int32)
    off = np.zeros(num_features, np.int32)
    dflt = np.full(num_features, num_bins, np.int32)
    for c, group in enumerate(bundler.groups):
        col[group] = c
        if len(group) > 1:
            off[group] = bundler.offsets[c]
            dflt[group] = bundler.default_bins[group]
    return dict(col=col, off=off, nb=n_bins.astype(np.int32), dflt=dflt)


def members_of(mapper, num_bins: int) -> Optional[Members]:
    """The device tables of a bin mapper's bundles; ``None`` without."""
    bundler = getattr(mapper, "bundler", None)
    if bundler is None:
        return None
    return Members(**{k: jnp.asarray(v) for k, v in member_tables(
        bundler, mapper.n_bins, num_bins).items()})


def member_view(planes: jnp.ndarray, m: Members) -> jnp.ndarray:
    """Bundle planes ``[..., 3, C, B]`` -> member view ``[..., 3, F, B]``.

    Each member's row is its column's row (one gather of whole rows),
    shifted left by its offset through ``log2 B`` static shifts, its
    default slot opened again and the bins past its count zeroed:
    element-wise work that fuses, no gather of single bins.  The default
    bin takes the node total less the member's other bins."""
    num_bins = planes.shape[-1]
    rows = jnp.take(planes, m.col, axis=-2)                  # [..., 3, F, B]
    total = jnp.sum(rows, axis=-1)                           # [..., 3, F]
    k = jnp.arange(num_bins, dtype=jnp.int32)

    def shift_left(x, s):
        pad = jnp.zeros(x.shape[:-1] + (s,), x.dtype)
        return jnp.concatenate([x[..., s:], pad], axis=-1)

    view = rows
    for b in range(max(num_bins - 1, 1).bit_length()):
        s = 1 << b
        if s >= num_bins:
            break
        on = ((m.off >> b) & 1)[:, None] > 0                 # [F, 1]
        view = jnp.where(on, shift_left(view, s), view)
    # own bin k: merged k below the default, k - 1 above it
    right = jnp.concatenate(
        [jnp.zeros(view.shape[:-1] + (1,), view.dtype), view[..., :-1]],
        axis=-1)
    d = m.dflt[:, None]
    view = jnp.where(k < d, view, jnp.where(k > d, right, 0.0))
    view = jnp.where(k < m.nb[:, None], view, 0.0)
    at_default = (k == d).astype(view.dtype)                 # [F, B]
    return view + at_default * (total - jnp.sum(view, axis=-1))[..., None]


def split_route(m: Optional[Members], feat, thr):
    """``(col, lo, hi, inv)`` of splits ``(feat, thr)`` (any shape): a row
    goes left iff ``go_left(code of col, lo, hi, inv)``."""
    if m is None:
        return feat, jnp.zeros_like(thr), thr, jnp.zeros_like(thr, bool)
    col, off = m.col[feat], m.off[feat]
    nb, d = m.nb[feat], m.dflt[feat]
    below = thr < d
    lo = jnp.where(below, off, off + thr)
    hi = jnp.where(below, off + thr, off + nb - 2)
    return col, lo.astype(thr.dtype), hi.astype(thr.dtype), ~below


def go_left(code, lo, hi, inv):
    """The routing rule of a split on a (bundle) column."""
    return ((code >= lo) & (code <= hi)) != inv
