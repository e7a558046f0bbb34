"""The DEDUP-C correction in a destination-major row layout (DESIGN.md §2).

Ring propagation subtracts ``D·x`` (or ``Dᵀ·x``) from the C-DUP result,
where ``D`` is the sparse correction of (src, dst, count) triples.  As a
scatter-add over raw triples that costs a sort of the destination
indices and a serial scatter in every propagation step.  Here the
triples are laid out once, at upload, by output row:

* the rows of one direction (destinations for ``D·x``, sources for
  ``Dᵀ·x``) are grouped into *width classes*; class ``c`` holds ``R_c``
  rows of ``K_c`` entries: ``(R_c, K_c)`` int32 gather indices and
  ``(R_c, K_c)`` float32 counts, pads pointing at node 0 with count 0;
* one ``(n,)`` int32 node map sends each node to its row in the
  concatenated class outputs, and a node with no triples to an appended
  zero row.

Applying it is, per class, ``sum_k w[r, k] * x[idx[r, k]]``, then one
concatenation and one gather through the node map: no sort, no scatter.

The class widths are ``1, 2, 3, 4, 6, 8, 12, ...`` (powers of two and
one and a half times them), so a row of ``m`` triples takes at most
``1.5 m`` slots and the whole layout at most 1.5× the triples.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CorrectionRows",
    "DeviceCorrection",
    "class_width",
    "correction_rows",
    "upload_correction",
    "apply_correction",
]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["idx", "weight", "node_row"],
    meta_fields=[],
)
@dataclasses.dataclass
class CorrectionRows:
    """One direction of the correction, row-major by output node.

    ``idx[c]`` / ``weight[c]``: class ``c``'s ``(R_c, K_c)`` gather indices
    and counts.  ``node_row``: ``(n,)``, each node's row in the
    concatenation of the class outputs; ``sum(R_c)`` (an appended zero
    row) for a node with no triples.  Host builds hold NumPy arrays,
    uploads JAX arrays."""

    idx: Tuple[jnp.ndarray, ...]
    weight: Tuple[jnp.ndarray, ...]
    node_row: jnp.ndarray


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["fwd", "rev"],
    meta_fields=[],
)
@dataclasses.dataclass
class DeviceCorrection:
    """The correction as a device graph holds it: ``fwd`` applies ``D·x``
    (rows by destination), ``rev`` applies ``Dᵀ·x`` (rows by source).
    ``rev`` is ``None`` when the triples are symmetric, so ``fwd`` serves
    both directions."""

    fwd: CorrectionRows
    rev: Optional[CorrectionRows] = None

    def rows(self, reverse: bool) -> CorrectionRows:
        return self.rev if reverse and self.rev is not None else self.fwd


def class_width(m: np.ndarray) -> np.ndarray:
    """The smallest class width ``>= m`` among ``1, 2, 3, 4, 6, 8, 12, ...``
    (``m >= 1``): ``2^k`` or ``3·2^(k-1)``, so under ``1.5 m``."""
    m = np.asarray(m, dtype=np.int64)
    p = np.left_shift(1, np.floor(np.log2(np.maximum(m, 1))).astype(np.int64))
    return np.where(m == p, p, np.where(2 * m <= 3 * p, (3 * p) // 2, 2 * p))


def correction_rows(src, dst, weight, n: int) -> CorrectionRows:
    """Host: the row layout that applies ``y[dst] += weight * x[src]`` over
    ``n`` nodes.  Each row lists its triples in source order, so the
    layout depends on the set of triples only, not on their order."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    weight = np.asarray(weight, dtype=np.float32)
    order = np.argsort(dst * max(int(n), 1) + src, kind="stable")
    cols = src[order].astype(np.int32)
    w = weight[order]
    widths = np.bincount(dst, minlength=n).astype(np.int64)
    starts = np.cumsum(widths) - widths
    nodes = np.flatnonzero(widths)
    k_of = class_width(widths[nodes])
    idx, wts = [], []
    node_row = np.empty(n, dtype=np.int32)
    offset = 0
    for k in np.unique(k_of):
        members = nodes[k_of == k]
        slot = np.arange(k)
        live = slot[None, :] < widths[members][:, None]
        take = np.where(live, starts[members][:, None] + slot[None, :], 0)
        idx.append(np.where(live, cols[take], 0).astype(np.int32))
        wts.append(np.where(live, w[take], 0).astype(np.float32))
        node_row[members] = offset + np.arange(members.size, dtype=np.int32)
        offset += members.size
    empty = np.ones(n, dtype=bool)
    empty[nodes] = False
    node_row[empty] = offset
    return CorrectionRows(tuple(idx), tuple(wts), node_row)


def _same(a: CorrectionRows, b: CorrectionRows) -> bool:
    return (
        len(a.idx) == len(b.idx)
        and np.array_equal(a.node_row, b.node_row)
        and all(np.array_equal(x, y) for x, y in zip(a.idx, b.idx))
        and all(np.array_equal(x, y) for x, y in zip(a.weight, b.weight))
    )


def upload_correction(src, dst, weight, n: int) -> DeviceCorrection:
    """Build both directions' layouts from host triples and upload them;
    one layout serves both when the triples are symmetric."""
    fwd = correction_rows(src, dst, weight, n)
    rev = correction_rows(dst, src, weight, n)
    if _same(fwd, rev):
        rev = None
    return jax.tree_util.tree_map(jnp.asarray, DeviceCorrection(fwd, rev))


def apply_correction(
    corr: DeviceCorrection, x: jnp.ndarray, reverse: bool = False
) -> jnp.ndarray:
    """``D·x`` (``Dᵀ·x`` when ``reverse``) for an ``(n,)`` or ``(n, B)``
    frontier ``x``."""
    return _apply_rows(corr.rows(reverse), jnp.asarray(x))


@jax.jit
def _apply_rows(rows: CorrectionRows, x: jnp.ndarray) -> jnp.ndarray:
    # jitted so that an eager caller compiles the classes once, not per op
    parts = []
    for idx, w in zip(rows.idx, rows.weight):
        msgs = x.at[idx].get(mode="promise_in_bounds")
        w = w.astype(x.dtype).reshape(w.shape + (1,) * (x.ndim - 1))
        parts.append(jnp.sum(msgs * w, axis=1))
    parts.append(jnp.zeros((1,) + x.shape[1:], dtype=x.dtype))
    out = jnp.concatenate(parts)
    return out.at[rows.node_row].get(mode="promise_in_bounds")
