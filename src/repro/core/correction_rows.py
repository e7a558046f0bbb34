"""Destination-major row layouts: the condensed layers and the DEDUP-C
correction (DESIGN.md §2).

A sparse step ``y[dst] ⊕= x[src]`` as a scatter over raw (src, dst)
pairs costs a sort of the destination indices and a serial scatter in
every propagation step.  Here the pairs are laid out once, on the host,
by output row:

* the rows of one direction (destinations for a forward step, sources
  for a reverse one) are grouped into *width classes*; class ``c`` holds
  ``R_c`` rows of ``K_c`` entries;
* one ``(n_out,)`` int32 node map sends each output node to its row in
  the concatenated class outputs, and a node with no entries to an
  appended row that holds the zero.

:func:`row_slots` builds that grouping once for both users.  A layer's
:class:`RowLayout` is the indices alone, one flat int32 array a class:
row by row when ``K_c`` is a multiple of :data:`ROW_TILE`, else
slot-major (``K_c`` slabs of ``R_c`` rows, ``R_c`` rounded up to a
multiple of it), so that a gather of it reshapes to ``(R_c, K_c, B)`` or
``(K_c, R_c, B)`` without a copy.  Its pads point at a row of the
semiring's zero appended to the frontier, so it serves any semiring
(:func:`apply_layout`).  The correction's :class:`CorrectionRows` holds
``(R_c, K_c)`` indices and counts: pads point at node 0 with count 0,
and a row applies ``sum_k w[r, k] * x[idx[r, k]]``
(:func:`apply_correction`).  Either way a step is, per class, one gather
and one reduce, then one concatenation and one gather through the node
map: no sort, no scatter.

The class widths are ``1, 2, 3, 4, 6, 8, 12, ...`` (powers of two and
one and a half times them), so a row of ``m`` entries takes at most
``1.5 m`` slots and the whole layout at most 1.5× the entries.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .semiring import Semiring

__all__ = [
    "RowLayout",
    "CorrectionRows",
    "DeviceCorrection",
    "ROW_TILE",
    "class_width",
    "row_slots",
    "row_layout",
    "correction_rows",
    "upload_correction",
    "apply_layout",
    "apply_correction",
]


# The sublane tile of a float32 frontier on the TPU (see _row_major).
ROW_TILE = 8


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["idx", "node_row"],
    meta_fields=["widths"],
)
@dataclasses.dataclass
class RowLayout:
    """One direction of an unweighted layer, by output node.

    ``idx[c]``: class ``c``'s ``(K_c · R_c,)`` source indices, row-major
    (slot ``k`` of row ``r`` at ``r · K_c + k``) when ``K_c`` is a multiple
    of :data:`ROW_TILE`, else slot-major (at ``k · R_c + r``) with ``R_c``
    a multiple of it; pads, and the rows that round ``R_c`` up, point at
    ``n_in`` (the zero row :func:`apply_layout` appends to the frontier).
    ``widths[c]`` is ``K_c``.  ``node_row``: ``(n_out,)``, each output
    node's row in the concatenation of the class outputs; ``sum(R_c)`` (an
    appended zero row) for a node with no edges.  Host builds hold NumPy
    arrays, uploads JAX arrays."""

    idx: Tuple[jnp.ndarray, ...]
    node_row: jnp.ndarray
    widths: Tuple[int, ...]


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


def row_slots(src, dst, n_out: int) -> Tuple[List[np.ndarray], np.ndarray]:
    """Host: the rows of ``y[dst] ⊕= x[src]`` over ``n_out`` output nodes.

    Returns, per width class, the ``(R_c, K_c)`` positions of each row's
    pairs in ``src``/``dst`` (``-1`` for a pad), and the ``(n_out,)``
    int32 node map.  Each row lists its pairs in source order, so the
    layout depends on the set of pairs only, not on their order."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    span = int(src.max()) + 1 if src.size else 1
    order = np.argsort(dst * span + src, kind="stable")
    widths = np.bincount(dst, minlength=n_out).astype(np.int64)
    starts = np.cumsum(widths) - widths
    nodes = np.flatnonzero(widths)
    k_of = class_width(widths[nodes])
    slots = []
    node_row = np.empty(n_out, dtype=np.int32)
    offset = 0
    for k in np.unique(k_of):
        members = nodes[k_of == k]
        slot = np.arange(k)
        live = slot[None, :] < widths[members][:, None]
        take = np.where(live, starts[members][:, None] + slot[None, :], 0)
        slots.append(np.where(live, order[take], -1))
        node_row[members] = offset + np.arange(members.size, dtype=np.int32)
        offset += members.size
    empty = np.ones(n_out, dtype=bool)
    empty[nodes] = False
    node_row[empty] = offset
    return slots, node_row


def row_layout(src, dst, n_in: int, n_out: int) -> RowLayout:
    """Host: the layout that applies ``y[dst] ⊕= x[src]`` for ``n_in``
    sources and ``n_out`` outputs under any semiring (pads at ``n_in``)."""
    src = np.asarray(src, dtype=np.int64)
    slots, node_row = row_slots(src, dst, n_out)
    idx, widths = [], []
    old, new = [0], [0]
    for s in slots:
        r, k = s.shape
        if not _row_major(k):
            s = np.concatenate([s, np.full(((-r) % ROW_TILE, k), -1)])
        flat = np.where(s >= 0, src[s], n_in)
        idx.append((flat if _row_major(k) else flat.T).reshape(-1).astype(np.int32))
        widths.append(int(k))
        old.append(old[-1] + r)
        new.append(new[-1] + s.shape[0])
    # a node's row moves by the rounding rows of the classes before its own
    old, new = np.asarray(old), np.asarray(new)
    cls = np.searchsorted(old[1:], node_row, side="right")
    node_row = (node_row - old[cls] + new[cls]).astype(np.int32)
    return RowLayout(tuple(idx), node_row, tuple(widths))


def _row_major(k: int) -> bool:
    """Whether a layer's class of width ``k`` is stored row by row: its
    gathered ``(R·k, B)`` messages are ``(R, k, B)`` in place when ``k``
    is a whole number of sublane tiles, and slot-major ``(k, R, B)``, with
    ``R`` rounded up to one, otherwise."""
    return k % ROW_TILE == 0


def correction_rows(src, dst, weight, n: int) -> CorrectionRows:
    """Host: the row layout that applies ``y[dst] += weight * x[src]`` over
    ``n`` nodes: :func:`row_slots` plus the counts, pads at node 0 with
    count 0."""
    src = np.asarray(src, dtype=np.int64)
    weight = np.asarray(weight, dtype=np.float32)
    slots, node_row = row_slots(src, dst, n)
    idx = tuple(np.where(s >= 0, src[s], 0).astype(np.int32) for s in slots)
    wts = tuple(np.where(s >= 0, weight[s], 0).astype(np.float32) for s in slots)
    return CorrectionRows(idx, wts, node_row)


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


def apply_layout(
    layout: RowLayout, x: jnp.ndarray, semiring: Semiring
) -> jnp.ndarray:
    """``y[v] = ⊕_{u -> v} x[u]`` for an ``(n_in,)`` or ``(n_in, B)``
    frontier ``x``; an output node with no edges reads the semiring's
    zero."""
    return _apply_layout(layout, jnp.asarray(x), semiring.add_kind, semiring.zero)


_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


@partial(jax.jit, static_argnames=("add_kind", "zero"))
def _apply_layout(layout: RowLayout, x, add_kind: str, zero: float):
    # jitted so that an eager caller compiles the classes once, not per op
    fill = jnp.full((1,) + x.shape[1:], zero, dtype=x.dtype)
    padded = jnp.concatenate([x, fill])
    reduce = _REDUCE[add_kind]
    parts = []
    for idx, k in zip(layout.idx, layout.widths):
        msgs = _take(padded, idx)
        if _row_major(k):
            parts.append(reduce(msgs.reshape((-1, k) + x.shape[1:]), axis=1))
        else:
            parts.append(reduce(msgs.reshape((k, -1) + x.shape[1:]), axis=0))
    return _by_node(parts, fill, layout.node_row)


def _take(x, idx):
    """``x[idx]`` for in-bounds int32 ``idx``: the gather alone, with no
    select for negative indices."""
    dims = jax.lax.GatherDimensionNumbers(
        offset_dims=tuple(range(1, x.ndim)),
        collapsed_slice_dims=(0,),
        start_index_map=(0,),
    )
    return jax.lax.gather(
        x, idx[:, None], dims, (1,) + x.shape[1:],
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _by_node(parts, fill, node_row):
    """Each node's row of the class outputs, ``fill`` for a node with none."""
    out = jnp.concatenate(parts + [fill])
    return out.at[node_row].get(mode="promise_in_bounds")


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
    return _by_node(parts, jnp.zeros((1,) + x.shape[1:], dtype=x.dtype), rows.node_row)
