"""Device-side propagation engine over graph representations.

Implements the paper's ``getNeighbors``-driven execution model as bulk
semiring propagation (DESIGN.md §2).  One call to :func:`propagate`
computes, for every vertex at once,

    y[v] = ⊕_{u -> v}  x[u] ⊗ w(u, v)

on any representation:

* ``DeviceExpanded``   — EXP: one segment-reduce over the expanded edges.
* ``DeviceCondensed``  — C-DUP / DEDUP-1: one segment-reduce per condensed
  layer (the 2-hop factorized SpMV, ``y = B_out^T (B_in^T x)``); path
  multiplicity is counted by ring semirings and ignored by idempotent ones.
* ``DevicePacked``     — the same condensed semantics with each layer also
  carried as a bit-packed block-sparse incidence so batched ring
  propagation feeds the MXU-aligned Pallas SpMM (DESIGN.md §6).
* correction structure — DEDUP-C: C-DUP propagation minus a sparse
  correction term makes ring propagation exact without rewriting edges;
  the term is applied from a destination-major row layout
  (:mod:`repro.core.correction_rows`), with no scatter.

**Batched frontiers** (DESIGN.md §3): ``x`` may be a single ``(n,)``
vector or an ``(n, B)`` matrix of ``B`` independent frontiers (multi-source
BFS, per-user personalized PageRank, ...).  Every semiring step then runs
as one factorized SpMM ``Y = B_out^T (B_in^T X)`` — per-column results are
identical to ``B`` single-vector calls, and the batch axis is annotated
with the ``graph_batch`` logical axis for mesh sharding.

All arrays are JAX; graph containers are registered pytrees so jitted
algorithms take them as arguments.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import TYPE_CHECKING, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..distributed.sharding import shard_frontier
from .condensed import BipartiteEdges, CondensedGraph, ExpandedGraph
from .correction_rows import DeviceCorrection, apply_correction, upload_correction
from .semiring import PLUS_TIMES, Semiring, kernelizable, segment_reduce

if TYPE_CHECKING:  # pragma: no cover - typing only
    from ..kernels.autotune import CrossoverTable
    from .dedup import StreamedCorrection

__all__ = [
    "DeviceBipartite",
    "DeviceExpanded",
    "DeviceCondensed",
    "PackedOperands",
    "FusedOperands",
    "DevicePackedLayer",
    "DevicePacked",
    "DeviceGraph",
    "Correction",
    "ResidencyBudget",
    "ResidencyError",
    "device_graph_bytes",
    "graph_shape_signature",
    "to_device",
    "to_device_packed",
    "with_graph_version",
    "propagate",
    "propagate_wedge",
]

# Trace-time evidence that a propagation step dispatched to the Pallas
# kernel instead of the XLA segment path (asserted by no-fallback tests
# and reported by benchmarks).  Incremented per layer step at dispatch.
KERNEL_DISPATCH_COUNT = 0

# Trace-time evidence of fused-epilogue stand-downs: every time a ring
# propagation over a corrected DevicePacked considers the fused DEDUP-C
# path and declines, the machine-readable reason from
# :func:`_fused_applicable` is counted here (dispatch-honesty tests pin
# these instead of guessing from timings).  Reset together with the
# dispatch count.
KERNEL_STANDDOWN_COUNT: dict = {}

# Trace-time evidence of which path applied the DEDUP-C correction in
# each traced ring propagation: ``'rows'`` (the row layout,
# :func:`~repro.core.correction_rows.apply_correction`) or ``'fused'``
# (the Pallas epilogue).  Reset together with the dispatch count.
CORRECTION_EPILOGUE_COUNT: dict = {}

# Trace-time count of interior layer steps: those whose input and output
# are both virtual layers (a chain of two or more virtual layers has
# them; each runs under the ``engine.interior`` device scope).  Reset
# together with the dispatch count.
INTERIOR_STEP_COUNT = 0


def reset_kernel_dispatch_count() -> None:
    global KERNEL_DISPATCH_COUNT, INTERIOR_STEP_COUNT
    KERNEL_DISPATCH_COUNT = 0
    INTERIOR_STEP_COUNT = 0
    KERNEL_STANDDOWN_COUNT.clear()
    CORRECTION_EPILOGUE_COUNT.clear()

# A DEDUP-C correction as the engine accepts it: the plain (src, dst,
# count) triples from build_correction, or the StreamedCorrection wrapper
# from build_correction_streaming (accounting rides along; the arrays are
# identical).  Anything that unpacks into three host arrays works.
Correction = Union[
    Tuple[np.ndarray, np.ndarray, np.ndarray], "StreamedCorrection"
]


def _correction_triples(
    correction: Optional[Correction],
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    if correction is None:
        return None
    cs, cd, cm = correction
    return cs, cd, cm


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst"],
    meta_fields=["n_src", "n_dst"],
)
@dataclasses.dataclass
class DeviceBipartite:
    src: jnp.ndarray
    dst: jnp.ndarray
    n_src: int
    n_dst: int


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "weight"],
    meta_fields=["n", "graph_version"],
)
@dataclasses.dataclass
class DeviceExpanded:
    """EXP: unique edges with multiplicity weights (1 after dedup).

    ``graph_version`` is the :class:`repro.core.delta.GraphVersion` of
    the extraction this upload came from (DESIGN.md §9).  It rides in the
    pytree *meta*, so it participates in jit static hashing: any compiled
    executable and donated/cached operand is keyed on it, and a version
    bump invalidates them all by construction.
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    weight: jnp.ndarray  # float multiplicities; all-ones when deduplicated
    n: int
    graph_version: int = 0


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["chains", "direct", "correction", "diag_mult"],
    meta_fields=["n_real", "deduplicated", "graph_version"],
)
@dataclasses.dataclass
class DeviceCondensed:
    """C-DUP / DEDUP-1 / DEDUP-C on device.

    ``chains``      list of chains; each chain a tuple of DeviceBipartite.
    ``direct``      optional real->real edges (may repeat = multiplicity).
    ``correction``  optional DEDUP-C correction in its row layout
                    (:class:`~repro.core.correction_rows.DeviceCorrection`);
                    when present, ring propagation subtracts it.
    ``diag_mult``   per-node count of self paths (subtracted by ring
                    propagation so self-loops never contribute).
    ``deduplicated``True when path multiplicity is structurally 1
                    (DEDUP-1 output), so ring propagation is exact as-is.
    ``graph_version`` source graph's delta version (DESIGN.md §9); static
                    pytree meta, so a bump invalidates every compiled
                    executable / cached operand keyed on this graph.
    """

    chains: Tuple[Tuple[DeviceBipartite, ...], ...]
    direct: Optional[DeviceBipartite]
    correction: Optional[DeviceCorrection]
    diag_mult: Optional[jnp.ndarray]
    n_real: int
    deduplicated: bool
    graph_version: int = 0


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["slot_src", "slot_row", "row_start", "row_count", "bitmaps"],
    meta_fields=["crossover"],
)
@dataclasses.dataclass
class PackedOperands:
    """One direction's streamed-slot kernel operands (see
    :class:`repro.kernels.pack.BlockSparseBitmap` for the layout).

    ``crossover`` is the measured-crossover dispatch table recorded at
    pack time (``to_device_packed(..., measure=True)``); it is a frozen
    hashable value riding in the pytree *meta* (it steers trace-time
    dispatch, so it must participate in jit static hashing).  ``None``
    means unmeasured: 'auto' falls back to the footprint formula.
    """

    slot_src: jnp.ndarray   # (n_slots,) int32
    slot_row: jnp.ndarray   # (n_slots,) int32
    row_start: jnp.ndarray  # (n_rt,) int32
    row_count: jnp.ndarray  # (n_rt,) int32
    bitmaps: jnp.ndarray    # (n_slots, WORDS, TILE) uint32
    crossover: Optional["CrossoverTable"] = None


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "kind", "main_src", "corr_src", "main_idx", "corr_idx",
        "slot_row", "row_start", "row_count", "bitmaps", "planes",
    ],
    meta_fields=["plane_weights", "n_h_pad", "n_x_pad", "n_out", "n_out_pad"],
)
@dataclasses.dataclass
class FusedOperands:
    """Operands of the fused last-layer-SpMM + DEDUP-C-epilogue kernel
    (:func:`repro.kernels.bitmap_spmm.bitmap_spmm_fused_pallas`): the
    interleaved main/correction slot stream built by
    :func:`repro.kernels.correction.build_fused_stream`, the main layer's
    bitmaps, and the correction's bit-planes.  ``n_h_pad`` / ``n_x_pad``
    are the padded row counts of the two streamed feature operands (the
    last hidden frontier and the original input)."""

    kind: jnp.ndarray       # (n_slots,) int32 — 0 main, 1 correction
    main_src: jnp.ndarray   # (n_slots,) int32
    corr_src: jnp.ndarray   # (n_slots,) int32
    main_idx: jnp.ndarray   # (n_slots,) int32
    corr_idx: jnp.ndarray   # (n_slots,) int32
    slot_row: jnp.ndarray   # (n_slots,) int32
    row_start: jnp.ndarray  # (n_rt,) int32
    row_count: jnp.ndarray  # (n_rt,) int32
    bitmaps: jnp.ndarray    # (n_main, WORDS, TILE) uint32
    planes: jnp.ndarray     # (n_corr, P, WORDS, TILE) uint32
    plane_weights: Tuple[float, ...]
    n_h_pad: int
    n_x_pad: int
    n_out: int
    n_out_pad: int


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "fwd", "rev"],
    meta_fields=["n_src", "n_dst", "n_src_pad", "n_dst_pad"],
)
@dataclasses.dataclass
class DevicePackedLayer:
    """One condensed layer in COO plus bit-packed streamed-slot form.

    ``src``/``dst`` drive the segment-reduce path (any semiring, any
    direction).  ``fwd`` is the dst-major packed incidence
    (:mod:`repro.kernels.pack`) consumed by the Pallas SpMM for batched
    forward propagation; ``rev`` packs the transposed incidence so
    ``reverse=True`` steps (HITS, out-degrees) dispatch to the kernel
    too.  Either is ``None`` when the layer is not packable (duplicate
    edges, e.g. multiplicity-carrying direct edges).
    """

    src: jnp.ndarray
    dst: jnp.ndarray
    fwd: Optional[PackedOperands]
    rev: Optional[PackedOperands]
    n_src: int
    n_dst: int
    n_src_pad: int
    n_dst_pad: int


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "chains", "direct", "correction", "diag_mult",
        "fused_fwd", "fused_rev",
    ],
    meta_fields=[
        "n_real", "deduplicated", "backend", "feature_block",
        "graph_version", "fused_standdown",
    ],
)
@dataclasses.dataclass
class DevicePacked:
    """A :class:`DeviceCondensed` whose layers carry packed SpMM operands.

    Identical propagation semantics; batched (``(n, B)``) steps under any
    kernelizable semiring (plus-times, min-plus, max-times, or-and), in
    either direction, are dispatched to :func:`repro.kernels.bitmap_spmm.
    bitmap_spmm_pallas` per layer when ``backend`` resolves to Pallas
    (DESIGN.md §6).  ``backend``: ``'pallas'`` | ``'xla'`` | ``'auto'``
    (the measured-crossover table recorded at pack time when present,
    else Pallas on TPU when the streamed working set fits VMEM — XLA
    segment-reduce otherwise).

    ``fused_fwd`` / ``fused_rev`` carry the fused last-layer +
    DEDUP-C-epilogue operands (one per direction) when the graph has a
    correction; ring propagation then runs the subtraction inside the
    kernel instead of applying the row layout after the last layer.  When
    they could *not* be built, ``fused_standdown`` records the
    machine-readable pack-time reason (``''`` when built; e.g.
    ``'unpackable_last_layer'`` — see :func:`_build_fused`), so
    dispatch-honesty tests pin why a graph stood down instead of
    guessing.  Further trace-time stand-downs
    (1-D frontier, non-ring semiring, ``hop_weight``) are counted per
    reason in :data:`KERNEL_STANDDOWN_COUNT`.

    ``graph_version`` is the source graph's delta version (DESIGN.md §9):
    static pytree meta, so a version bump invalidates every compiled
    executable and cached packed operand keyed on this graph.
    """

    chains: Tuple[Tuple[DevicePackedLayer, ...], ...]
    direct: Optional[DevicePackedLayer]
    correction: Optional[DeviceCorrection]
    diag_mult: Optional[jnp.ndarray]
    n_real: int
    deduplicated: bool
    backend: str
    feature_block: int
    fused_fwd: Optional[FusedOperands] = None
    fused_rev: Optional[FusedOperands] = None
    graph_version: int = 0
    fused_standdown: str = ""


DeviceGraph = Union[DeviceExpanded, DeviceCondensed, DevicePacked]


# ---------------------------------------------------------------------------
# Residency accounting and version-keyed dispatch (DESIGN.md §10)
# ---------------------------------------------------------------------------

def with_graph_version(graph: DeviceGraph, version: int) -> DeviceGraph:
    """The same device graph stamped with a different delta version.

    ``graph_version`` is static pytree metadata (it invalidates compiled
    executables by changing the jit cache key), so two stamps of the same
    arrays are distinct trace keys.  The serving tier uses this both ways:
    re-stamping an upload after :meth:`~repro.core.delta.LiveGraph.
    apply_delta`, and *normalizing* the version to 0 before dispatching a
    cached executable — staleness is enforced by the version-keyed result
    cache at admission, so the executable itself may be shared by every
    version (and every tenant) with the same shape signature."""
    return dataclasses.replace(graph, graph_version=int(version))


def device_graph_bytes(graph: DeviceGraph) -> int:
    """Device bytes held by one uploaded graph: the sum over every pytree
    leaf (edge arrays, packed bitmaps, fused operand streams, the
    correction's row layout).  This is the unit the serving tier's
    :class:`ResidencyBudget` charges per resident tenant."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(graph):
        nbytes = getattr(leaf, "nbytes", None)
        total += int(nbytes) if nbytes is not None else np.asarray(leaf).nbytes
    return total


def graph_shape_signature(graph: DeviceGraph) -> str:
    """Hashable signature of a device graph's *compiled shape*: the pytree
    structure (version normalized to 0) plus every leaf's shape and dtype.

    Two graphs with equal signatures produce identical jit trace keys, so
    a compiled propagation executable for one serves the other without
    re-tracing — the key of the serving tier's executable cache
    ``(kind, bucket, signature)`` (DESIGN.md §10).  The signature excludes
    ``graph_version`` on purpose: version churn under a live delta stream
    must not churn executables (staleness lives in the result cache)."""
    import hashlib

    leaves, treedef = jax.tree_util.tree_flatten(with_graph_version(graph, 0))
    parts = [str(treedef)]
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", np.asarray(leaf).shape))
        dtype = getattr(leaf, "dtype", np.asarray(leaf).dtype)
        parts.append(f"{shape}:{dtype}")
    return hashlib.sha1("|".join(parts).encode()).hexdigest()


class ResidencyError(RuntimeError):
    """A device-graph upload cannot fit the residency budget even after
    every evictable tenant has been evicted (a single graph larger than
    ``max_device_bytes`` is unsatisfiable — raise, never thrash)."""


@dataclasses.dataclass
class ResidencyBudget:
    """Device-byte accounting for multi-graph serving residency.

    The serving twin of :class:`repro.core.planner.ExtractionBudget`'s
    assembly account (same charge/release discipline, bytes not rows):
    every resident tenant's packed operands are charged while on device,
    ``peak_resident_bytes`` bounds what the device ever held at once, and
    the LRU eviction traffic is recorded so benches and tests can assert
    the budget actually did work (``n_evictions > 0`` under pressure) —
    not just that answers came back.

    :meth:`charge` raises :class:`ResidencyError` on a violating upload;
    the serving tier evicts least-recently-used tenants *before* charging,
    so a raise here means a single graph exceeds the whole budget."""

    max_device_bytes: Optional[int] = None
    resident_bytes: int = 0          # live: bytes currently on device
    peak_resident_bytes: int = 0     # max resident_bytes ever observed
    uploaded_bytes: int = 0          # total bytes ever uploaded
    evicted_bytes: int = 0           # total bytes freed by eviction
    n_uploads: int = 0
    n_evictions: int = 0

    def would_fit(self, nbytes: int) -> bool:
        return (
            self.max_device_bytes is None
            or self.resident_bytes + int(nbytes) <= self.max_device_bytes
        )

    def charge(self, nbytes: int, what: str = "device graph") -> None:
        nbytes = int(nbytes)
        if not self.would_fit(nbytes):
            raise ResidencyError(
                f"residency budget exceeded: {self.resident_bytes} resident "
                f"+ {nbytes} uploading ({what}) > max_device_bytes="
                f"{self.max_device_bytes}; evict a tenant or raise the budget"
            )
        self.resident_bytes += nbytes
        self.uploaded_bytes += nbytes
        self.n_uploads += 1
        if self.resident_bytes > self.peak_resident_bytes:
            self.peak_resident_bytes = self.resident_bytes

    def release(self, nbytes: int, evicted: bool = False) -> None:
        self.resident_bytes -= int(nbytes)
        assert self.resident_bytes >= 0, "released more bytes than charged"
        if evicted:
            self.evicted_bytes += int(nbytes)
            self.n_evictions += 1


# ---------------------------------------------------------------------------
# Host -> device conversion
# ---------------------------------------------------------------------------

def _dev_edges(e: BipartiteEdges) -> DeviceBipartite:
    return DeviceBipartite(
        jnp.asarray(e.src, dtype=jnp.int32),
        jnp.asarray(e.dst, dtype=jnp.int32),
        e.n_src,
        e.n_dst,
    )


def self_path_counts(graph: CondensedGraph) -> np.ndarray:
    """Host: number of closed u->u paths per real node (diagonal of M)."""
    diag = np.zeros(graph.n_real, dtype=np.int64)
    for chain in graph.chains:
        if chain.n_layers == 1:
            e_in, e_out = chain.edges
            # Join (u, V) with (V, u): count matching (V, u) occurrences.
            key_in = e_in.dst.astype(np.int64) * graph.n_real + e_in.src
            key_out = e_out.src.astype(np.int64) * graph.n_real + e_out.dst
            key_out_sorted = np.sort(key_out)
            lo = np.searchsorted(key_out_sorted, key_in, side="left")
            hi = np.searchsorted(key_out_sorted, key_in, side="right")
            np.add.at(diag, e_in.src, (hi - lo))
        else:
            s, d, m = chain.path_pairs()
            mask = s == d
            np.add.at(diag, s[mask], m[mask])
    if graph.direct is not None and graph.direct.n_edges:
        mask = graph.direct.src == graph.direct.dst
        np.add.at(diag, graph.direct.src[mask], 1)
    return diag


def to_device(
    graph: Union[CondensedGraph, ExpandedGraph],
    correction: Optional[Correction] = None,
    deduplicated: bool = False,
    drop_self_loops: bool = True,
    graph_version: int = 0,
) -> DeviceGraph:
    """Build the device representation.

    For ``CondensedGraph`` inputs, pass ``correction`` (the triples from
    :func:`repro.core.dedup.build_correction` or a
    :class:`~repro.core.dedup.StreamedCorrection` built under a budget by
    :func:`~repro.core.dedup.build_correction_streaming`) to get DEDUP-C
    semantics, or ``deduplicated=True`` for DEDUP-1 output.  Without
    either, ring propagation counts duplicate paths (C-DUP semantics) —
    fine for idempotent algorithms, flagged by :func:`propagate`
    otherwise.

    ``graph_version`` stamps the upload with the live graph's delta
    version (:class:`repro.core.delta.GraphVersion`, DESIGN.md §9); it is
    static pytree meta, so re-uploading after ``apply_delta`` changes the
    jit cache key and every stale compiled executable dies with it.
    """
    if isinstance(graph, ExpandedGraph):
        g = graph.without_self_loops() if drop_self_loops else graph
        with obs.span("engine.upload"):
            return _settled(DeviceExpanded(
                jnp.asarray(g.src, dtype=jnp.int32),
                jnp.asarray(g.dst, dtype=jnp.int32),
                jnp.minimum(jnp.asarray(g.multiplicity, dtype=jnp.float32), 1.0),
                g.n,
                graph_version=int(graph_version),
            ))
    with obs.span("engine.upload"):
        chains = tuple(tuple(_dev_edges(e) for e in c.edges) for c in graph.chains)
        direct = _dev_edges(graph.direct) if graph.direct is not None else None
        corr = None
        triples = _correction_triples(correction)
        if triples is not None:
            corr = upload_correction(*triples, graph.n_real)
        chains, direct, corr = _settled((chains, direct, corr))
    diag = None
    if drop_self_loops and corr is None:
        # Full self-path multiplicity: DEDUP-1's uniqueness invariant is
        # off-diagonal only — u reaches itself once per containing virtual
        # node, and all of those must be subtracted.
        counts = self_path_counts(graph)
        with obs.span("engine.upload"):
            diag = _settled(jnp.asarray(counts, dtype=jnp.float32))
    return DeviceCondensed(
        chains=chains,
        direct=direct,
        correction=corr,
        diag_mult=diag,
        n_real=graph.n_real,
        deduplicated=deduplicated,
        graph_version=int(graph_version),
    )


def _settled(tree):
    """``tree``, once its arrays are on the device if the recorder is on,
    so that an ``engine.upload`` span times the transfer, not its enqueue."""
    if obs.enabled():
        jax.block_until_ready(tree)
    return tree


def _upload_operands(bsb, crossover=None) -> PackedOperands:
    with obs.span("engine.upload"):
        return _settled(PackedOperands(
            slot_src=jnp.asarray(bsb.slot_src),
            slot_row=jnp.asarray(bsb.slot_row),
            row_start=jnp.asarray(bsb.row_start),
            row_count=jnp.asarray(bsb.row_count),
            bitmaps=jnp.asarray(bsb.bitmaps),
            crossover=crossover,
        ))


def _measure_direction(bsb, dev_src, dev_dst, n_src, n_dst, measure_kwargs):
    """Record a crossover table for one packed direction by racing the
    kernel (autotuned) against the segment path on this host."""
    from ..kernels.autotune import measure_crossover
    from ..kernels.ops import PackedLayer

    layer = PackedLayer(
        bsb=bsb,
        bsb_rev=None,
        src=dev_src,
        dst=dev_dst,
        n_src=n_src,
        n_dst=n_dst,
    )
    return measure_crossover(layer, **measure_kwargs)


def _pack_edges(
    e: BipartiteEdges,
    dev: DeviceBipartite,
    shard_edges: Optional[int] = None,
    measure: bool = False,
    measure_kwargs: Optional[dict] = None,
    pack_method: str = "reduceat",
):
    """``dev`` is the already-uploaded COO layer from :func:`to_device`,
    reused so the edge arrays cross to the device only once.  Packs both
    directions: the forward incidence and its transpose (reverse steps).
    ``shard_edges`` routes the packing through the shard-at-a-time path
    (:func:`repro.kernels.pack.pack_bipartite` slices + OR-merge,
    DESIGN.md §7) so packing transients stay bounded for large layers.
    ``measure`` additionally races each direction against the segment
    path and stores the crossover table on the uploaded operands.

    Returns ``(DevicePackedLayer, fwd_bsb, rev_bsb)`` — the host-side
    packings ride along so :func:`to_device_packed` can build the fused
    correction stream without re-packing."""
    from ..kernels.pack import TILE, pack_bipartite

    fwd = rev = None
    fwd_bsb = rev_bsb = None
    # min one tile each way, matching the pack's pad-slot convention
    # (BlockSparseBitmap.n_src_tiles): zero-node layers stay kernel-safe
    n_src_pad = max(-(-e.n_src // TILE), 1) * TILE
    n_dst_pad = max(-(-e.n_dst // TILE), 1) * TILE
    try:
        with obs.span("engine.pack"):
            fwd_bsb = pack_bipartite(e, method=pack_method, shard_edges=shard_edges)
            rev_bsb = pack_bipartite(
                e.reversed(), method=pack_method, shard_edges=shard_edges
            )
        fwd_table = rev_table = None
        if measure:
            kw = measure_kwargs or {}
            fwd_table = _measure_direction(
                fwd_bsb, dev.src, dev.dst, e.n_src, e.n_dst, kw
            )
            rev_table = _measure_direction(
                rev_bsb, dev.dst, dev.src, e.n_dst, e.n_src, kw
            )
        fwd = _upload_operands(fwd_bsb, fwd_table)
        rev = _upload_operands(rev_bsb, rev_table)
    except ValueError:
        fwd = rev = None  # duplicate edges (multiplicity): COO path only
        fwd_bsb = rev_bsb = None
    layer = DevicePackedLayer(
        src=dev.src,
        dst=dev.dst,
        fwd=fwd,
        rev=rev,
        n_src=e.n_src,
        n_dst=e.n_dst,
        n_src_pad=n_src_pad,
        n_dst_pad=n_dst_pad,
    )
    return layer, fwd_bsb, rev_bsb


def _upload_fused(stream, main_bsb, corr_planes) -> FusedOperands:
    from ..kernels.pack import TILE

    with obs.span("engine.upload"):
        return _settled(FusedOperands(
            kind=jnp.asarray(stream.kind),
            main_src=jnp.asarray(stream.main_src),
            corr_src=jnp.asarray(stream.corr_src),
            main_idx=jnp.asarray(stream.main_idx),
            corr_idx=jnp.asarray(stream.corr_idx),
            slot_row=jnp.asarray(stream.slot_row),
            row_start=jnp.asarray(stream.row_start),
            row_count=jnp.asarray(stream.row_count),
            bitmaps=jnp.asarray(main_bsb.bitmaps),
            planes=jnp.asarray(corr_planes.planes),
            plane_weights=corr_planes.plane_weights,
            n_h_pad=main_bsb.n_src_tiles * TILE,
            n_x_pad=corr_planes.n_src_tiles * TILE,
            n_out=main_bsb.n_dst,
            n_out_pad=main_bsb.n_row_tiles * TILE,
        ))


def _build_fused(
    graph: CondensedGraph,
    chains_host,
    triples: Tuple[np.ndarray, np.ndarray, np.ndarray],
) -> Tuple[Optional[FusedOperands], Optional[FusedOperands], str]:
    """Build the fused (last layer + DEDUP-C epilogue) operands for both
    directions.  Forward fuses into the last chain's final layer (the one
    whose output space is the real nodes); reverse propagation walks each
    chain backwards, so its final step is the same chain's *first* layer
    transposed.  Requires that layer to be packable (no duplicates).

    Returns ``(fused_fwd, fused_rev, standdown_reason)`` — the reason is
    ``''`` when the operands were built and otherwise one of the
    machine-readable pack-time stand-down reasons recorded on
    :attr:`DevicePacked.fused_standdown`:

    * ``'no_chains_or_empty_correction'`` — nothing to fuse into, or a
      correction with zero triples (the epilogue would be a no-op);
    * ``'unpackable_last_layer'`` — the fusing layer has duplicate edges
      and cannot be bit-packed;
    * ``'endpoint_mismatch'`` — the fusing layer's output space is not
      the real-node space (the correction subtracts over real nodes).
    """
    from ..kernels.correction import build_fused_stream, pack_correction

    cs, cd, cm = triples
    if not graph.chains or cs.size == 0:
        return None, None, "no_chains_or_empty_correction"
    _, last_fwd_bsb, _ = chains_host[-1][-1]
    _, _, first_rev_bsb = chains_host[-1][0]
    if last_fwd_bsb is None or first_rev_bsb is None:
        return None, None, "unpackable_last_layer"
    n = graph.n_real
    if last_fwd_bsb.n_dst != n or first_rev_bsb.n_dst != n:
        return None, None, "endpoint_mismatch"
    with obs.span("engine.pack"):
        corr_fwd = pack_correction(cs, cd, cm, n_src=n, n_dst=n)
        corr_rev = pack_correction(cd, cs, cm, n_src=n, n_dst=n)
    fused = []
    for bsb, planes in ((last_fwd_bsb, corr_fwd), (first_rev_bsb, corr_rev)):
        with obs.span("engine.pack"):
            stream = build_fused_stream(bsb, planes)
        fused.append(_upload_fused(stream, bsb, planes))
    return fused[0], fused[1], ""


def to_device_packed(
    graph: CondensedGraph,
    correction: Optional[Correction] = None,
    deduplicated: bool = False,
    drop_self_loops: bool = True,
    backend: str = "auto",
    feature_block: int = 128,
    pack_shard_edges: Optional[int] = None,
    fuse_correction: bool = True,
    measure: bool = False,
    measure_kwargs: Optional[dict] = None,
    graph_version: int = 0,
    pack_method: str = "reduceat",
) -> DevicePacked:
    """Like :func:`to_device`, additionally packing every condensed layer
    into bit-packed block-sparse SpMM operands (DESIGN.md §6) so batched
    ring propagation runs on the Pallas kernel.  Correction / dedup
    semantics are identical to :func:`to_device` (streamed corrections
    accepted the same way).  ``pack_shard_edges`` bounds the host packing
    transients per layer (shard-at-a-time packing, DESIGN.md §7) — the
    uploaded operands are byte-identical either way.

    ``fuse_correction`` (default on) also builds the fused last-layer +
    DEDUP-C-epilogue operands when a correction is present, so batched
    ring propagation subtracts the correction inside the kernel.
    ``measure=True`` races each packed direction against the segment path
    at pack time and records the crossover table on the operands
    (:mod:`repro.kernels.autotune`); 'auto' dispatch then follows the
    measurement.  ``measure_kwargs`` forwards to
    :func:`~repro.kernels.autotune.measure_crossover` (batch sizes, ops,
    a deterministic ``time_fn`` for tests).  ``pack_method`` selects the
    host-side pack fold (``'reduceat'`` | ``'scatter'``, a cost-model
    knob — DESIGN.md §12); the packed operands are byte-identical either
    way.
    """
    base = to_device(
        graph,
        correction=correction,
        deduplicated=deduplicated,
        drop_self_loops=drop_self_loops,
    )
    assert isinstance(base, DeviceCondensed)
    chains_host = tuple(
        tuple(
            _pack_edges(
                e, d, pack_shard_edges, measure, measure_kwargs, pack_method
            )
            for e, d in zip(c.edges, dc)
        )
        for c, dc in zip(graph.chains, base.chains)
    )
    chains = tuple(tuple(t[0] for t in c) for c in chains_host)
    direct = (
        _pack_edges(
            graph.direct, base.direct, pack_shard_edges, measure,
            measure_kwargs, pack_method,
        )[0]
        if graph.direct is not None
        else None
    )
    fused_fwd = fused_rev = None
    triples = _correction_triples(correction)
    if triples is None:
        standdown = "no_correction"
    elif not fuse_correction:
        standdown = "fuse_correction_disabled"
    else:
        cs, cd, cm = triples
        fused_fwd, fused_rev, standdown = _build_fused(
            graph,
            chains_host,
            (np.asarray(cs), np.asarray(cd), np.asarray(cm)),
        )
    return DevicePacked(
        chains=chains,
        direct=direct,
        correction=base.correction,
        diag_mult=base.diag_mult,
        n_real=graph.n_real,
        deduplicated=deduplicated,
        backend=backend,
        feature_block=feature_block,
        fused_fwd=fused_fwd,
        fused_rev=fused_rev,
        graph_version=int(graph_version),
        fused_standdown=standdown,
    )


# ---------------------------------------------------------------------------
# Propagation
# ---------------------------------------------------------------------------

def _gather(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(x, idx, axis=0)


def _edge_propagate(
    sr: Semiring,
    edges: DeviceBipartite,
    x: jnp.ndarray,
    reverse: bool,
) -> jnp.ndarray:
    src, dst = (edges.dst, edges.src) if reverse else (edges.src, edges.dst)
    n_out = edges.n_src if reverse else edges.n_dst
    return segment_reduce(sr, _gather(x, src), dst, n_out)


def _kernel_applicable(
    graph: "DevicePacked",
    layer: DevicePackedLayer,
    x: jnp.ndarray,
    semiring: Semiring,
    reverse: bool,
) -> bool:
    """Static (trace-time) dispatch: batched kernelizable steps, both
    directions.

    The streamed-window VMEM footprint (DESIGN.md §6) is shared with
    kernels.ops via kernels.pack (imported lazily — the kernels package
    pulls in the Pallas stack); since the source column is streamed, the
    formula no longer depends on the source count, so the old 8 MiB
    resident-column cliff is gone.  The two 'auto' policies intentionally
    differ in one respect: the engine only selects Pallas on a real TPU
    (interpret mode is for explicit backend='pallas' testing), while the
    standalone ops wrapper will run interpret mode anywhere.
    """
    if x.ndim != 2 or not kernelizable(semiring):
        return False
    packed = layer.rev if reverse else layer.fwd
    if packed is None:
        return False
    if graph.backend == "pallas":
        return True
    if graph.backend == "xla":
        return False
    from ..kernels.pack import fits_vmem

    n_slots = int(packed.slot_src.shape[0])
    if packed.crossover is not None:
        # measured decision wins over both heuristics: the table was
        # recorded on this host, so a measured-pallas cell dispatches
        # even off-TPU (only sanity-checked against the VMEM budget of
        # its recorded config), and a measured-xla cell never dispatches
        # no matter what the footprint formula says
        n_src_dir = layer.n_dst if reverse else layer.n_src
        entry = packed.crossover.lookup(
            semiring.add_kind, n_src_dir, x.shape[1]
        )
        if entry is not None:
            if entry.backend == "xla":
                return False
            return fits_vmem(
                x.shape[1],
                entry.feature_block,
                x.dtype.itemsize,
                n_slots=n_slots,
                row_window=entry.row_window,
            )
    fits = fits_vmem(
        x.shape[1],
        graph.feature_block,
        x.dtype.itemsize,
        n_slots=n_slots,
    )
    return jax.default_backend() == "tpu" and fits


def _packed_layer_spmm(
    layer: DevicePackedLayer,
    x: jnp.ndarray,
    feature_block: int,
    semiring: Semiring,
    reverse: bool,
) -> jnp.ndarray:
    """One layer of the factorized SpMM ``Y = B ⊕ X`` on the Pallas kernel.

    The kernel window geometry comes from the operands' crossover table
    when one was recorded (the measured-fastest config for this cell);
    unmeasured packs stream the default ``(TILE, feature_block)`` window.
    """
    from ..kernels.bitmap_spmm import bitmap_spmm_pallas
    from ..kernels.pack import TILE

    global KERNEL_DISPATCH_COUNT
    KERNEL_DISPATCH_COUNT += 1
    ops = layer.rev if reverse else layer.fwd
    n_in_pad = layer.n_dst_pad if reverse else layer.n_src_pad
    n_out_pad = layer.n_src_pad if reverse else layer.n_dst_pad
    n_out = layer.n_src if reverse else layer.n_dst
    row_window = TILE
    if ops.crossover is not None:
        n_src_dir = layer.n_dst if reverse else layer.n_src
        entry = ops.crossover.lookup(semiring.add_kind, n_src_dir, x.shape[1])
        if entry is not None and entry.backend == "pallas":
            row_window = entry.row_window
            feature_block = entry.feature_block
    f = x.shape[1]
    f_pad = -(-f // feature_block) * feature_block
    # a >TILE window streams several source tiles per fetch: the source
    # axis must pad to a whole number of windows
    n_in_pad = -(-n_in_pad // row_window) * row_window
    xp = jnp.pad(x, ((0, n_in_pad - x.shape[0]), (0, f_pad - f)))
    yp = bitmap_spmm_pallas(
        ops.slot_src,
        ops.slot_row,
        ops.row_start,
        ops.row_count,
        ops.bitmaps,
        xp,
        n_dst_pad=n_out_pad,
        feature_block=feature_block,
        op=semiring.add_kind,
        zero=float(semiring.zero),
        row_window=row_window,
    )
    return yp[:n_out, :f]


def _layer_propagate(
    graph: DeviceGraph,
    sr: Semiring,
    edges,
    x: jnp.ndarray,
    reverse: bool,
) -> jnp.ndarray:
    if isinstance(graph, DevicePacked) and _kernel_applicable(
        graph, edges, x, sr, reverse
    ):
        return _packed_layer_spmm(edges, x, graph.feature_block, sr, reverse)
    return _edge_propagate(sr, edges, x, reverse)


def _fused_applicable(
    graph: "DevicePacked",
    fused: Optional[FusedOperands],
    x: jnp.ndarray,
    semiring: Semiring,
    hop_weight: Optional[float],
) -> Tuple[bool, str]:
    """Trace-time fused-epilogue dispatch: batched plus-times ring steps
    only (the correction is a ring concept), no per-hop weighting (the
    fused output folds the subtraction into one chain's hop, which only
    commutes unweighted), and the same backend policy as the per-layer
    kernel (explicit 'pallas' always, 'xla' never, 'auto' on TPU when the
    fused working set — two streamed feature operands, the plane stack,
    two accumulators — fits VMEM).

    Returns ``(dispatch, reason)``: ``(True, '')`` when the fused kernel
    runs, else ``False`` plus the machine-readable stand-down reason —
    the pack-time :attr:`DevicePacked.fused_standdown` when the operands
    were never built, or one of ``'frontier_1d'`` /
    ``'semiring_<name>'`` / ``'hop_weight'`` / ``'backend_xla'`` /
    ``'vmem_or_backend'`` for trace-time declines.  :func:`propagate`
    counts each miss under its reason in
    :data:`KERNEL_STANDDOWN_COUNT`."""
    if fused is None:
        return False, graph.fused_standdown or "not_built"
    if x.ndim != 2:
        return False, "frontier_1d"
    if semiring.name != "plus_times":
        return False, f"semiring_{semiring.name}"
    if hop_weight is not None:
        return False, "hop_weight"
    if graph.backend == "pallas":
        return True, ""
    if graph.backend == "xla":
        return False, "backend_xla"
    from ..kernels.pack import fused_fits_vmem

    fits = fused_fits_vmem(
        x.shape[1],
        graph.feature_block,
        x.dtype.itemsize,
        n_planes=len(fused.plane_weights),
        n_slots=int(fused.kind.shape[0]),
    )
    if jax.default_backend() == "tpu" and fits:
        return True, ""
    return False, "vmem_or_backend"


def _fused_layer_spmm(
    fused: FusedOperands,
    h: jnp.ndarray,
    x: jnp.ndarray,
    feature_block: int,
) -> jnp.ndarray:
    """The last layer of the last chain with the DEDUP-C subtraction in
    the kernel epilogue: ``y = B h − D x`` in one launch."""
    from ..kernels.bitmap_spmm import bitmap_spmm_fused_pallas

    global KERNEL_DISPATCH_COUNT
    KERNEL_DISPATCH_COUNT += 1
    f = h.shape[1]
    f_pad = -(-f // feature_block) * feature_block
    hp = jnp.pad(h, ((0, fused.n_h_pad - h.shape[0]), (0, f_pad - f)))
    xp = jnp.pad(x, ((0, fused.n_x_pad - x.shape[0]), (0, f_pad - f)))
    yp = bitmap_spmm_fused_pallas(
        fused.kind,
        fused.main_src,
        fused.corr_src,
        fused.main_idx,
        fused.corr_idx,
        fused.slot_row,
        fused.row_start,
        fused.row_count,
        fused.bitmaps,
        fused.planes,
        hp,
        xp,
        n_dst_pad=fused.n_out_pad,
        plane_weights=fused.plane_weights,
        feature_block=feature_block,
    )
    return yp[: fused.n_out, :f]


def _interior_scope(step: int, n_steps: int):
    """``engine.interior`` around step ``step`` of a chain of ``n_steps``
    when it runs from one virtual layer to another (counted in
    :data:`INTERIOR_STEP_COUNT`); nothing around a step that reads or
    writes the real nodes."""
    global INTERIOR_STEP_COUNT
    if not 0 < step < n_steps - 1:
        return contextlib.nullcontext()
    INTERIOR_STEP_COUNT += 1
    return jax.named_scope("engine.interior")


def _apply_hop(sr: Semiring, y: jnp.ndarray, hop_weight: Optional[float]) -> jnp.ndarray:
    if hop_weight is None:
        return y
    return sr.mul(y, jnp.asarray(hop_weight, dtype=y.dtype))


def propagate(
    graph: DeviceGraph,
    x: jnp.ndarray,
    semiring: Semiring = PLUS_TIMES,
    *,
    reverse: bool = False,
    hop_weight: Optional[float] = None,
    allow_duplicates: bool = False,
    layer_weights: Optional[Tuple[Tuple[jnp.ndarray, ...], ...]] = None,
) -> jnp.ndarray:
    """One superstep: ⊕-combine ⊗-weighted messages along all edges.

    ``x`` is one frontier ``(n,)`` or a batch of ``B`` frontiers ``(n, B)``
    processed in a single factorized SpMM; per-column results equal ``B``
    independent single-frontier calls (DESIGN.md §3).  ``hop_weight`` is
    applied once per *logical* (real->real) hop, not per condensed layer,
    so BFS hop counting matches the expanded graph.

    ``layer_weights`` carries edge properties on condensed chains
    (DESIGN.md §11): one tuple per chain, one ``(layer_size,)`` array per
    *virtual* layer, ⊗-applied to the hidden frontier while it occupies
    that layer.  A condensed path's weight is then the ⊗-product of its
    virtual-node properties (min-plus: path cost = Σ weights; max-min:
    path width = min capacity), while every incidence step stays an
    unweighted SpMM — so :func:`~repro.core.semiring.kernelizable`
    packed/Pallas dispatch is unaffected.  Direct edges carry no virtual
    node, hence the weight identity (``semiring.one``).  Only idempotent
    semirings are supported (the DEDUP-C correction algebra is
    multiplicity-based and does not extend to weighted ring sums).
    """
    n_in = graph.n if isinstance(graph, DeviceExpanded) else graph.n_real
    if x.ndim not in (1, 2) or x.shape[0] != n_in:
        raise ValueError(
            f"frontier must be ({n_in},) or ({n_in}, B); got shape {x.shape}"
        )
    x = shard_frontier(x)
    if layer_weights is not None:
        if isinstance(graph, DeviceExpanded):
            raise ValueError(
                "layer_weights are condensed-chain edge properties; the "
                "expanded representation needs them folded into a dense "
                "weighted matrix instead (tests/oracle.py does exactly that)"
            )
        if not semiring.idempotent:
            raise ValueError(
                "layer_weights require an idempotent semiring: the ring "
                "correction (DEDUP-C) subtracts path multiplicities and "
                "has no weighted analogue"
            )
        if len(layer_weights) != len(graph.chains):
            raise ValueError(
                f"layer_weights must cover all {len(graph.chains)} chains; "
                f"got {len(layer_weights)}"
            )
        for ci, (cw, chain) in enumerate(zip(layer_weights, graph.chains)):
            if len(cw) != len(chain) - 1:
                raise ValueError(
                    f"chain {ci} has {len(chain) - 1} virtual layers; got "
                    f"{len(cw)} weight arrays"
                )
    if isinstance(graph, DeviceExpanded):
        src, dst = (graph.dst, graph.src) if reverse else (graph.src, graph.dst)
        msgs = _gather(x, src)
        if semiring.name == "plus_times":
            msgs = msgs * _bcast(graph.weight, msgs)
        y = segment_reduce(semiring, msgs, dst, graph.n)
        return shard_frontier(_apply_hop(semiring, y, hop_weight))

    assert isinstance(graph, (DeviceCondensed, DevicePacked))
    exact = (
        semiring.idempotent
        or graph.deduplicated
        or graph.correction is not None
    )
    if not exact and not allow_duplicates:
        raise ValueError(
            "ring propagation on C-DUP counts duplicate paths; pass a "
            "correction (DEDUP-C), a deduplicated graph (DEDUP-1), or "
            "allow_duplicates=True (paper §4.1 duplication problem)"
        )

    # Fused DEDUP-C epilogue (DESIGN.md §6): the last chain's final layer
    # and the correction subtraction run as one kernel launch; the
    # trailing row-layout correction below is then skipped.
    fused = None
    if isinstance(graph, DevicePacked) and graph.correction is not None:
        cand = graph.fused_rev if reverse else graph.fused_fwd
        ok, reason = _fused_applicable(graph, cand, x, semiring, hop_weight)
        if ok:
            fused = cand
        else:
            KERNEL_STANDDOWN_COUNT[reason] = (
                KERNEL_STANDDOWN_COUNT.get(reason, 0) + 1
            )

    y = None
    for ci, chain in enumerate(graph.chains):
        seq: Sequence[DeviceBipartite] = chain[::-1] if reverse else chain
        w_seq: Optional[Sequence[jnp.ndarray]] = None
        if layer_weights is not None:
            # weight i lives on virtual layer i; walking the chain
            # backwards visits the layers in reverse order
            cw = layer_weights[ci]
            w_seq = cw[::-1] if reverse else cw
        h = x
        fuse_here = fused is not None and ci == len(graph.chains) - 1
        for si, e in enumerate(seq[:-1] if fuse_here else seq):
            with jax.named_scope("engine.layer"), _interior_scope(si, len(seq)):
                h = _layer_propagate(graph, semiring, e, h, reverse)
                if w_seq is not None and si < len(seq) - 1:
                    h = semiring.mul(h, _bcast(jnp.asarray(w_seq[si]), h))
        if fuse_here:
            with jax.named_scope("engine.fused"):
                h = _fused_layer_spmm(fused, h, x, graph.feature_block)
        h = _apply_hop(semiring, h, hop_weight)
        y = h if y is None else semiring.add(y, h)
    if graph.direct is not None:
        with jax.named_scope("engine.layer"):
            h = _layer_propagate(graph, semiring, graph.direct, x, reverse)
        h = _apply_hop(semiring, h, hop_weight)
        y = h if y is None else semiring.add(y, h)
    if y is None:
        zero_shape = (graph.n_real,) + x.shape[1:]
        y = jnp.full(zero_shape, semiring.zero, dtype=x.dtype)

    if semiring.name == "plus_times":
        # Exactness corrections only make sense in the ring.
        if graph.correction is not None:
            path = "fused" if fused is not None else "rows"
            CORRECTION_EPILOGUE_COUNT[path] = (
                CORRECTION_EPILOGUE_COUNT.get(path, 0) + 1
            )
            # the fused kernel's epilogue has already subtracted it
            if fused is None:
                with jax.named_scope("engine.correction"):
                    corr = apply_correction(graph.correction, x, reverse)
                    y = y - _apply_hop(semiring, corr, hop_weight)
        elif graph.diag_mult is not None:
            y = y - _apply_hop(
                semiring, x * _bcast(graph.diag_mult, x), hop_weight
            )
    return shard_frontier(y)


def propagate_wedge(
    graph: DeviceGraph,
    x: jnp.ndarray,
    *,
    reverse: bool = False,
    wedge: Optional[DeviceCorrection] = None,
) -> jnp.ndarray:
    """Exact two-hop ring propagation ``y = Aᵀ(Aᵀx)`` on a DEDUP-C graph
    from *uncorrected* C-DUP hops (DESIGN.md §11).

    The linear DEDUP-C identity ``A = M − D`` composes quadratically:

        ``A² = (M − D)² = M² − (MD + DM − D²)``

    so the exact wedge count is two raw multiplicity hops (each a plain
    kernel-path SpMM — no per-step correction subtraction, no fused
    epilogue needed) minus the *wedge correction* ``W = MD + DM − D²`` —
    the duplicate wedges whose legs are multiple condensed paths through
    shared virtual nodes.  With ``wedge`` precomputed by
    :func:`repro.core.dedup.build_wedge_correction` and uploaded in its
    row layout (:func:`~repro.core.correction_rows.upload_correction`)
    the correction is one sparse pass (``y = M(Mx) − Wx``); without it it
    is assembled on the fly from the graph's own ``D``
    (``y = M(Mx) − M(Dx) − D(Mx) + D(Dx)``).  Byte-identical to two
    per-step-corrected :func:`propagate` calls on integer frontiers.
    """
    if isinstance(graph, DeviceExpanded):
        y = propagate(graph, x, PLUS_TIMES, reverse=reverse)
        return propagate(graph, y, PLUS_TIMES, reverse=reverse)
    if graph.correction is None:
        if graph.deduplicated:
            y = propagate(graph, x, PLUS_TIMES, reverse=reverse)
            return propagate(graph, y, PLUS_TIMES, reverse=reverse)
        raise ValueError(
            "propagate_wedge needs a DEDUP-C correction: the quadratic "
            "wedge correction is built from the linear D triples"
        )
    raw = dataclasses.replace(graph, correction=None, diag_mult=None)
    mx = propagate(raw, x, PLUS_TIMES, reverse=reverse, allow_duplicates=True)
    mmx = propagate(raw, mx, PLUS_TIMES, reverse=reverse, allow_duplicates=True)
    if wedge is not None:
        return shard_frontier(mmx - apply_correction(wedge, x, reverse))
    dx = apply_correction(graph.correction, x, reverse)
    mdx = propagate(raw, dx, PLUS_TIMES, reverse=reverse, allow_duplicates=True)
    dmx = apply_correction(graph.correction, mx, reverse)
    ddx = apply_correction(graph.correction, dx, reverse)
    return shard_frontier(mmx - mdx - dmx + ddx)


def _bcast(w: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Broadcast per-edge/per-node weight against feature matrices."""
    if like.ndim == w.ndim:
        return w.astype(like.dtype)
    return w.astype(like.dtype).reshape(w.shape + (1,) * (like.ndim - w.ndim))
