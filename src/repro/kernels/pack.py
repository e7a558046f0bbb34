"""Host-side packing: BipartiteEdges -> bit-packed block-sparse incidence.

The paper's BITMAP idea (per-virtual-node bitmaps consulted during
traversal) reborn TPU-native: the 0/1 incidence matrix of a condensed
layer is tiled into 128x128 blocks; only nonzero blocks are stored, each
as a 4x128 uint32 bitmap (2 KiB instead of 64 KiB f32).  Bit ``b`` of
word ``[w, c]`` is the cell (row ``32 w + b``, column ``c``): a block's
bits run down its rows, so its minor axis is the 128 columns.  On the TPU
the array then keeps the compact (4, 128) tiling that the kernel reads in
place; a minor axis of 4 words would be padded to 128 lanes, and copied
so on every kernel call.  The Pallas kernel
unpacks a block's bits in VMEM and feeds the MXU with a dense 128x128
operand — bandwidth-compressed SpMM (see DESIGN.md §6).

Layout (streamed slot list + run table):
    slot_src  : (n_slots,) int32  — source-tile index per nonzero block
    slot_row  : (n_slots,) int32  — dst row-tile index per nonzero block
    bitmaps   : (n_slots, TILE//32, TILE) uint32
    row_start : (n_row_tiles,) int32 — first slot of each row tile
    row_count : (n_row_tiles,) int32 — slots in each row tile

Slots are sorted by (row tile, source tile), so the kernel's inner grid
axis walks each row tile's source blocks as one contiguous, monotonically
increasing run — the access pattern the Pallas pipeline double-buffers
(DESIGN.md §6).  Every row tile owns at least one slot (empty rows get a
single all-zero pad bitmap, mathematically inert) so each output tile is
visited and written exactly once per feature tile.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..core.condensed import BipartiteEdges

TILE = 128
WORDS = TILE // 32

# Per-grid-cell VMEM working-set budget (bytes).  Lives here (numpy-only
# module) so both auto-dispatchers (kernels.ops.bitmap_spmm and
# core.engine._kernel_applicable) share one formula without the engine
# importing the Pallas stack.
_VMEM_BUDGET = 8 * 2**20

# Scalar-prefetch budget (bytes): the slot/run tables land in SMEM,
# which is far smaller than VMEM.  Conservative cap; graphs with more
# nonzero blocks than this fall back to the segment path instead of
# failing inside Mosaic.
_SMEM_BUDGET = 256 * 2**10

# Pipeline depth: Pallas double-buffers each streamed input block (fetch
# tile t+1 while the MXU consumes tile t).
_STREAM_WINDOW = 2

# Column chunk width of the kernel's min/max masked-select reduction
# (bitmap_spmm imports it from here): sizes the (TILE, CHUNK, Fb) select
# intermediate that the footprint formula must account for.
STREAM_CHUNK = 8

# Bit-field widths of pack_bipartite's combined sort key; derived from
# the tile constants so the layout can't silently drift from them.
_C_BITS = TILE.bit_length() - 1          # column-in-tile
_W_BITS = WORDS.bit_length() - 1         # word-in-column
_B_BITS = 5                              # bit-in-word (uint32)

__all__ = [
    "BlockSparseBitmap",
    "unpack_block",
    "pack_bipartite",
    "merge_block_sparse",
    "streamed_footprint_bytes",
    "fits_vmem",
    "fused_fits_vmem",
    "measure_pack_throughput",
    "TILE",
    "WORDS",
]


def unpack_block(words: np.ndarray) -> np.ndarray:
    """One (WORDS, TILE) bitmap -> its dense (TILE, TILE) 0/1 block."""
    shifts = np.arange(32, dtype=np.uint32)[None, :, None]
    return ((words[:, None, :] >> shifts) & 1).reshape(TILE, TILE)


def streamed_footprint_bytes(
    n_features: int, feature_block: int, itemsize: int, row_window: int = TILE
) -> int:
    """Per-grid-cell VMEM working set of the streamed kernel, in bytes.

    The source column is *streamed* through a double-buffered window of
    one (row_window, feature_block) tile, so — unlike the old
    resident-column formula — the footprint is independent of ``n_src``:
    window (x2 buffers) + bitmap slot (x2) + output tile (x2) + f32
    accumulator.  ``n_features`` is accepted (both dispatchers know it)
    but intentionally unused: streaming removed the source-count *and*
    feature-count terms — only the window dimensions matter.
    ``row_window`` is the autotune axis (DESIGN.md §6): source rows
    fetched per streamed step, a multiple of ``TILE``.
    """
    del n_features  # the streamed window is one feature_block tile wide
    x_tile = row_window * feature_block * itemsize
    bitmap_slot = TILE * WORDS * 4
    out_tile = TILE * feature_block * itemsize
    acc = TILE * feature_block * 4
    # kernel-body intermediates, whichever op variant is larger: the
    # unpacked dense 0/1 mask (sum) vs the (TILE, CHUNK, Fb) f32 select
    # of the min/max path — without these the formula re-grows a cliff
    # at wide feature blocks; a >TILE row window also materializes one
    # (TILE, Fb) sub-tile slice of the fetched window
    body = max(TILE * TILE * 4, TILE * STREAM_CHUNK * feature_block * 4)
    if row_window > TILE:
        body += TILE * feature_block * itemsize
    return _STREAM_WINDOW * (x_tile + bitmap_slot + out_tile) + acc + body


def fits_vmem(
    n_features: int,
    feature_block: int,
    itemsize: int,
    n_slots: Optional[int] = None,
    row_window: int = TILE,
) -> bool:
    """Whether the streamed kernel's working set fits the VMEM budget —
    the one fits formula both auto-dispatchers must agree on.  With the
    source column streamed this no longer depends on the source count, so
    graphs far above the old 8 MiB resident-column cliff still dispatch
    to the kernel.  ``n_slots`` (when the caller knows it) guards the one
    remaining size-dependent operand: the scalar-prefetched slot/run
    tables, which live in SMEM — four int32 tables bounded by ``n_slots``
    entries each.  ``row_window`` sizes the streamed source window of the
    candidate kernel configuration (autotune sweep, DESIGN.md §6).
    """
    if n_slots is not None and 4 * n_slots * 4 > _SMEM_BUDGET:
        return False
    return (
        streamed_footprint_bytes(
            n_features, feature_block, itemsize, row_window=row_window
        )
        <= _VMEM_BUDGET
    )


def fused_fits_vmem(
    n_features: int,
    feature_block: int,
    itemsize: int,
    n_planes: int,
    n_slots: Optional[int] = None,
) -> bool:
    """VMEM/SMEM admission for the fused DEDUP-C-epilogue kernel.

    On top of the plain streamed footprint it double-buffers a *second*
    feature operand (the original input frontier next to the hidden one)
    and the ``n_planes``-deep correction bitmap stack, and holds a second
    f32 accumulator; its slot stream carries eight scalar tables instead
    of four.
    """
    if n_slots is not None and 8 * n_slots * 4 > _SMEM_BUDGET:
        return False
    base = streamed_footprint_bytes(n_features, feature_block, itemsize)
    extra = _STREAM_WINDOW * (
        TILE * feature_block * itemsize + n_planes * TILE * WORDS * 4
    )
    extra += TILE * feature_block * 4  # second accumulator
    return base + extra <= _VMEM_BUDGET


@dataclasses.dataclass
class BlockSparseBitmap:
    """Destination-major packed incidence: rows = dst, cols = src."""

    slot_src: np.ndarray   # (n_slots,) int32
    slot_row: np.ndarray   # (n_slots,) int32
    bitmaps: np.ndarray    # (n_slots, WORDS, TILE) uint32
    row_start: np.ndarray  # (n_row_tiles,) int32
    row_count: np.ndarray  # (n_row_tiles,) int32
    n_dst: int             # logical rows
    n_src: int             # logical cols

    @property
    def n_slots(self) -> int:
        return int(self.slot_src.shape[0])

    @property
    def n_row_tiles(self) -> int:
        return int(self.row_start.shape[0])

    @property
    def max_k(self) -> int:
        return int(self.row_count.max()) if self.row_count.size else 0

    @property
    def n_src_tiles(self) -> int:
        # min 1, matching pack_bipartite's n_st: pad slots index source
        # tile 0, so a zero-source layer must still pad x to one (inert,
        # all-zero) tile instead of handing the kernel a 0-row operand
        return max(-(-self.n_src // TILE), 1)

    @property
    def n_nonzero_blocks(self) -> int:
        return int((self.bitmaps.any(axis=(1, 2))).sum())

    def nbytes(self) -> int:
        return int(
            self.slot_src.nbytes
            + self.slot_row.nbytes
            + self.bitmaps.nbytes
            + self.row_start.nbytes
            + self.row_count.nbytes
        )

    def to_dense(self) -> np.ndarray:
        """Oracle helper: dense (n_dst_pad, n_src_pad) 0/1 matrix."""
        dense = np.zeros(
            (self.n_row_tiles * TILE, self.n_src_tiles * TILE), dtype=np.float32
        )
        for s in range(self.n_slots):
            w = self.bitmaps[s]
            if not w.any():
                continue
            bits = unpack_block(w)
            i = int(self.slot_row[s])
            b = int(self.slot_src[s])
            dense[i * TILE : (i + 1) * TILE, b * TILE : (b + 1) * TILE] += bits
        return dense


def _slot_layout(ub_rows: np.ndarray, ub_cols: np.ndarray, n_rt: int):
    """Canonical slot-stream layout from sorted unique (row, src) blocks:
    per row tile, real slots in ascending source order, one all-zero pad
    slot for each empty row tile.  Shared by :func:`pack_bipartite` and
    :func:`merge_block_sparse` so a merged pack is byte-identical to a
    one-shot pack."""
    counts = np.bincount(ub_rows, minlength=n_rt)
    empty = np.flatnonzero(counts == 0)
    all_rows = np.concatenate([ub_rows, empty])
    all_cols = np.concatenate([ub_cols, np.zeros(empty.size, dtype=np.int64)])
    order = np.argsort(all_rows, kind="stable")
    slot_row = all_rows[order].astype(np.int32)
    slot_src = all_cols[order].astype(np.int32)
    n_slots = slot_row.size
    slot_of = np.empty(n_slots, dtype=np.int64)
    slot_of[order] = np.arange(n_slots)
    row_count = np.bincount(slot_row, minlength=n_rt).astype(np.int32)
    row_start = np.concatenate(
        [[0], np.cumsum(row_count[:-1])]
    ).astype(np.int32)
    return slot_row, slot_src, row_start, row_count, slot_of, n_slots


def _popcount(bitmaps: np.ndarray) -> int:
    """Total set bits across a bitmap stack (the packed edge count)."""
    fn = getattr(np, "bitwise_count", None)
    if fn is not None:
        return int(fn(bitmaps).sum())
    return int(np.unpackbits(bitmaps.view(np.uint8)).sum())


def merge_block_sparse(parts: "list[BlockSparseBitmap]") -> BlockSparseBitmap:
    """Merge per-shard packed incidences into one (DESIGN.md §7).

    Every part must pack a disjoint edge subset of the *same* logical
    matrix (equal ``n_dst``/``n_src``).  Slots sharing a (row tile, src
    tile) block are OR-folded; pad slots are dropped and re-derived; the
    canonical slot ordering is rebuilt — so the result is byte-identical
    to packing all edges at once, which is what lets sharded extraction
    build ``DevicePackedLayer`` operands shard-at-a-time without ever
    sorting the full edge list in one shot.  Overlapping edges (the same
    (src, dst) cell set in two parts) are rejected, matching
    :func:`pack_bipartite`'s duplicate check.
    """
    if not parts:
        raise ValueError("merge_block_sparse needs at least one part")
    n_dst, n_src = parts[0].n_dst, parts[0].n_src
    for p in parts:
        if p.n_dst != n_dst or p.n_src != n_src:
            raise ValueError("parts disagree on logical matrix shape")
    n_rt = max(-(-n_dst // TILE), 1)
    n_st = max(-(-n_src // TILE), 1)
    rows, cols, maps = [], [], []
    total_bits = 0
    for p in parts:
        live = p.bitmaps.any(axis=(1, 2))  # drop pad slots
        live_maps = p.bitmaps[live]
        rows.append(p.slot_row[live].astype(np.int64))
        cols.append(p.slot_src[live].astype(np.int64))
        maps.append(live_maps)
        total_bits += _popcount(live_maps)
    rows_c = np.concatenate(rows) if rows else np.empty(0, np.int64)
    cols_c = np.concatenate(cols) if cols else np.empty(0, np.int64)
    maps_c = (
        np.concatenate(maps)
        if maps and sum(m.shape[0] for m in maps)
        else np.zeros((0, WORDS, TILE), dtype=np.uint32)
    )
    key = rows_c * n_st + cols_c
    order = np.argsort(key, kind="stable")
    key_s = key[order]
    starts = (
        np.flatnonzero(np.r_[True, key_s[1:] != key_s[:-1]])
        if key_s.size
        else np.empty(0, dtype=np.int64)
    )
    uniq = key_s[starts] if key_s.size else np.empty(0, dtype=np.int64)
    flat = maps_c[order].reshape(-1, TILE * WORDS)
    merged = (
        np.bitwise_or.reduceat(flat, starts, axis=0)
        if starts.size
        else np.zeros((0, TILE * WORDS), dtype=np.uint32)
    )
    if _popcount(merged) != total_bits:
        raise ValueError(
            "merge_block_sparse requires disjoint edge shards "
            "(a (src, dst) cell is set in more than one part)"
        )
    slot_row, slot_src, row_start, row_count, slot_of, n_slots = _slot_layout(
        uniq // n_st, uniq % n_st, n_rt
    )
    full = np.concatenate(
        [merged, np.zeros((n_slots - uniq.size, TILE * WORDS), dtype=np.uint32)]
    )
    # slot i holds the block that _slot_layout placed at position i:
    # candidate j (real blocks first, pads after) lands at slot slot_of[j]
    bitmaps = np.empty((n_slots, TILE * WORDS), dtype=np.uint32)
    bitmaps[slot_of] = full
    return BlockSparseBitmap(
        slot_src=slot_src,
        slot_row=slot_row,
        bitmaps=bitmaps.reshape(n_slots, WORDS, TILE),
        row_start=row_start,
        row_count=row_count,
        n_dst=n_dst,
        n_src=n_src,
    )


def pack_bipartite(
    edges: BipartiteEdges,
    method: str = "reduceat",
    shard_edges: Optional[int] = None,
) -> BlockSparseBitmap:
    """Pack dst-major: y[dst] += x[src]  ==  y = B @ x with B[dst, src]=1.

    Duplicate (src, dst) pairs are rejected — a bitmap holds one bit per
    cell (condensed incidence layers are duplicate-free by construction;
    multiplicity lives across *paths*, not within a layer).

    ``method`` selects the fold strategy: ``'reduceat'`` (default) sorts
    edges once by a combined (block, row, word, bit) key — that single
    sort yields the duplicate check, the block grouping, *and* the word
    runs, folded with one buffered ``np.bitwise_or.reduceat`` pass;
    ``'scatter'`` is the original algorithm (two ``np.unique`` sorts plus
    an unbuffered ``np.bitwise_or.at`` scatter), kept as the before/after
    baseline for ``benchmarks/bench_kernels.py``.

    ``shard_edges`` bounds the edges packed in one shot (DESIGN.md §7):
    larger edge lists are packed slice by slice and OR-merged
    *incrementally* with :func:`merge_block_sparse` — byte-identical
    output, with resident packing state bounded by the accumulated packed
    form plus one slice's pack (never all slices at once, whose per-slice
    pad slots would otherwise dwarf the final structure on tall
    matrices).
    """
    if method not in ("reduceat", "scatter"):
        raise ValueError(f"unknown pack method {method!r}")
    if shard_edges is not None and edges.n_edges > shard_edges:
        width = max(int(shard_edges), 1)
        acc: Optional[BlockSparseBitmap] = None
        for lo in range(0, edges.n_edges, width):
            part = pack_bipartite(
                BipartiteEdges(
                    edges.src[lo : lo + width],
                    edges.dst[lo : lo + width],
                    edges.n_src,
                    edges.n_dst,
                ),
                method=method,
            )
            acc = part if acc is None else merge_block_sparse([acc, part])
        assert acc is not None
        return acc
    src = edges.src
    dst = edges.dst
    n_rt = max(-(-edges.n_dst // TILE), 1)
    n_st = max(-(-edges.n_src // TILE), 1)
    bd = dst // TILE
    bs = src // TILE
    r = (dst % TILE).astype(np.int64)
    c = (src % TILE).astype(np.int64)
    word = r // 32
    bit = (r % 32).astype(np.uint32)
    bkey = bd.astype(np.int64) * n_st + bs

    if method == "scatter":
        key = dst.astype(np.int64) * edges.n_src + src
        if np.unique(key).size != key.size:
            raise ValueError("pack_bipartite requires duplicate-free edges")
        uniq, inv = np.unique(bkey, return_inverse=True)
    else:
        # one sort does everything: the full key is unique per (src, dst)
        # cell (duplicate check), its high bits group blocks row-major
        # with source tiles ascending (the kernel's streaming order), and
        # its (word, column) middle bits delimit the bitmap-word runs.
        # All field widths are powers of two, so packing/unpacking is
        # pure shift/mask — the residual cost after the scatter is gone.
        low = _C_BITS + _W_BITS + _B_BITS
        full = (
            (bkey << low)
            | (word << (_C_BITS + _B_BITS))
            | (c << _B_BITS)
            | bit
        )
        order_e = np.argsort(full, kind="stable")
        full_s = full[order_e]
        if full_s.size and np.any(full_s[1:] == full_s[:-1]):
            raise ValueError("pack_bipartite requires duplicate-free edges")
        bkey_s = full_s >> low
        block_bounds = np.flatnonzero(
            np.r_[True, bkey_s[1:] != bkey_s[:-1]]
        ) if bkey_s.size else np.empty(0, dtype=np.int64)
        uniq = bkey_s[block_bounds] if bkey_s.size else np.empty(0, np.int64)

    # pad every empty row tile with one all-zero slot so each output tile
    # is visited (and therefore written) by the kernel
    slot_row, slot_src, row_start, row_count, slot_of, n_slots = _slot_layout(
        uniq // n_st, uniq % n_st, n_rt
    )

    flat = np.zeros(n_slots * TILE * WORDS, dtype=np.uint32)
    if src.size:
        if method == "scatter":
            lin = (slot_of[inv] * WORDS + word) * TILE + c
            np.bitwise_or.at(flat, lin, np.uint32(1) << bit)
        else:
            # slot_of is monotone over sorted blocks (pads append after
            # each row's real slots), so the sorted edge order is also
            # sorted by (slot, word, column): reduceat folds each word run
            block_of_edge = np.repeat(
                slot_of[: uniq.size],
                np.diff(np.r_[block_bounds, full_s.size]),
            )
            rw_s = (full_s >> _B_BITS) & (TILE * WORDS - 1)
            lin_s = (block_of_edge << (_C_BITS + _W_BITS)) | rw_s
            starts = np.flatnonzero(np.r_[True, lin_s[1:] != lin_s[:-1]])
            vals_s = np.uint32(1) << bit[order_e]
            flat[lin_s[starts]] = np.bitwise_or.reduceat(vals_s, starts)
    bitmaps = flat.reshape(n_slots, WORDS, TILE)
    return BlockSparseBitmap(
        slot_src=slot_src,
        slot_row=slot_row,
        bitmaps=bitmaps,
        row_start=row_start,
        row_count=row_count,
        n_dst=edges.n_dst,
        n_src=edges.n_src,
    )


def measure_pack_throughput(
    edges: BipartiteEdges,
    methods: "tuple[str, ...]" = ("reduceat", "scatter"),
    repeats: int = 3,
    time_fn=None,
) -> "dict[str, float]":
    """Measured edges/second of ``pack_bipartite`` per fold method.

    Feeds the extraction cost model (``repro.core.cost.Throughputs``) the
    same way ``measure_crossover`` feeds kernel dispatch: a small measured
    table that overrides the analytic default.  ``time_fn`` is injectable
    for deterministic tests (same contract as ``autotune.measure_crossover``:
    it receives a zero-arg callable and returns elapsed seconds).
    """
    import time as _time

    out: "dict[str, float]" = {}
    for method in methods:
        if time_fn is not None:
            elapsed = float(time_fn(lambda: pack_bipartite(edges, method=method)))
        else:
            elapsed = float("inf")
            for _ in range(max(1, repeats)):
                t0 = _time.perf_counter()
                pack_bipartite(edges, method=method)
                elapsed = min(elapsed, _time.perf_counter() - t0)
        out[method] = edges.n_edges / max(elapsed, 1e-9)
    return out
