"""Pallas TPU kernel: bit-packed block-sparse SpMM (the condensed hot loop).

Computes ``y = B @ x`` where ``B`` is the 0/1 incidence of one condensed
layer, stored as a streamed slot list of bitmap blocks
(:mod:`repro.kernels.pack`).  Two calls realize the paper's 2-hop condensed
propagation ``y = B_out (B_in^T x)`` without ever materializing the
expanded adjacency.

TPU mapping (see DESIGN.md §6):

* grid = (feature tiles, slots); the inner axis walks the packed slot
  stream — sorted by (dst row tile, src tile) — so the Pallas pipeline
  streams one (128, Fb) source tile per step through a double-buffered
  VMEM window (tile t+1 is fetched while the MXU consumes tile t).
  Per-cell VMEM is O(window), independent of n_src: no resident source
  column, no 8 MiB cliff.
* the slot tables (``slot_src``, ``slot_row``) and the per-row-tile
  (start, count) run table are scalar-prefetched into SMEM; the BlockSpec
  index maps read them to route each slot's source tile and output tile —
  a data-dependent gather at tile granularity, which is the TPU-friendly
  kind.
* bitmaps (4 x 128 uint32 = 2 KiB) are unpacked in-register into a dense
  128x128 0/1 operand — 32x less HBM traffic than an f32 block.  Their
  minor axis is the 128 columns, so the TPU keeps them in a compact
  (4, 128) tiling that the kernel reads in place.
* a (128, Fb) f32 VMEM scratch accumulates across a row tile's slots; the
  run table marks the first slot (init) and last slot (write-out), so
  each output tile is written exactly once.
* ``op`` selects the ⊕-reduction: ``'sum'`` feeds the MXU
  (``jnp.dot(mask, x)``); ``'min'``/``'max'`` run the idempotent-semiring
  variant — masked select over column chunks on the VPU, so min-plus /
  max-times / or-and propagation (batched BFS, reachability) runs packed
  too, and ``inf`` frontiers never meet a multiply.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pack import STREAM_CHUNK as _CHUNK
from .pack import TILE, WORDS

__all__ = [
    "bitmap_spmm_pallas",
    "bitmap_spmm_fused_pallas",
    "default_interpret",
]

# _CHUNK: source rows the masked select (min/max ops) reads from the
# window per loop step, one aligned (8, Fb) sublane group; it lives in
# pack, whose footprint formula still reserves a (TILE, _CHUNK, Fb)
# intermediate for it.


# The 0/1 mask times an f32 frontier must not round the frontier: at the
# default precision the MXU takes f32 operands as one bf16 pass.
_EXACT = jax.lax.Precision.HIGHEST


def default_interpret() -> bool:
    """Interpret mode policy: compiled on TPU, interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _unpack_bits(words: jnp.ndarray) -> jnp.ndarray:
    """(WORDS, TILE) uint32 -> (TILE, TILE) 0/1 int32, in-register: bit
    ``b`` of word ``[w, c]`` is row ``32 w + b``, column ``c``
    (:mod:`repro.kernels.pack`).

    Mosaic casts int32, not uint32, to float, so the mask leaves as int32.
    """
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (WORDS, 32, TILE), 1)
    bits = (words[:, None, :] >> shifts) & jnp.uint32(1)
    return bits.reshape(TILE, TILE).astype(jnp.int32)


def _kernel(
    slot_src_ref,   # scalar prefetch: (n_slots,) source tile per slot
    slot_row_ref,   # scalar prefetch: (n_slots,) dst row tile per slot
    row_start_ref,  # scalar prefetch: (n_rt,) run table starts
    row_count_ref,  # scalar prefetch: (n_rt,) run table counts
    bitmaps_ref,    # (1, WORDS, TILE) current slot's bitmap
    x_ref,          # (row_window, Fb) current source window (streamed)
    y_ref,          # (TILE, Fb) output tile of the slot's row
    acc_ref,        # VMEM scratch: (TILE, Fb) f32 accumulator
    *,
    op: str,
    zero: float,
    window_tiles: int,
):
    s = pl.program_id(1)
    row = slot_row_ref[s]
    start = row_start_ref[row]
    first = s == start
    last = s == start + row_count_ref[row] - 1
    init = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}[op]

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.full(acc_ref.shape, init, acc_ref.dtype)

    # the fetched window spans window_tiles source tiles; this slot's
    # bitmap addresses one of them (slot_src modulo the window)
    base = 0
    if window_tiles > 1:
        base = pl.multiple_of((slot_src_ref[s] % window_tiles) * TILE, TILE)
    if op == "sum":
        mask = _unpack_bits(bitmaps_ref[0]).astype(x_ref.dtype)
        acc_ref[...] += jnp.dot(
            mask,
            x_ref[pl.ds(base, TILE), :],
            preferred_element_type=jnp.float32,
            precision=_EXACT,
        )
    else:
        # masked select, one source row at a time: column r of the mask,
        # taken out by a lane reduction, gates row r of the window
        mask = _unpack_bits(bitmaps_ref[0])
        lane = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
        fill = jnp.inf if op == "min" else -jnp.inf
        combine = jnp.minimum if op == "min" else jnp.maximum

        def body(c, acc):
            start = pl.multiple_of(base + c * _CHUNK, _CHUNK)
            xc = x_ref[pl.ds(start, _CHUNK), :].astype(jnp.float32)
            for k in range(_CHUNK):
                hit = jnp.max(
                    jnp.where(lane == c * _CHUNK + k, mask, 0),
                    axis=1,
                    keepdims=True,
                )
                acc = combine(acc, jnp.where(hit != 0, xc[k : k + 1, :], fill))
            return acc

        acc_ref[...] = jax.lax.fori_loop(0, TILE // _CHUNK, body, acc_ref[...])

    @pl.when(last)
    def _():
        out = acc_ref[...]
        # rows with no incident sources take the semiring zero, matching
        # the segment-reduce path's empty-segment convention
        if op == "min":
            out = jnp.where(jnp.isposinf(out), jnp.float32(zero), out)
        elif op == "max":
            out = jnp.where(jnp.isneginf(out), jnp.float32(zero), out)
        y_ref[...] = out.astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_dst_pad", "feature_block", "op", "zero", "interpret", "row_window"
    ),
)
def _bitmap_spmm_pallas(
    slot_src: jnp.ndarray,
    slot_row: jnp.ndarray,
    row_start: jnp.ndarray,
    row_count: jnp.ndarray,
    bitmaps: jnp.ndarray,
    x: jnp.ndarray,
    n_dst_pad: int,
    feature_block: int,
    op: str,
    zero: float,
    interpret: bool,
    row_window: int,
) -> jnp.ndarray:
    n_slots = slot_src.shape[0]
    n_src_pad, f = x.shape
    w = row_window // TILE
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(f // feature_block, n_slots),
        in_specs=[
            pl.BlockSpec(
                (1, WORDS, TILE), lambda j, s, ss, sr, rs, rc: (s, 0, 0)
            ),
            pl.BlockSpec(
                (row_window, feature_block),
                lambda j, s, ss, sr, rs, rc: (ss[s] // w, j),
            ),
        ],
        out_specs=pl.BlockSpec(
            (TILE, feature_block), lambda j, s, ss, sr, rs, rc: (sr[s], j)
        ),
        scratch_shapes=[pltpu.VMEM((TILE, feature_block), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, op=op, zero=zero, window_tiles=w),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_dst_pad, f), x.dtype),
        interpret=interpret,
    )(slot_src, slot_row, row_start, row_count, bitmaps, x)


def _fused_kernel(
    kind_ref,       # scalar prefetch: (n_slots,) 0 = incidence, 1 = correction
    main_src_ref,   # scalar prefetch: (n_slots,) h source tile per slot
    corr_src_ref,   # scalar prefetch: (n_slots,) x source tile per slot
    main_idx_ref,   # scalar prefetch: (n_slots,) main bitmap index (BlockSpec)
    corr_idx_ref,   # scalar prefetch: (n_slots,) corr plane index (BlockSpec)
    slot_row_ref,   # scalar prefetch: (n_slots,) dst row tile per slot
    row_start_ref,  # scalar prefetch: (n_rt,) run table starts
    row_count_ref,  # scalar prefetch: (n_rt,) run table counts
    bitmaps_ref,    # (1, WORDS, TILE) current main slot's bitmap
    planes_ref,     # (1, P, WORDS, TILE) current correction slot's planes
    h_ref,          # (TILE, Fb) last-hidden source tile (main slots)
    x_ref,          # (TILE, Fb) input-frontier source tile (corr slots)
    y_ref,          # (TILE, Fb) output tile of the slot's row
    acc_ref,        # VMEM scratch: (TILE, Fb) f32 main accumulator
    cacc_ref,       # VMEM scratch: (TILE, Fb) f32 correction accumulator
    *,
    plane_weights: tuple,
):
    """Fused DEDUP-C epilogue (DESIGN.md §6): walk the interleaved
    main/correction slot stream, accumulate the two terms separately, and
    write ``acc − cacc`` once per output tile — the same arithmetic as
    SpMM-then-subtract, in one launch.  Correction slots reconstruct the
    integer count matrix from bit-planes: ``Σ_k 2^k (D_k ⊙ x)``; each
    plane feeds the MXU like a main slot, and the power-of-two scaling is
    float-exact."""
    s = pl.program_id(1)
    row = slot_row_ref[s]
    start = row_start_ref[row]
    first = s == start
    last = s == start + row_count_ref[row] - 1
    is_corr = kind_ref[s] == 1

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
        cacc_ref[...] = jnp.zeros(cacc_ref.shape, cacc_ref.dtype)

    @pl.when(jnp.logical_not(is_corr))
    def _():
        mask = _unpack_bits(bitmaps_ref[0]).astype(h_ref.dtype)
        acc_ref[...] += jnp.dot(
            mask, h_ref[...], preferred_element_type=jnp.float32,
            precision=_EXACT,
        )

    @pl.when(is_corr)
    def _():
        cacc = cacc_ref[...]
        for k, w in enumerate(plane_weights):
            mask = _unpack_bits(planes_ref[0, k]).astype(x_ref.dtype)
            cacc = cacc + jnp.float32(w) * jnp.dot(
                mask, x_ref[...], preferred_element_type=jnp.float32,
                precision=_EXACT,
            )
        cacc_ref[...] = cacc

    @pl.when(last)
    def _():
        y_ref[...] = (acc_ref[...] - cacc_ref[...]).astype(y_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_dst_pad", "feature_block", "plane_weights", "interpret"
    ),
)
def _bitmap_spmm_fused(
    kind: jnp.ndarray,
    main_src: jnp.ndarray,
    corr_src: jnp.ndarray,
    main_idx: jnp.ndarray,
    corr_idx: jnp.ndarray,
    slot_row: jnp.ndarray,
    row_start: jnp.ndarray,
    row_count: jnp.ndarray,
    bitmaps: jnp.ndarray,
    planes: jnp.ndarray,
    h: jnp.ndarray,
    x: jnp.ndarray,
    n_dst_pad: int,
    feature_block: int,
    plane_weights: tuple,
    interpret: bool,
) -> jnp.ndarray:
    n_slots = kind.shape[0]
    f = h.shape[1]
    n_planes = planes.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(f // feature_block, n_slots),
        in_specs=[
            pl.BlockSpec(
                (1, WORDS, TILE),
                lambda j, s, kd, ms, cs, mi, ci, sr, rs, rc: (mi[s], 0, 0),
            ),
            pl.BlockSpec(
                (1, n_planes, WORDS, TILE),
                lambda j, s, kd, ms, cs, mi, ci, sr, rs, rc: (ci[s], 0, 0, 0),
            ),
            pl.BlockSpec(
                (TILE, feature_block),
                lambda j, s, kd, ms, cs, mi, ci, sr, rs, rc: (ms[s], j),
            ),
            pl.BlockSpec(
                (TILE, feature_block),
                lambda j, s, kd, ms, cs, mi, ci, sr, rs, rc: (cs[s], j),
            ),
        ],
        out_specs=pl.BlockSpec(
            (TILE, feature_block),
            lambda j, s, kd, ms, cs, mi, ci, sr, rs, rc: (sr[s], j),
        ),
        scratch_shapes=[
            pltpu.VMEM((TILE, feature_block), jnp.float32),
            pltpu.VMEM((TILE, feature_block), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, plane_weights=plane_weights),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_dst_pad, f), h.dtype),
        interpret=interpret,
    )(
        kind, main_src, corr_src, main_idx, corr_idx,
        slot_row, row_start, row_count,
        bitmaps, planes, h, x,
    )


def bitmap_spmm_fused_pallas(
    kind: jnp.ndarray,       # (n_slots,) int32
    main_src: jnp.ndarray,   # (n_slots,) int32
    corr_src: jnp.ndarray,   # (n_slots,) int32
    main_idx: jnp.ndarray,   # (n_slots,) int32
    corr_idx: jnp.ndarray,   # (n_slots,) int32
    slot_row: jnp.ndarray,   # (n_slots,) int32
    row_start: jnp.ndarray,  # (n_rt,) int32
    row_count: jnp.ndarray,  # (n_rt,) int32
    bitmaps: jnp.ndarray,    # (n_main, WORDS, TILE) uint32
    planes: jnp.ndarray,     # (n_corr, P, WORDS, TILE) uint32
    h: jnp.ndarray,          # (n_h_pad, F) last-hidden frontier
    x: jnp.ndarray,          # (n_x_pad, F) original input frontier
    n_dst_pad: int,
    plane_weights: "tuple[float, ...]",
    feature_block: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused last-layer SpMM with the DEDUP-C subtraction in the epilogue:
    ``y = B h − D x`` over one interleaved slot stream
    (:func:`repro.kernels.correction.build_fused_stream`), plus-times
    ring only.  ``h`` and ``x`` are the two streamed feature operands —
    the last hidden frontier and the original input — each padded to its
    own tile multiple; both must share the feature width ``F``."""
    if h.shape[1] != x.shape[1]:
        raise ValueError(
            f"h and x must share the feature axis: {h.shape} vs {x.shape}"
        )
    f = h.shape[1]
    if (
        n_dst_pad % TILE
        or f % feature_block
        or h.shape[0] % TILE
        or x.shape[0] % TILE
    ):
        raise ValueError(
            f"padded dims required: n_dst_pad={n_dst_pad}, f={f}, "
            f"h_rows={h.shape[0]}, x_rows={x.shape[0]} (TILE={TILE}, "
            f"fb={feature_block})"
        )
    if planes.shape[1] != len(plane_weights):
        raise ValueError("plane_weights must match the plane count")
    if interpret is None:
        interpret = default_interpret()
    return _bitmap_spmm_fused(
        kind, main_src, corr_src, main_idx, corr_idx,
        slot_row, row_start, row_count,
        bitmaps, planes, h, x,
        n_dst_pad=n_dst_pad,
        feature_block=feature_block,
        plane_weights=tuple(float(w) for w in plane_weights),
        interpret=bool(interpret),
    )


def bitmap_spmm_pallas(
    slot_src: jnp.ndarray,   # (n_slots,) int32
    slot_row: jnp.ndarray,   # (n_slots,) int32
    row_start: jnp.ndarray,  # (n_rt,) int32
    row_count: jnp.ndarray,  # (n_rt,) int32
    bitmaps: jnp.ndarray,    # (n_slots, WORDS, TILE) uint32
    x: jnp.ndarray,          # (n_src_pad, F); row_window/fb multiples
    n_dst_pad: int,
    feature_block: int = 128,
    op: str = "sum",
    zero: float = 0.0,
    interpret: bool | None = None,
    row_window: int = TILE,
) -> jnp.ndarray:
    """Streamed bit-packed SpMM: ``y = B ⊕ x`` over one packed incidence.

    ``op``/``zero`` come from the semiring's ``add_kind``/``zero``
    (``'sum'`` = plus-times on the MXU; ``'min'``/``'max'`` = idempotent
    masked select).  ``interpret=None`` auto-selects compiled mode on TPU
    and interpret mode elsewhere (:func:`default_interpret`).

    ``(row_window, feature_block)`` is the autotuned window configuration
    (:mod:`repro.kernels.autotune`): ``feature_block`` tiles the feature /
    batch axis (the outer grid axis walks ``F`` in ``feature_block``-wide
    tiles, so ``B ≫ 128`` frontiers stream through the same pipeline) and
    ``row_window`` is the number of source rows fetched per streamed step
    — a multiple of ``TILE``; windows wider than one tile amortize DMA
    issue over more resident rows, and the slot's bitmap addresses its
    ``TILE``-row sub-tile of the window.
    """
    if op not in ("sum", "min", "max"):
        raise ValueError(f"unknown kernel op {op!r}")
    if row_window % TILE or row_window <= 0:
        raise ValueError(f"row_window must be a positive multiple of {TILE}")
    n_src_pad, f = x.shape
    if n_dst_pad % TILE or f % feature_block or n_src_pad % row_window:
        raise ValueError(
            f"padded dims required: n_dst_pad={n_dst_pad}, f={f}, "
            f"n_src_pad={n_src_pad} (TILE={TILE}, fb={feature_block}, "
            f"row_window={row_window})"
        )
    if interpret is None:
        interpret = default_interpret()
    return _bitmap_spmm_pallas(
        slot_src,
        slot_row,
        row_start,
        row_count,
        bitmaps,
        x,
        n_dst_pad=n_dst_pad,
        feature_block=feature_block,
        op=op,
        zero=float(zero),
        interpret=bool(interpret),
        row_window=int(row_window),
    )
