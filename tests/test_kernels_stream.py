"""Streamed-kernel suite (DESIGN.md §6): the Pallas slot-stream SpMM must
match the segment-reduce oracle across everything the old resident-column
kernel excluded — source columns above the old 8 MiB VMEM budget,
``reverse=True`` (transposed packing), idempotent semirings (min/max
masked-select variant), ragged last tiles, and B=1 vs B>1 frontiers —
and the auto-dispatchers must actually *send* those cases to the kernel
(no silent XLA fallback)."""
import zlib

import numpy as np
import jax.numpy as jnp
import pytest

from conftest import random_bipartite, random_membership_graph
from oracle import bipartite_semiring_ref

from repro.core import dedup, engine
from repro.core.condensed import BipartiteEdges
from repro.core.semiring import (
    MAX_MIN,
    MAX_TIMES,
    MIN_PLUS,
    OR_AND,
    PLUS_TIMES,
    kernelizable,
)
from repro.kernels.ops import PackedLayer, bitmap_spmm, resolve_backend
from repro.kernels.pack import (
    TILE,
    fits_vmem,
    pack_bipartite,
    streamed_footprint_bytes,
)
# The lifted budget: the old dispatcher kept the (n_src_pad, Fb) source
# column resident and fell back to XLA above this many bytes.
OLD_COLUMN_BUDGET = 8 * 2**20

SEMIRINGS = [PLUS_TIMES, MIN_PLUS, MAX_TIMES, OR_AND, MAX_MIN]


def _frontier(rng, n, b, semiring):
    if semiring is MIN_PLUS:
        x = np.where(rng.random((n, b)) < 0.3, rng.random((n, b)), np.inf)
    elif semiring is MAX_MIN:
        # widths: mostly-zero non-negative, a few inf sources
        x = np.where(rng.random((n, b)) < 0.3, rng.random((n, b)), 0.0)
        x = np.where(rng.random((n, b)) < 0.05, np.inf, x)
    elif semiring in (MAX_TIMES, OR_AND):
        x = (rng.random((n, b)) < 0.4).astype(np.float64) * rng.random((n, b))
    else:
        x = rng.standard_normal((n, b))
    return x.astype(np.float32)


# ---------------------------------------------------------------------------
# Parity: kernel == segment oracle, all semirings x directions x shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    # (n_src, n_dst, n_edges, B) — ragged last tiles and B=1 vs B>1
    (4, 4, 6, 1),
    (130, 257, 900, 3),
    (300, 300, 3000, 1),
    (513, 200, 4000, 7),
])
@pytest.mark.parametrize("semiring", SEMIRINGS, ids=lambda s: s.name)
@pytest.mark.parametrize("reverse", [False, True])
def test_kernel_matches_segment_oracle(shape, semiring, reverse):
    n_src, n_dst, n_e, b = shape
    # crc32, not hash(): str hashing is salted per process, and a seed
    # that changes every run makes parity failures unreproducible
    seed = zlib.crc32(f"{shape}{semiring.name}{reverse}".encode())
    rng = np.random.default_rng(seed)
    e = random_bipartite(n_src, n_dst, n_e, rng)
    layer = PackedLayer.from_edges(e)
    n_in = n_dst if reverse else n_src
    n_out = n_src if reverse else n_dst
    x = _frontier(rng, n_in, b, semiring)
    # shared NumPy differential oracle (tests/oracle.py) — no JAX on the
    # reference side, so a bug in the segment path can't cancel out
    want = bipartite_semiring_ref(e, x, semiring, reverse=reverse).astype(
        np.float32
    )
    got = np.asarray(bitmap_spmm(
        layer, jnp.asarray(x), backend="pallas",
        semiring=semiring, reverse=reverse,
    ))
    assert got.shape == (n_out, b)
    atol = 1e-4 if semiring is PLUS_TIMES else 0.0
    assert np.allclose(got, want, atol=atol), (
        np.abs(got - want).max(), semiring.name, reverse
    )


def test_vector_frontier_matches_matrix_column():
    """B=1 via a 1-D frontier squeezes back and equals the (n, 1) call."""
    rng = np.random.default_rng(3)
    e = random_bipartite(90, 70, 500, rng)
    layer = PackedLayer.from_edges(e)
    x = rng.standard_normal(90).astype(np.float32)
    y1 = bitmap_spmm(layer, jnp.asarray(x), backend="pallas")
    y2 = bitmap_spmm(layer, jnp.asarray(x[:, None]), backend="pallas")
    assert y1.shape == (70,)
    assert np.array_equal(np.asarray(y1), np.asarray(y2)[:, 0])


# ---------------------------------------------------------------------------
# The lifted cliff: above-old-budget columns dispatch packed, exactly
# ---------------------------------------------------------------------------

def _tall_clustered_edges(rng, n_src=20480, n_dst=200, tiles_hit=10, per=48):
    srcs, dsts = [], []
    for t in rng.choice(n_src // TILE, size=tiles_hit, replace=False):
        s = rng.choice(TILE, size=per, replace=False) + int(t) * TILE
        d = rng.choice(n_dst, size=per, replace=False)
        srcs.append(s)
        dsts.append(d)
    src, dst = np.concatenate(srcs), np.concatenate(dsts)
    key = dst.astype(np.int64) * n_src + src
    _, idx = np.unique(key, return_index=True)
    return BipartiteEdges(src[idx], dst[idx], n_src, n_dst)


def test_above_old_budget_column_dispatches_to_kernel_exactly():
    rng = np.random.default_rng(0)
    e = _tall_clustered_edges(rng)
    layer = PackedLayer.from_edges(e)
    f = 128
    col_bytes = layer.bsb.n_src_tiles * TILE * f * 4
    assert col_bytes > OLD_COLUMN_BUDGET, "test must cross the old cliff"
    # the new streaming-aware formula dispatches to the kernel...
    assert resolve_backend("auto", f, 128, 4) == "pallas"
    assert fits_vmem(f, 128, 4)
    # ...and the footprint really is column-size independent
    assert streamed_footprint_bytes(f, 128, 4) < OLD_COLUMN_BUDGET
    # integer-valued floats: sums are exact in f32, so exact equality
    x = rng.integers(-4, 5, size=(e.n_src, f)).astype(np.float32)
    got = np.asarray(bitmap_spmm(layer, jnp.asarray(x), backend="auto"))
    want = bipartite_semiring_ref(e, x, PLUS_TIMES).astype(np.float32)
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# Engine dispatch: forward, reverse, and idempotent all hit the kernel
# ---------------------------------------------------------------------------

def _packed_pair(seed=11):
    rng = np.random.default_rng(seed)
    g = random_membership_graph(40, 12, 4, rng)
    corr = dedup.build_correction(g)
    return (
        engine.to_device(g, correction=corr),
        engine.to_device_packed(g, correction=corr, backend="pallas"),
        g,
        rng,
    )


@pytest.mark.parametrize("reverse", [False, True])
@pytest.mark.parametrize(
    "semiring", SEMIRINGS, ids=lambda s: s.name
)
def test_engine_packed_dispatches_and_matches_segment(reverse, semiring):
    coo, packed, g, rng = _packed_pair()
    X = jnp.asarray(_frontier(rng, g.n_real, 4, semiring))
    engine.reset_kernel_dispatch_count()
    y_seg = np.asarray(engine.propagate(coo, X, semiring, reverse=reverse))
    assert engine.KERNEL_DISPATCH_COUNT == 0  # COO graph: segment path only
    y_pk = np.asarray(engine.propagate(packed, X, semiring, reverse=reverse))
    assert engine.KERNEL_DISPATCH_COUNT > 0, (
        f"{semiring.name} reverse={reverse} fell back to the segment path"
    )
    atol = 1e-4 if semiring is PLUS_TIMES else 0.0
    assert np.allclose(y_pk, y_seg, atol=atol), (semiring.name, reverse)


def test_kernel_applicable_policy():
    _, packed, g, rng = _packed_pair()
    layer = packed.chains[0][0]
    X = jnp.zeros((layer.n_src, 3), jnp.float32)
    for reverse in (False, True):
        for sr in SEMIRINGS:
            assert engine._kernel_applicable(packed, layer, X, sr, reverse)
    # 1-D frontiers and non-kernelizable semirings stay on segment path
    assert not engine._kernel_applicable(
        packed, layer, jnp.zeros(layer.n_src), PLUS_TIMES, False
    )
    # explicit xla backend wins
    import dataclasses
    xla = dataclasses.replace(packed, backend="xla")
    assert not engine._kernel_applicable(xla, layer, X, PLUS_TIMES, False)
    # auto only picks pallas on a real TPU (interpret mode is test-only)
    auto = dataclasses.replace(packed, backend="auto")
    import jax
    expected = jax.default_backend() == "tpu"
    assert engine._kernel_applicable(auto, layer, X, PLUS_TIMES, False) == expected


def test_engine_reverse_equals_transposed_forward():
    """reverse=True on the packed rep == forward on the reversed graph
    (the HITS / out-degree direction), per chain layer."""
    coo, packed, g, rng = _packed_pair(seed=5)
    X = jnp.asarray(rng.standard_normal((g.n_real, 3)).astype(np.float32))
    engine.reset_kernel_dispatch_count()
    y_rev = np.asarray(engine.propagate(packed, X, PLUS_TIMES, reverse=True))
    assert engine.KERNEL_DISPATCH_COUNT > 0
    y_coo = np.asarray(engine.propagate(coo, X, PLUS_TIMES, reverse=True))
    assert np.allclose(y_rev, y_coo, atol=1e-4)


# ---------------------------------------------------------------------------
# Packing: run-table integrity, method equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_pack_methods_identical(seed):
    rng = np.random.default_rng(seed)
    e = random_bipartite(
        int(rng.integers(1, 500)), int(rng.integers(1, 500)),
        int(rng.integers(0, 2500)), rng,
    )
    a = pack_bipartite(e, method="scatter")
    b = pack_bipartite(e, method="reduceat")
    for f in ("slot_src", "slot_row", "bitmaps", "row_start", "row_count"):
        assert np.array_equal(getattr(a, f), getattr(b, f)), f


def test_pack_run_table_integrity():
    rng = np.random.default_rng(7)
    e = random_bipartite(700, 400, 2000, rng)
    bsb = pack_bipartite(e)
    n_rt = -(-e.n_dst // TILE)
    assert bsb.row_start.shape == (n_rt,) and bsb.row_count.shape == (n_rt,)
    assert (bsb.row_count >= 1).all()  # empty rows carry a pad slot
    assert bsb.row_count.sum() == bsb.n_slots
    assert np.array_equal(
        bsb.row_start, np.r_[0, np.cumsum(bsb.row_count[:-1])]
    )
    # slots sorted by (row, src tile): the kernel's streaming order
    order_key = bsb.slot_row.astype(np.int64) * (bsb.n_src_tiles + 1) + bsb.slot_src
    real = bsb.bitmaps.any(axis=(1, 2))
    assert (np.diff(order_key[real]) > 0).all()
    for i in range(n_rt):
        assert (bsb.slot_row[bsb.row_start[i]:bsb.row_start[i] + bsb.row_count[i]] == i).all()


def test_zero_source_layer_is_kernel_safe():
    """Pad slots index source tile 0, so a zero-source layer must still
    pad x to one inert tile instead of handing the kernel a 0-row operand."""
    e = BipartiteEdges(np.array([], np.int64), np.array([], np.int64), 0, 256)
    layer = PackedLayer.from_edges(e)
    y = bitmap_spmm(layer, jnp.zeros((0, 4), jnp.float32), backend="pallas")
    assert y.shape == (256, 4) and not np.asarray(y).any()
    y = bitmap_spmm(
        layer, jnp.zeros((256, 4), jnp.float32), backend="pallas", reverse=True
    )
    assert y.shape == (0, 4)


def test_pack_unknown_method_rejected():
    e = BipartiteEdges(np.array([0]), np.array([0]), 1, 1)
    with pytest.raises(ValueError):
        pack_bipartite(e, method="magic")


def test_reverse_pack_is_transpose():
    rng = np.random.default_rng(9)
    e = random_bipartite(300, 150, 1200, rng)
    layer = PackedLayer.from_edges(e)
    fwd = layer.bsb.to_dense()[: e.n_dst, : e.n_src]
    rev = layer.bsb_rev.to_dense()[: e.n_src, : e.n_dst]
    assert np.array_equal(fwd.T, rev)


# ---------------------------------------------------------------------------
# Dispatch plumbing
# ---------------------------------------------------------------------------

def test_resolve_backend_policy():
    assert resolve_backend("pallas", 128, 128, 4) == "pallas"
    assert resolve_backend("xla", 128, 128, 4) == "xla"
    assert resolve_backend("auto", 128, 128, 4) == "pallas"
    assert resolve_backend("auto", 128, 128, 4, packable=False) == "xla"
    # unknown (non-kernelizable) semirings conservatively stay on XLA
    import dataclasses
    weird = dataclasses.replace(PLUS_TIMES, name="weird_sum")
    assert not kernelizable(weird)
    assert resolve_backend("auto", 128, 128, 4, semiring=weird) == "xla"
    # an absurd feature block busts the streamed budget -> xla
    assert resolve_backend("auto", 128, 8192 * 16, 4) == "xla"
    # slot tables are scalar-prefetched into SMEM: a block count past the
    # SMEM budget falls back instead of failing inside Mosaic
    assert resolve_backend("auto", 128, 128, 4, n_slots=1_000_000) == "xla"
    assert resolve_backend("auto", 128, 128, 4, n_slots=10_000) == "pallas"
    assert fits_vmem(128, 128, 4, n_slots=10_000)
    assert not fits_vmem(128, 128, 4, n_slots=1_000_000)

