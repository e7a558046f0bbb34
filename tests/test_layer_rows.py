"""The condensed layers' row layout (``repro.core.correction_rows.row_layout``
and ``apply_layout``): the apply against the dense NumPy bipartite
reference of ``oracle.py``, exactly on integer frontiers, under each
semiring ⊕, both directions and every frontier width; the build's
invariants; and a packed graph's XLA path against the segment path of the
same graph in COO."""
import numpy as np
import pytest

from conftest import copurchase_graph, random_bipartite
from oracle import bipartite_semiring_ref

from repro.core import correction_rows as cr
from repro.core import engine, extract
from repro.core.condensed import BipartiteEdges
from repro.core.semiring import MAX_TIMES, MIN_PLUS, PLUS_TIMES
from repro.data.synth import dblp_catalog

Q1 = """
Nodes(ID, Name) :- Author(ID, Name).
Edges(ID1, ID2) :- AuthorPub(ID1, PubID), AuthorPub(ID2, PubID).
"""

SEMIRINGS = {"sum": PLUS_TIMES, "min": MIN_PLUS, "max": MAX_TIMES}


def _dblp():
    return extract(
        dblp_catalog(n_authors=300, n_pubs=500, mean_authors_per_pub=5.0,
                     seed=1), Q1, mode="condensed",
    ).graph


def _tpch():
    return copurchase_graph(47, 160, 31, np.random.default_rng(2))


def _zipf_wide_row():
    """Mostly one to three edges an output, and one output far wider."""
    rng = np.random.default_rng(4)
    e = random_bipartite(200, 90, 400, rng)
    wide = np.unique(rng.integers(0, 200, 1500))
    return BipartiteEdges(
        np.concatenate([e.src, wide]),
        np.concatenate([e.dst, np.full(wide.size, 7)]), 200, 90,
    )


CASES = {
    "empty": lambda: BipartiteEdges(np.zeros(0, int), np.zeros(0, int), 5, 7),
    "isolated_outputs": lambda: BipartiteEdges(
        np.array([0, 3, 3, 8]), np.array([2, 2, 9, 0]), 10, 12,
    ),
    "duplicate_edges": lambda: BipartiteEdges(
        np.array([1, 1, 1, 4, 0]), np.array([0, 0, 2, 2, 2]), 6, 3,
    ),
    "zipf_wide_row": _zipf_wide_row,
    "dblp": lambda: _dblp().chains[0].edges[0],
    "tpch_interior": lambda: _tpch().chains[0].edges[1],
}


@pytest.fixture(scope="module")
def edges():
    cache = {}

    def get(case):
        if case not in cache:
            cache[case] = CASES[case]()
        return cache[case]

    return get


def _layout(e, reverse):
    if reverse:
        return cr.row_layout(e.dst, e.src, e.n_dst, e.n_src)
    return cr.row_layout(e.src, e.dst, e.n_src, e.n_dst)


@pytest.mark.parametrize("width", [None, 1, 8, 32], ids=["1d", "B1", "B8", "B32"])
@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("op", sorted(SEMIRINGS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_apply_matches_dense_reference_exactly(edges, case, op, reverse, width):
    e = edges(case)
    sr = SEMIRINGS[op]
    n_in = e.n_dst if reverse else e.n_src
    rng = np.random.default_rng(n_in)
    shape = (n_in,) if width is None else (n_in, width)
    x = rng.integers(0, 5, shape).astype(np.float32)
    got = np.asarray(cr.apply_layout(_layout(e, reverse), x, sr))
    want = bipartite_semiring_ref(e, x, sr, reverse=reverse)
    assert got.shape == want.shape and got.dtype == np.float32
    assert np.array_equal(got, want)


def _class_rows(rows):
    """Each class of a layout as ``(R_c, K_c)`` source indices."""
    return [i.reshape(-1, k) if k % cr.ROW_TILE == 0 else i.reshape(k, -1).T
            for i, k in zip(rows.idx, rows.widths)]


@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_build_invariants(edges, case, reverse):
    e = edges(case)
    src, dst = (e.dst, e.src) if reverse else (e.src, e.dst)
    n_in, n_out = (e.n_dst, e.n_src) if reverse else (e.n_src, e.n_dst)
    rows = _layout(e, reverse)
    assert len(rows.widths) == len(rows.idx)
    rebuilt = []
    row = 0
    for idx, k in zip(_class_rows(rows), rows.widths):
        assert idx.dtype == np.int32 and idx.shape[1] == k
        assert idx.shape[0] % cr.ROW_TILE == 0 or k % cr.ROW_TILE == 0
        for r in range(idx.shape[0]):
            live = idx[r] != n_in
            owner = np.flatnonzero(rows.node_row == row + r)
            if not live.any():
                # a row that rounds the class up: no node reads it
                assert owner.size == 0
                continue
            # live slots first, in source order, then pads at n_in
            assert live[0] and not live[np.argmin(live):].any() or live.all()
            assert (np.diff(idx[r][live]) >= 0).all()
            assert owner.size == 1
            rebuilt += [(int(s), int(owner[0])) for s in idx[r][live]]
        row += idx.shape[0]
    # every edge exactly once, duplicates as often as they occur
    assert sorted(rebuilt) == sorted(zip(src.tolist(), dst.tolist()))
    # outputs with no edges read the appended zero row
    touched = np.zeros(n_out, bool)
    touched[dst] = True
    assert rows.node_row.shape == (n_out,) and rows.node_row.dtype == np.int32
    assert (rows.node_row[~touched] == row).all()
    # under 1.5x the edges, but for the rounding rows of each class
    rounding = cr.ROW_TILE * sum(rows.widths)
    assert sum(i.size for i in rows.idx) <= 1.5 * src.size + rounding


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_ignores_edge_order(edges, case):
    e = edges(case)
    perm = np.random.default_rng(0).permutation(e.n_edges)
    a = cr.row_layout(e.src, e.dst, e.n_src, e.n_dst)
    b = cr.row_layout(e.src[perm], e.dst[perm], e.n_src, e.n_dst)
    assert np.array_equal(a.node_row, b.node_row)
    assert a.widths == b.widths and len(a.idx) == len(b.idx)
    assert all(np.array_equal(x, y) for x, y in zip(a.idx, b.idx))


GRAPHS = {"dblp": _dblp, "tpch": _tpch}


@pytest.fixture(scope="module")
def graph_pair():
    cache = {}

    def get(name):
        if name not in cache:
            g = GRAPHS[name]()
            cache[name] = (
                g,
                engine.to_device(g, drop_self_loops=False),
                engine.to_device_packed(g, drop_self_loops=False, backend="xla"),
            )
        return cache[name]

    return get


@pytest.mark.parametrize("width", [None, 8], ids=["1d", "B8"])
@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("op", sorted(SEMIRINGS))
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_packed_rows_propagate_matches_segment_path(
    graph_pair, name, op, reverse, width
):
    """Every layer step of a packed graph off the kernel runs on its row
    layout, and gives the segment path's answer bit for bit."""
    g, coo, packed = graph_pair(name)
    sr = SEMIRINGS[op]
    rng = np.random.default_rng(width or 0)
    shape = (g.n_real,) if width is None else (g.n_real, width)
    x = rng.integers(0, 4, shape).astype(np.float32)
    engine.reset_kernel_dispatch_count()
    got = np.asarray(engine.propagate(packed, x, sr, reverse=reverse,
                                      allow_duplicates=True))
    steps = sum(len(c) for c in packed.chains)
    assert engine.LAYER_STEP_COUNT == {"rows": steps}
    want = np.asarray(engine.propagate(coo, x, sr, reverse=reverse,
                                       allow_duplicates=True))
    assert np.array_equal(got, want)


def test_packed_layers_hold_no_coo_edges():
    g = _tpch()
    packed = engine.to_device_packed(g, drop_self_loops=False, backend="xla")
    for chain, host in zip(packed.chains, g.chains):
        for layer, e in zip(chain, host.edges):
            assert not hasattr(layer, "src") and not hasattr(layer, "dst")
            assert layer.rows_fwd.node_row.shape == (e.n_dst,)
            assert layer.rows_rev.node_row.shape == (e.n_src,)
            want = _layout(e, False)
            assert layer.rows_fwd.widths == want.widths
            assert all(np.array_equal(np.asarray(a), b)
                       for a, b in zip(layer.rows_fwd.idx, want.idx))
