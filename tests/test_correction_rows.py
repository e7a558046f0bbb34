"""The DEDUP-C correction's row layout (``repro.core.correction_rows``):
its apply against a NumPy ``D·x`` / ``Dᵀ·x`` from the raw triples, the
build's invariants, the shared builder against the correction's own loop,
and the layout sharded over four virtual devices."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import correction_rows as cr
from repro.core import dedup, extract
from repro.data.synth import dblp_catalog, tpch_catalog

REPO = os.path.join(os.path.dirname(__file__), "..")

Q1 = """
Nodes(ID, Name) :- Author(ID, Name).
Edges(ID1, ID2) :- AuthorPub(ID1, PubID), AuthorPub(ID2, PubID).
"""

Q2 = """
Nodes(ID, Name) :- Customer(ID, Name).
Edges(ID1, ID2) :- Orders(ok1, ID1), LineItem(ok1, pk),
                   Orders(ok2, ID2), LineItem(ok2, pk).
"""


def _extracted(catalog, query):
    graph = extract(catalog, query, mode="condensed").graph
    cs, cd, cm = dedup.build_correction(graph)
    return cs, cd, cm, graph.n_real


def _zipf(n=300, wide=2500, seed=4):
    """Mostly one to three triples a row, and one row far wider."""
    rng = np.random.default_rng(seed)
    dst = rng.integers(0, n, 900)
    src = rng.integers(0, n, 900)
    dst = np.concatenate([dst, np.full(wide, 7)])
    src = np.concatenate([src, rng.integers(0, n, wide)])
    key = np.unique(src * n + dst)
    return key // n, key % n, rng.integers(1, 5, key.size), n


CASES = {
    "empty": lambda: (np.zeros(0, int), np.zeros(0, int), np.zeros(0), 9),
    "isolated_nodes": lambda: (
        np.array([0, 3, 3, 8]), np.array([3, 0, 8, 3]),
        np.array([1.0, 1.0, 2.0, 2.0]), 20,
    ),
    "diagonal_only": lambda: (
        np.arange(0, 30, 3), np.arange(0, 30, 3), np.arange(1, 11), 31,
    ),
    "asymmetric": lambda: (
        np.array([0, 0, 1, 2, 5]), np.array([1, 2, 2, 4, 4]),
        np.array([1.0, 2.0, 3.0, 1.0, 1.0]), 6,
    ),
    "zipf_wide_row": _zipf,
    "dblp": lambda: _extracted(
        dblp_catalog(n_authors=400, n_pubs=700, mean_authors_per_pub=6.0,
                     seed=1), Q1,
    ),
    "tpch_multilayer": lambda: _extracted(tpch_catalog(seed=2), Q2),
}


@pytest.fixture(scope="module")
def triples():
    cache = {}

    def get(case):
        if case not in cache:
            cache[case] = CASES[case]()
        return cache[case]

    return get


def _want(src, dst, w, n, x, reverse):
    src, dst = (dst, src) if reverse else (src, dst)
    y = np.zeros(x.shape, dtype=np.float64)
    w = np.asarray(w, np.float64).reshape((-1,) + (1,) * (x.ndim - 1))
    np.add.at(y, dst, w * x[src])
    return y


@pytest.mark.parametrize("width", [None, 1, 8, 32], ids=["1d", "B1", "B8", "B32"])
@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_apply_matches_triples_exactly(triples, case, reverse, width):
    src, dst, w, n = triples(case)
    corr = cr.upload_correction(src, dst, w, n)
    rng = np.random.default_rng(n)
    shape = (n,) if width is None else (n, width)
    x = rng.integers(0, 4, shape).astype(np.float32)
    got = np.asarray(cr.apply_correction(corr, x, reverse))
    assert got.shape == x.shape and got.dtype == np.float32
    assert np.array_equal(got, _want(src, dst, w, n, x, reverse))


@pytest.mark.parametrize("case", sorted(CASES))
def test_build_invariants(triples, case):
    src, dst, w, n = triples(case)
    rows = cr.correction_rows(src, dst, w, n)
    nnz = np.asarray(src).size
    rebuilt = []
    row = 0
    for idx, wt in zip(rows.idx, rows.weight):
        assert idx.shape == wt.shape and idx.dtype == np.int32
        for r in range(idx.shape[0]):
            live = wt[r] != 0
            # live slots first, then pads at node 0 with count 0
            assert not live[np.argmin(live):].any() or live.all()
            assert (idx[r][~live] == 0).all()
            owner = np.flatnonzero(rows.node_row == row + r)
            assert owner.size == 1
            rebuilt += [(s, owner[0], c) for s, c in zip(idx[r][live], wt[r][live])]
        row += idx.shape[0]
    assert row == sum(i.shape[0] for i in rows.idx)
    # every triple exactly once
    got = sorted((int(s), int(d), float(c)) for s, d, c in rebuilt)
    want = sorted(zip(np.asarray(src).tolist(), np.asarray(dst).tolist(),
                      np.asarray(w, float).tolist()))
    assert got == want
    # nodes with no triples read the appended zero row
    touched = np.zeros(n, bool)
    touched[np.asarray(dst, int)] = True
    assert (rows.node_row[~touched] == row).all()
    assert sum(i.size for i in rows.idx) <= 1.5 * nnz


def test_class_widths_bound_each_row():
    m = np.arange(1, 5000)
    k = cr.class_width(m)
    assert (k >= m).all() and (k <= 1.5 * m).all()
    assert set(cr.class_width(np.array([1, 2, 3, 4, 5, 7, 9, 13]))) == {
        1, 2, 3, 4, 6, 8, 12, 16,
    }


def _loop_correction_rows(src, dst, weight, n):
    """The correction's layout as it was built before the layers shared
    its builder: the reference the shared builder must still meet."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    weight = np.asarray(weight, dtype=np.float32)
    order = np.argsort(dst * max(int(n), 1) + src, kind="stable")
    cols = src[order].astype(np.int32)
    w = weight[order]
    widths = np.bincount(dst, minlength=n).astype(np.int64)
    starts = np.cumsum(widths) - widths
    nodes = np.flatnonzero(widths)
    k_of = cr.class_width(widths[nodes])
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
    return idx, wts, node_row


@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_shared_builder_keeps_the_correction_layout(triples, case, reverse):
    src, dst, w, n = triples(case)
    if reverse:
        src, dst = dst, src
    rows = cr.correction_rows(src, dst, w, n)
    idx, wts, node_row = _loop_correction_rows(src, dst, w, n)
    assert np.array_equal(rows.node_row, node_row)
    assert node_row.dtype == rows.node_row.dtype == np.int32
    assert len(rows.idx) == len(idx) == len(rows.weight)
    for a, b in zip(rows.idx + rows.weight, tuple(idx) + tuple(wts)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_layout_ignores_triple_order(triples):
    src, dst, w, n = triples("zipf_wide_row")
    perm = np.random.default_rng(0).permutation(src.size)
    a = cr.correction_rows(src, dst, w, n)
    b = cr.correction_rows(src[perm], dst[perm], w[perm], n)
    assert np.array_equal(a.node_row, b.node_row)
    assert all(np.array_equal(x, y) for x, y in zip(a.idx, b.idx))
    assert all(np.array_equal(x, y) for x, y in zip(a.weight, b.weight))


@pytest.mark.parametrize("case,symmetric", [
    ("dblp", True), ("tpch_multilayer", True), ("diagonal_only", True),
    ("asymmetric", False), ("zipf_wide_row", False),
])
def test_one_layout_serves_symmetric_triples(triples, case, symmetric):
    corr = cr.upload_correction(*triples(case))
    assert (corr.rev is None) == symmetric


FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.core import algorithms, dedup, engine
from repro.data.synth import dblp_catalog
from repro.core import extract
from repro.distributed.sharding import edge_mesh, shard_graph_edges

Q1 = ("Nodes(ID, Name) :- Author(ID, Name).\n"
      "Edges(ID1, ID2) :- AuthorPub(ID1, PubID), AuthorPub(ID2, PubID).")
g = extract(dblp_catalog(n_authors=301, n_pubs=500, mean_authors_per_pub=5.0,
                         seed=3), Q1, mode="condensed").graph
cs, cd, cm = dedup.build_correction(g)
keep = (cs + 2 * cd) % 5 != 0        # an asymmetric set needs both layouts
mesh = edge_mesh((2, 2), ("data", "model"))
seeds = algorithms.one_hot_frontier(g.n_real, np.arange(0, 240, 30))
for name, corr in (("symmetric", (cs, cd, cm)),
                   ("asymmetric", (cs[keep], cd[keep], cm[keep]))):
    dev = engine.to_device(g, correction=corr)
    assert (dev.correction.rev is None) == (name == "symmetric"), name
    sharded = shard_graph_edges(dev, mesh)
    for rows in (sharded.correction.fwd, sharded.correction.rev):
        if rows is None:
            continue
        assert rows.node_row.sharding.is_fully_replicated
        for a in rows.idx + rows.weight:
            assert len({s.device.id for s in a.addressable_shards}) == 4
            assert all(s.data.shape[0] * 4 == a.shape[0]
                       for s in a.addressable_shards)
    for run in (lambda d: algorithms.pagerank(d, num_iters=20),
                lambda d: algorithms.personalized_pagerank(d, seeds)):
        want, got = np.asarray(run(dev)), np.asarray(run(sharded))
        assert np.abs(got - want).max() <= 1e-6, (name, np.abs(got - want).max())
    print("FOUR_OK", name)
"""


def test_sharded_layout_matches_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES], capture_output=True, text=True,
        timeout=600, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert proc.stdout.count("FOUR_OK") == 2
