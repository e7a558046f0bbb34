"""Seeded golden regressions for the condensation-native analytics
(DESIGN.md §11): SCC component counts, triangle totals, and distance
histograms on the DBLP and TPC-H extraction fixtures (the paper's
running examples) plus an asymmetric layered fixture for the directed
algorithms — so refactors of the correction algebra / semiring layer
can't silently drift.  The TPC-H values are pinned; every pinned value was
cross-checked against the dense-expansion oracle (tests/oracle.py) when
recorded, and the oracle assertions stay in the tests so a drift is
reported as "disagrees with the dense expansion", not just "differs from
a magic number".  The DBLP values are derived from that oracle inside the
test, from the catalog that ``dblp_catalog`` draws.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from oracle import (
    bfs_ref,
    connected_components_ref,
    dense_adjacency,
    scc_labels_ref,
    triangle_counts_ref,
)

from repro.core import algorithms, dedup, engine
from repro.core.extract import extract
from repro.data.synth import dblp_catalog, layered_condensed, tpch_catalog

Q1_COAUTHOR = """
Nodes(ID, Name) :- Author(ID, Name).
Edges(ID1, ID2) :- AuthorPub(ID1, PubID), AuthorPub(ID2, PubID).
"""

Q2_COPURCHASE = """
Nodes(ID, Name) :- Customer(ID, Name).
Edges(ID1, ID2) :- Orders(ok1, ID1), LineItem(ok1, pk),
                   Orders(ok2, ID2), LineItem(ok2, pk).
"""

# Distance histograms count hops 0..7 over sources [0, 1, 2, 3]; the
# triangle total is sum(t)/3 as an exact integer.
SOURCES = [0, 1, 2, 3]
GOLDEN = {
    "tpch": dict(
        n_real=200,
        n_components=4,
        largest_component=197,
        triangle_total=809_775,
        distance_histogram=[4, 527, 257, 0, 0, 0, 0, 0],
        n_unreachable=12,
    ),
}


def _oracle_goldens(g):
    """The DBLP goldens, from the dense expansion of the fixture."""
    A = dense_adjacency(g)
    sizes = np.unique(connected_components_ref(A), return_counts=True)[1]
    dist = bfs_ref(A, SOURCES)
    finite = dist[np.isfinite(dist)].astype(np.int64)
    return dict(
        n_real=A.shape[0],
        n_components=int(sizes.size),
        largest_component=int(sizes.max()),
        triangle_total=int(triangle_counts_ref(A).sum() / 3),
        distance_histogram=np.bincount(finite, minlength=8)[:8].tolist(),
        n_unreachable=int(np.isinf(dist).sum()),
    )


def _fixture(name):
    if name == "dblp":
        cat = dblp_catalog(
            n_authors=400, n_pubs=700, mean_authors_per_pub=6.0, seed=1
        )
        return extract(cat, Q1_COAUTHOR, mode="condensed").graph
    cat = tpch_catalog(n_customers=200, n_orders=800, n_parts=60, seed=2)
    return extract(cat, Q2_COPURCHASE, mode="condensed").graph


@pytest.fixture(scope="module", params=["dblp", "tpch"])
def fixture_graph(request):
    g = _fixture(request.param)
    corr = dedup.build_correction(g)
    want = GOLDEN.get(request.param) or _oracle_goldens(g)
    return want, g, engine.to_device(g, correction=corr)


def test_scc_component_goldens(fixture_graph):
    want, g, dev = fixture_graph
    assert g.n_real == want["n_real"]
    labels = algorithms.scc_labels(dev, batch=32)
    cond = algorithms.condensation(dev, labels=labels)
    assert cond.n_components == want["n_components"]
    assert int(cond.sizes.max()) == want["largest_component"]
    assert int(cond.sizes.sum()) == want["n_real"]
    # both fixtures are co-occurrence (symmetric) graphs: every SCC is a
    # weak component and the condensation DAG has no edges
    assert cond.dag_src.size == 0 and int(cond.layers.max()) == 0
    assert np.array_equal(
        labels,
        np.asarray(algorithms.connected_components(dev)).astype(labels.dtype),
    )


def test_triangle_total_goldens(fixture_graph):
    want, g, dev = fixture_graph
    t = algorithms.triangle_counts(dev, block=128, mode="wedge")
    total = t.sum() / 3.0
    assert float(total).is_integer()
    assert int(total) == want["triangle_total"]
    # byte-identical across correction modes
    assert np.array_equal(t, algorithms.triangle_counts(dev, block=128))
    wedge = dedup.build_wedge_correction(g)
    assert np.array_equal(
        t, algorithms.triangle_counts(dev, block=128, wedge=wedge)
    )


def test_distance_histogram_goldens(fixture_graph):
    want, g, dev = fixture_graph
    dist = np.asarray(
        algorithms.shortest_paths_multi(dev, jnp.asarray(SOURCES))
    )
    finite = dist[np.isfinite(dist)].astype(np.int64)
    hist = np.bincount(finite, minlength=8)[:8]
    assert hist.tolist() == want["distance_histogram"]
    assert int(np.isinf(dist).sum()) == want["n_unreachable"]


# ---------------------------------------------------------------------------
# Directed goldens: an asymmetric layered fixture with a real condensation
# DAG, plus the `connected_components(undirected=...)` regression.
# ---------------------------------------------------------------------------

def _asymmetric_fixture():
    # seed chosen so the graph is weakly but NOT strongly connected:
    # forward-only labeling genuinely diverges from symmetrized labeling
    return layered_condensed(20, [6], [8, 8], seed=1, symmetric=False)


def test_directed_scc_and_layering_goldens():
    g = _asymmetric_fixture()
    A = dense_adjacency(g)
    assert not np.array_equal(A, A.T), "fixture must be asymmetric"
    dev = engine.to_device(g, correction=dedup.build_correction(g))
    labels = algorithms.scc_labels(dev, batch=8)
    assert np.array_equal(labels, scc_labels_ref(A))
    cond = algorithms.condensation(dev, labels=labels)
    assert cond.n_components == 19
    assert int(cond.sizes.max()) == 2
    assert int(cond.layers.max()) == 5
    assert cond.dag_src.size == 41
    # layering invariant: every DAG edge points strictly downward
    assert (cond.layers[cond.dag_src] > cond.layers[cond.dag_dst]).all()


def test_connected_components_undirected_regression():
    """`connected_components` used to propagate labels forward only —
    on an asymmetric fixture that splits one weak component into many
    labels.  `undirected=True` (default) must symmetrize via the packed
    reverse operands and agree with the dense oracle."""
    g = _asymmetric_fixture()
    A = dense_adjacency(g)
    dev = engine.to_device(g)
    cc_u = np.asarray(algorithms.connected_components(dev, undirected=True))
    cc_d = np.asarray(algorithms.connected_components(dev, undirected=False))
    assert np.array_equal(
        cc_u.astype(np.float64), connected_components_ref(A, undirected=True)
    )
    # the fixture is weakly connected: one component, labeled by node 0
    assert np.unique(cc_u).size == 1 and cc_u[0] == 0
    # the old directed semantics fracture it — the regression this pins
    assert np.unique(cc_d).size == 5
    assert not np.array_equal(cc_u, cc_d)
    # default flag value is the fix
    assert np.array_equal(np.asarray(algorithms.connected_components(dev)), cc_u)
    # packed representation takes the same reverse path
    packed = engine.to_device_packed(
        g, correction=dedup.build_correction(g), backend="pallas"
    )
    assert np.array_equal(
        np.asarray(algorithms.connected_components(packed, undirected=True)),
        cc_u,
    )


def test_triangle_goldens_stable_across_backends():
    """The DBLP triangle vector is byte-identical on the packed Pallas
    path (fused and unfused DEDUP-C epilogue) — kernel backends cannot
    perturb the correction algebra."""
    g = _fixture("dblp")
    corr = dedup.build_correction(g)
    t_ref = algorithms.triangle_counts(
        engine.to_device(g, correction=corr), block=128
    )
    for fuse in (True, False):
        packed = engine.to_device_packed(
            g, correction=corr, backend="pallas", fuse_correction=fuse
        )
        t = algorithms.triangle_counts(packed, block=128, mode="wedge")
        assert np.array_equal(t, t_ref), f"fuse_correction={fuse}"
    assert int(t_ref.sum() / 3) == _oracle_goldens(g)["triangle_total"]
