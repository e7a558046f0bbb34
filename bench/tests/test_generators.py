"""The benchmark's generators and host reference, on small cuts of the
configuration: the reference agrees with the dense oracle of the
repository's tests, the DBLP generator keeps Table 1's rows per
publication, the seed changes the rows but not one packed shape, and the
traffic generator offers the same work on every seed."""
import numpy as np
import pytest

import gen
import harness
import reference
import traffic
from oracle import bfs_ref, common_neighbors_ref, dense_adjacency, dense_multiplicity

SMALL = {
    "dblp-q1": {"authors": 400, "pubs": 750},
}


def small(config, seed):
    cfg, module = harness.load_config(config)
    cfg.update(SMALL[config])
    return cfg, module, harness.build(cfg, module, seed)


def dense_ppr(a, nodes, damping=0.85, iters=20):
    deg = a.sum(axis=1)[:, None]
    seeds = np.zeros((a.shape[0], len(nodes)))
    seeds[nodes, np.arange(len(nodes))] = 1.0
    x = seeds
    for _ in range(iters):
        contrib = np.where(deg > 0, x / np.maximum(deg, 1.0), 0.0)
        dangling = np.where(deg > 0, 0.0, x).sum(axis=0)
        x = (1 - damping) * seeds + damping * (a.T @ contrib + dangling * seeds)
    return x


@pytest.mark.parametrize("config", sorted(SMALL))
def test_reference_agrees_with_the_dense_oracle(config):
    cfg, module, built = small(config, 2**31 + 99)
    ref = reference.Reference(*module.incidence(built.tables))
    a = dense_adjacency(built.graph)
    m = dense_multiplicity(built.graph, drop_self_loops=False)
    assert ref.n == a.shape[0]
    nodes = np.random.default_rng(0).choice(ref.n, size=12, replace=False)
    want_bfs = bfs_ref(a, nodes)
    want_cn = common_neighbors_ref(m, nodes)
    for j, u in enumerate(nodes):
        assert np.array_equal(ref.bfs(int(u)), want_bfs[:, j])
        assert np.array_equal(ref.common_neighbors(int(u)), want_cn[:, j])
    np.testing.assert_allclose(ref.ppr(nodes, 0.85, 20), dense_ppr(a, nodes), atol=1e-12)


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_dblp_generator_keeps_the_rows_per_publication(seed):
    cfg, module = harness.load_config("dblp-q1")
    t = module.tables(dict(cfg, authors=16_000, pubs=30_000), seed)
    rows = t["AuthorPub"]["pid"].size
    assert rows == round(30_000 * cfg["mean_authors_per_pub"])
    sizes = np.bincount(t["AuthorPub"]["pid"] - 1_000_000)
    assert sizes.min() >= 1 and sizes.max() > 100        # heavy-tailed
    pairs = t["AuthorPub"]["aid"].astype(np.int64) * 10**7 + t["AuthorPub"]["pid"]
    assert np.unique(pairs).size == rows                 # an author once per paper


def test_zipf_sizes_sum_to_the_mean():
    rng = np.random.default_rng(8)
    for n, mean in ((1000, 2.8666), (5000, 2.0), (300, 7.5)):
        sizes = gen.zipf_sizes(n, mean, rng)
        assert sizes.min() >= 1 and sizes.sum() == round(n * mean)


@pytest.mark.parametrize("config", sorted(SMALL))
def test_seed_changes_rows_but_no_packed_shape(config):
    from repro.core import engine

    shapes, rows = [], []
    for seed in (1, 2**32 + 1):
        cfg, module, built = small(config, seed)
        exact = engine.to_device_packed(built.graph, correction=built.correction)
        counts = engine.to_device_packed(built.graph, drop_self_loops=False)
        shapes.append((engine.graph_shape_signature(exact),
                       engine.graph_shape_signature(counts), built.shape))
        rows.append(module.incidence(built.tables)[1])
    assert shapes[0] == shapes[1]
    assert not np.array_equal(rows[0], rows[1])


def test_tile_relabel_moves_ids_only_inside_their_tile():
    rng = np.random.default_rng(4)
    fixed = np.arange(1000) % 3 == 2
    perm = gen.tile_relabel(1000, rng, fixed=fixed)
    assert np.array_equal(np.sort(perm), np.arange(1000))
    assert np.array_equal(perm // gen.TILE, np.arange(1000) // gen.TILE)
    assert np.array_equal(perm[fixed], np.arange(1000)[fixed])
    assert np.all(fixed[perm] == fixed)
    assert (perm != np.arange(1000)).mean() > 0.5


def test_zipf_ranks_skew_toward_few():
    ranks = gen.zipf_ranks(np.random.default_rng(1), 10_000, 5000, 1.2)
    assert ranks.min() == 0 and ranks.max() < 10_000
    assert np.mean(ranks < 10) > 0.3


def test_schedule_offers_the_same_work_on_every_seed():
    mix = {"rate_qps": 50.0, "kinds": {"common_neighbors": 0.6, "bfs": 0.3, "ppr": 0.1},
           "nodes": {"zipf": 1.2}, "check": {"ppr": 4, "bfs": 8, "common_neighbors": 8}}
    relabel = {7: gen.tile_relabel(1000, np.random.default_rng(1)),
               2**31 + 3: gen.tile_relabel(1000, np.random.default_rng(2))}
    a, b = (traffic.schedule(mix, relabel[s], s, 10.0) for s in relabel)
    again = traffic.schedule(mix, relabel[7], 7, 10.0)
    assert a.due.size == b.due.size == 500
    assert np.all(np.diff(a.due) >= 0) and a.due.max() < 10.0
    for s in (a, b):
        assert [s.kinds.count(k) for k in ("common_neighbors", "bfs", "ppr")] == [300, 150, 50]
        assert [sum(s.kinds[q] == k for q in s.checked) for k in ("ppr", "bfs")] == [4, 8]
    assert np.array_equal(a.due, b.due)     # the same instants
    assert a.kinds != b.kinds and not np.array_equal(a.nodes, b.nodes)
    assert np.array_equal(a.due, again.due) and np.array_equal(a.nodes, again.nodes)
    # the same requests of the structure, in another order, on every seed
    structural = [
        sorted(zip(s.kinds, np.argsort(relabel[seed])[s.nodes].tolist()))
        for s, seed in ((a, 7), (b, 2**31 + 3))
    ]
    assert structural[0] == structural[1]


def test_uniform_schedule_draws_its_nodes_from_the_seed():
    mix = {"rate_qps": 20.0, "kinds": {"ppr": 1.0}, "nodes": "uniform", "check": {"ppr": 4}}
    a, b = (traffic.schedule(mix, np.arange(5000), s, 10.0) for s in (1, 2))
    assert a.nodes.size == 200 and len(set(a.nodes)) > 190
    assert len(set(a.nodes) & set(b.nodes)) < 50


@pytest.mark.parametrize("config", sorted(SMALL))
def test_node_of_names_the_served_node_of_each_structural_node(config):
    degrees = []
    for seed in (5, 2**33 + 5):
        cfg, module = harness.load_config(config)
        cfg.update(SMALL[config])
        n, node, _ = module.incidence(module.tables(cfg, seed))
        degrees.append(np.bincount(node, minlength=n)[module.node_of(cfg, seed)])
    assert np.array_equal(degrees[0], degrees[1])
