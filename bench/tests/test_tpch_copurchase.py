"""The TPC-H co-purchase configuration at a small cut (SF 0.002): its
generator keeps the shapes of the specification's clause 4.2, its
reference agrees with the dense oracle of the repository's tests, the
seed changes the rows but not one packed shape, and ``node_of`` names
each structural customer's served node."""
import ast

import numpy as np
import pytest

import harness
import reference
from oracle import (
    bfs_ref,
    common_neighbors_ref,
    dense_adjacency,
    dense_multiplicity,
    personalized_pagerank_ref,
)

CONFIG = "tpch-copurchase"
SF = 0.002
SEEDS = (3, 2**31 + 11)


def small(seed=None, sf=SF):
    cfg, module = harness.load_config(CONFIG)
    cfg = dict(cfg, scale=sf)
    return cfg, module, (None if seed is None else module.tables(cfg, seed))


def test_the_query_is_q_tpch():
    """Verbatim, as ``benchmarks/bench_extraction.py`` assigns it."""
    tree = ast.parse((harness.ROOT / "benchmarks" / "bench_extraction.py").read_text())
    (q_tpch,) = [node.value.value for node in tree.body if isinstance(node, ast.Assign)
                 and [t.id for t in node.targets] == ["Q_TPCH"]]
    cfg, _, _ = small()
    assert cfg["query"].strip() == q_tpch.strip()


@pytest.mark.parametrize("seed", SEEDS)
def test_generator_keeps_the_clause_4_2_shapes(seed):
    cfg, module, t = small(seed)
    n_cust, n_orders, n_parts = 300, 3000, 400
    assert module.sizes(cfg) == (n_cust, n_orders, n_parts)
    assert t["Customer"]["ckey"].size == n_cust
    assert np.array_equal(np.sort(t["Orders"]["okey"]),
                          np.arange(n_orders) + module.ORDER_KEY0)
    assert t["Part"]["pkey"].size == n_parts
    # o_custkey: never a multiple of 3, and every other customer can order
    ocust = t["Orders"]["ckey"]
    assert np.all(ocust % 3 != 0)
    assert np.unique(ocust).size > 0.95 * (n_cust - n_cust // 3)
    # 1 to 7 lines per order, uniform
    lines = np.bincount(t["LineItem"]["okey"] - module.ORDER_KEY0, minlength=n_orders)
    assert lines.min() == 1 and lines.max() == 7
    assert np.all(np.bincount(lines)[1:] > n_orders / 7 * 0.8)
    assert abs(lines.mean() - 4.0) < 0.1
    # a part at most once per order; parts uniform over PART
    pairs = np.stack([t["LineItem"]["okey"], t["LineItem"]["pkey"]], axis=1)
    assert np.unique(pairs, axis=0).shape[0] == lines.sum()
    parts = t["LineItem"]["pkey"] - module.PART_KEY0
    assert parts.min() >= 0 and parts.max() < n_parts
    assert np.bincount(parts, minlength=n_parts).min() > 0


def test_full_scale_sizes():
    cfg, module, _ = small(sf=harness.load_config(CONFIG)[0]["scale"])
    assert module.sizes(cfg) == (24_000, 240_000, 32_000)


def test_reference_agrees_with_the_dense_oracle():
    cfg, module, _ = small()
    built = harness.build(cfg, module, SEEDS[1])
    assert [len(c["virtual_layers"]) for c in built.shape["chains"]] == [3]
    ref = reference.Reference(*module.incidence(built.tables))
    a = dense_adjacency(built.graph)
    m = dense_multiplicity(built.graph, drop_self_loops=False)
    assert ref.n == a.shape[0] == 300
    # customers that never order have no neighbour
    assert not a[np.arange(0, 300, 3)].any()
    nodes = np.random.default_rng(0).choice(ref.n, size=12, replace=False)
    nodes[0] = 3   # a customer that never orders
    want_bfs = bfs_ref(a, nodes)
    want_cn = common_neighbors_ref(m, nodes)
    for j, u in enumerate(nodes):
        assert np.array_equal(ref.bfs(int(u)), want_bfs[:, j])
        assert np.array_equal(ref.common_neighbors(int(u)), want_cn[:, j])
    np.testing.assert_allclose(ref.ppr(nodes, 0.85, 20),
                               personalized_pagerank_ref(a, nodes), atol=1e-12)


def test_seed_changes_rows_but_no_packed_shape():
    from repro.core import engine

    shapes, rows = [], []
    for seed in SEEDS:
        cfg, module, _ = small()
        built = harness.build(cfg, module, seed)
        exact = engine.to_device_packed(built.graph, correction=built.correction)
        counts = engine.to_device_packed(built.graph, drop_self_loops=False)
        shapes.append((engine.graph_shape_signature(exact),
                       engine.graph_shape_signature(counts), built.shape))
        rows.append(module.incidence(built.tables)[1:])
    assert shapes[0] == shapes[1]
    assert not np.array_equal(rows[0][0], rows[1][0])
    assert not np.array_equal(rows[0][1], rows[1][1])


def test_node_of_names_the_served_node_of_each_structural_node():
    degrees = []
    for seed in SEEDS:
        cfg, module, t = small(seed)
        n, node, _ = module.incidence(t)
        node_of = module.node_of(cfg, seed)
        assert np.array_equal(np.sort(node_of), np.arange(n))
        assert np.all(node_of[::3] == np.arange(0, n, 3))   # silent stay put
        degrees.append(np.bincount(node, minlength=n)[node_of])
    assert np.array_equal(degrees[0], degrees[1])
