"""Continuous-batching multi-tenant serving tier (DESIGN.md §10).

The tier's four contracts, each tested where it can actually break:

* residency — LRU eviction under the byte budget is loss-free: an
  evicted tenant's next query re-uploads from the retained host arrays
  and answers byte-identically; an unsatisfiable budget raises instead
  of thrashing.
* staleness — a request stamped with a superseded ``graph_version``
  bounces at submit, per tenant (the same stamp is fine on a tenant
  still at that version).
* caches — results are keyed on ``(tenant, kind, node, version)`` and a
  ``LiveGraph.apply_delta`` invalidates exactly the bumped tenant's
  entries; executables are keyed on ``(kind, width, shape signature)``
  and shape-sharing tenants reuse one trace.
* handoff — a version bump quiesces new admissions, drains in-flight
  queries against the old graph, then swaps (the regression for the old
  ``update_graph`` fully-drained-queue requirement).
"""
import numpy as np
import pytest

from conftest import copurchase_graph, random_membership_graph
from oracle import dense_adjacency, personalized_pagerank_ref

from repro.core import dedup, engine
from repro.core.delta import LiveGraph
from repro.core.dedup import graph_from_membership
from repro.core.engine import ResidencyBudget, ResidencyError
from repro.data.synth import dblp_catalog
from repro.launch.cells import place_serving_replicas
from repro.serve import tier as serve_tier
from repro.serve import (
    GraphQuery,
    GraphQueryServer,
    GraphServingTier,
    ServeRequest,
    ServerStats,
)

Q_DBLP = (
    "Nodes(ID, Name) :- Author(ID, Name).\n"
    "Edges(ID1, ID2) :- AuthorPub(ID1, PubID), AuthorPub(ID2, PubID)."
)


def _two_tenant_tier(budget=None, **kw):
    rng = np.random.default_rng(0)
    tier = GraphServingTier(max_batch=8, budget=budget, **kw)
    tier.add_tenant("A", random_membership_graph(30, 10, 4, rng))
    tier.add_tenant("B", random_membership_graph(26, 9, 4, rng))
    return tier


def _reqs(tenant, kind, nodes, qid0=0):
    return [ServeRequest(qid0 + i, tenant, kind, n) for i, n in enumerate(nodes)]


# ---------------------------------------------------------------------------
# Residency: LRU eviction is loss-free
# ---------------------------------------------------------------------------

def test_lru_evict_then_resubmit_byte_identical():
    ref = _two_tenant_tier()
    want_a = ref.serve(_reqs("A", "bfs", range(4)))
    want_b = ref.serve(_reqs("B", "ppr", range(4), qid0=100))
    per_tenant = {n: t.resident_bytes for n, t in ref.tenants.items()}

    # budget fits one tenant at a time: every switch is an eviction
    budget = ResidencyBudget(max_device_bytes=int(max(per_tenant.values()) * 1.2))
    assert budget.max_device_bytes < sum(per_tenant.values())
    tier = _two_tenant_tier(budget=budget, result_cache=False)
    got_a1 = tier.serve(_reqs("A", "bfs", range(4)))
    got_b = tier.serve(_reqs("B", "ppr", range(4), qid0=100))   # evicts A
    got_a2 = tier.serve(_reqs("A", "bfs", range(4), qid0=200))  # evicts B
    assert budget.n_evictions >= 2
    assert tier.tenants["A"].n_uploads == 2   # evicted and re-uploaded
    for q in want_a:
        assert got_a1[q].tobytes() == want_a[q].tobytes()
        assert got_a2[q + 200].tobytes() == want_a[q].tobytes()
    for q in want_b:
        assert got_b[q].tobytes() == want_b[q].tobytes()


def test_unsatisfiable_budget_raises_instead_of_thrashing():
    tier = _two_tenant_tier(budget=ResidencyBudget(max_device_bytes=64))
    with pytest.raises(ResidencyError, match="budget"):
        tier.serve(_reqs("A", "bfs", [0]))


def test_explicit_evict_frees_budget_and_reload_matches():
    tier = _two_tenant_tier()
    first = tier.serve(_reqs("A", "common_neighbors", range(3)))
    resident = tier.budget.resident_bytes
    tier.evict_tenant("A")
    assert tier.budget.resident_bytes < resident
    assert tier.tenants["A"].device is None
    tier.result_cache_enabled = False   # force recompute on reload
    again = tier.serve(_reqs("A", "common_neighbors", range(3), qid0=50))
    for q in first:
        assert first[q].tobytes() == again[q + 50].tobytes()


# ---------------------------------------------------------------------------
# Staleness: per-tenant version stamps
# ---------------------------------------------------------------------------

def test_stale_version_rejects_across_tenants():
    rng = np.random.default_rng(1)
    tier = _two_tenant_tier()
    fresh = random_membership_graph(30, 10, 4, rng)
    tier.update_tenant("A", fresh, version=3)
    with pytest.raises(ValueError, match="stale"):
        tier.submit(ServeRequest(1, "A", "bfs", 0, graph_version=0))
    # the same stamp is valid on tenant B, which is still at version 0
    assert tier.submit(ServeRequest(2, "B", "bfs", 0, graph_version=0)) is None
    assert tier.submit(ServeRequest(3, "A", "bfs", 0, graph_version=3)) is None
    out = {r.qid for r in tier.drain()}
    assert out == {2, 3}
    with pytest.raises(ValueError, match="increase"):
        tier.update_tenant("A", fresh, version=3)


def test_submit_validation():
    tier = _two_tenant_tier()
    with pytest.raises(ValueError, match="unknown tenant"):
        tier.submit(ServeRequest(1, "nope", "bfs", 0))
    with pytest.raises(ValueError, match="unknown query kind"):
        tier.submit(ServeRequest(1, "A", "pagerank_all", 0))
    with pytest.raises(ValueError, match="out of range"):
        tier.submit(ServeRequest(1, "A", "bfs", 10_000))
    tier.submit(ServeRequest(1, "A", "bfs", 0))
    with pytest.raises(ValueError, match="already pending"):
        tier.submit(ServeRequest(1, "A", "ppr", 1))


# ---------------------------------------------------------------------------
# Result cache: keyed on version, invalidated per tenant
# ---------------------------------------------------------------------------

def _live_tier():
    tier = GraphServingTier(max_batch=8)
    for name, seed in (("A", 0), ("B", 1)):
        cat = dblp_catalog(
            n_authors=40, n_pubs=80, mean_authors_per_pub=3.0, seed=seed
        )
        tier.add_tenant(name, LiveGraph(cat, Q_DBLP, mode="condensed"))
    return tier


def test_result_cache_hit_after_unrelated_tenant_delta():
    tier = _live_tier()
    tier.serve(_reqs("A", "bfs", [0, 1]))
    tier.serve(_reqs("B", "bfs", [0, 1], qid0=10))
    assert tier.result_stats.hits == 0

    # unrelated tenant's write: B bumps, A's cache must survive
    live_b = tier.tenants["B"].live
    live_b.apply_delta(inserts={"AuthorPub": {
        "aid": np.array([0], dtype=np.int64),
        "pid": np.array([999_999], dtype=np.int64),
    }})
    assert tier.tenants["B"].version == int(live_b.version)
    assert tier.result_stats.invalidated > 0

    res = tier.submit(ServeRequest(20, "A", "bfs", 0))
    assert res is not None and res.cached          # A: still a hit
    assert tier.submit(ServeRequest(21, "B", "bfs", 0)) is None   # B: miss
    tier.drain()
    assert tier.result_stats.hits == 1
    # stamps against B's superseded version bounce
    with pytest.raises(ValueError, match="stale"):
        tier.submit(ServeRequest(22, "B", "bfs", 0, graph_version=0))


def test_delta_drains_inflight_against_old_graph():
    tier = _live_tier()
    tier.submit(ServeRequest(1, "B", "bfs", 0))
    old_version = tier.tenants["B"].version
    baseline = GraphServingTier(max_batch=8)
    baseline.add_tenant("B", tier.tenants["B"].host)
    want = baseline.serve(_reqs("B", "bfs", [0], qid0=1))

    tier.tenants["B"].live.apply_delta(inserts={"AuthorPub": {
        "aid": np.array([1], dtype=np.int64),
        "pid": np.array([999_998], dtype=np.int64),
    }})
    handoff = tier.take_handoff()
    assert [r.qid for r in handoff] == [1]
    assert handoff[0].graph_version == old_version
    assert handoff[0].value.tobytes() == want[1].tobytes()
    assert tier.n_pending == 0
    assert not tier.tenants["B"].quiescing


# ---------------------------------------------------------------------------
# Executable cache: shared across shape-sharing graphs, no re-traces
# ---------------------------------------------------------------------------

def test_executable_cache_reuse_across_shape_sharing_graphs():
    # disjoint same-size membership sets over the same node count: the
    # two graphs differ in content but share every array shape, so their
    # shape signatures — and compiled executables — coincide
    ga = graph_from_membership(12, [{0, 1, 2}, {3, 4, 5}, {6, 7, 8}])
    gb = graph_from_membership(12, [{0, 1, 3}, {2, 4, 6}, {5, 7, 8}])
    assert (
        engine.graph_shape_signature(engine.to_device(ga))
        == engine.graph_shape_signature(engine.to_device(gb))
    )
    tier = GraphServingTier(max_batch=4, result_cache=False)
    tier.add_tenant("A", ga, with_counts=False)
    tier.add_tenant("B", gb, with_counts=False)
    out_a = tier.serve(_reqs("A", "bfs", range(4)))
    out_b = tier.serve(_reqs("B", "bfs", range(4), qid0=10))
    assert tier.exec_stats.misses == 1 and tier.exec_stats.hits == 1
    for entry in tier._executables.values():
        assert entry.traces[0] == 1, "shape-sharing tenant re-traced"
    # shared executable, different answers: content still matters
    assert out_a[0].shape == out_b[10].shape
    assert any(out_a[i].tobytes() != out_b[10 + i].tobytes() for i in range(4))


def test_executable_cache_warm_eviction():
    tier = _two_tenant_tier(max_executables=2, result_cache=False)
    tier.serve(_reqs("A", "bfs", range(2)))
    tier.serve(_reqs("A", "ppr", range(2), qid0=10))
    tier.serve(_reqs("A", "common_neighbors", range(2), qid0=20))
    assert tier.exec_stats.evictions == 1
    assert len(tier._executables) == 2


def test_bucket_version_churn_does_not_retrace():
    """Version bumps must not invalidate executables: dispatch strips the
    version (staleness lives in the result cache), so the same (kind,
    width, signature) serves every version with one trace."""
    rng = np.random.default_rng(2)
    g = random_membership_graph(20, 8, 4, rng)
    tier = GraphServingTier(max_batch=4, result_cache=False)
    tier.add_tenant("A", g, with_counts=False)
    tier.serve(_reqs("A", "bfs", range(4)))
    tier.update_tenant("A", g, version=1)
    tier.serve(_reqs("A", "bfs", range(4), qid0=10))
    assert tier.exec_stats.misses == 1
    for entry in tier._executables.values():
        assert entry.traces[0] == 1


# ---------------------------------------------------------------------------
# Quiesce handoff (GraphQueryServer regression + tier)
# ---------------------------------------------------------------------------

def test_server_quiesce_blocks_submits_until_swap_done():
    rng = np.random.default_rng(3)
    g = random_membership_graph(20, 8, 4, rng)
    server = GraphQueryServer(engine.to_device(g))
    server.begin_quiesce()
    with pytest.raises(ValueError, match="quiescing"):
        server.submit(GraphQuery(1, "bfs", 0))
    with pytest.raises(ValueError, match="quiescing"):
        server.run([GraphQuery(2, "bfs", 0)])
    server.end_quiesce()
    server.submit(GraphQuery(3, "bfs", 0))
    assert set(server.flush()) == {3}


def test_tier_quiescing_tenant_rejects_submit():
    tier = _two_tenant_tier()
    tier.tenants["A"].quiescing = True
    with pytest.raises(ValueError, match="quiescing"):
        tier.submit(ServeRequest(1, "A", "bfs", 0))
    # other tenants keep admitting
    assert tier.submit(ServeRequest(2, "B", "bfs", 0)) is None
    tier.tenants["A"].quiescing = False
    tier.drain()


# ---------------------------------------------------------------------------
# ServerStats: occupancy and padding waste
# ---------------------------------------------------------------------------

def test_server_stats_occupancy_math():
    s = ServerStats()
    assert s.occupancy == 1.0 and s.padding_waste == 0.0   # idle: no waste
    s.record_batch(6, 8)
    s.record_batch(8, 8)
    assert s.occupancy == pytest.approx(14 / 16)
    assert s.padding_waste == pytest.approx(2 / 16)
    assert s.batch_widths_used == {8: 2}
    other = ServerStats()
    other.record_batch(2, 4)
    s.merge(other)
    assert s.occupancy == pytest.approx(16 / 20)
    assert s.batch_widths_used == {8: 2, 4: 1}


def test_tier_stats_track_occupancy():
    tier = _two_tenant_tier(result_cache=False)
    tier.serve(_reqs("A", "bfs", range(6)))   # 6 real in an 8-wide bucket
    assert tier.stats.n_batches == 1
    assert tier.stats.occupancy == pytest.approx(6 / 8)
    assert tier.stats.batch_widths_used == {8: 1}


# ---------------------------------------------------------------------------
# Replica placement
# ---------------------------------------------------------------------------

def test_place_serving_replicas_balanced_and_disjoint():
    placements = place_serving_replicas(
        ["A", "B", "C"], n_devices=8, group_size=2, replicas=2
    )
    assert len(placements) == 6
    for p in placements:
        assert len(p.devices) == 2
        assert max(p.devices) < 8
    # a tenant's replicas never share a device group
    for t in "ABC":
        groups = [p.devices for p in placements if p.tenant == t]
        assert len(set(groups)) == len(groups) == 2
    # load balanced to within one replica per group
    load = {}
    for p in placements:
        load[p.devices] = load.get(p.devices, 0) + 1
    assert max(load.values()) - min(load.values()) <= 1


def test_place_serving_replicas_errors():
    with pytest.raises(ValueError, match="group"):
        place_serving_replicas(["A"], n_devices=2, group_size=4)
    with pytest.raises(ValueError, match="distinct"):
        place_serving_replicas(["A"], n_devices=2, group_size=1, replicas=3)


# ---------------------------------------------------------------------------
# End-to-end correctness: the tier is a scheduler, not a new algorithm
# ---------------------------------------------------------------------------

def test_tier_answers_match_direct_algorithms():
    import jax.numpy as jnp

    from repro.core import algorithms

    rng = np.random.default_rng(4)
    g = random_membership_graph(24, 8, 4, rng)
    corr = dedup.build_correction(g)
    dev = engine.to_device(g, correction=corr)
    tier = GraphServingTier(max_batch=4)
    tier.add_tenant("A", g, correction=corr)
    nodes = [0, 3, 7, 11]
    got = tier.serve(_reqs("A", "bfs", nodes))
    want = np.asarray(
        algorithms.bfs_multi(dev, jnp.asarray(nodes, dtype=jnp.int32))
    )
    for i, q in enumerate(nodes):
        assert np.array_equal(got[i], want[:, i]), q


def test_copurchase_ppr_batch_matches_the_dense_oracle():
    """A TPC-H-shaped chain of three virtual layers (orders, parts,
    orders), served packed: each column of one PPR batch is the dense
    oracle's personalized PageRank, customers that never order included."""
    g = copurchase_graph(45, 150, 30, np.random.default_rng(21))
    assert [len(c.layer_sizes) for c in g.chains] == [3]
    tier = GraphServingTier(max_batch=8, result_cache=False)
    tier.add_tenant("T", g, packed=True, with_counts=False)
    nodes = [0, 1, 2, 3, 5, 8, 40, 44]
    got = tier.serve(_reqs("T", "ppr", nodes))
    want = personalized_pagerank_ref(dense_adjacency(g), nodes,
                                     tier.damping, tier.ppr_iters)
    for i in range(len(nodes)):
        np.testing.assert_allclose(got[i], want[:, i], atol=1e-5)


# ---------------------------------------------------------------------------
# Condensation-native analytics kinds (DESIGN.md §11)
# ---------------------------------------------------------------------------

def test_tier_serves_analytics_kinds_against_oracle():
    """scc / triangles / shortest / widest answers equal the dense
    oracle — through the full admission/batching/cache path."""
    import jax.numpy as jnp

    from oracle import (
        bfs_ref,
        dense_adjacency,
        scc_labels_ref,
        triangle_counts_ref,
    )
    from repro.core import algorithms

    rng = np.random.default_rng(6)
    g = random_membership_graph(24, 8, 4, rng)
    A = dense_adjacency(g)
    tier = GraphServingTier(max_batch=4)
    tier.add_tenant("A", g)
    nodes = [0, 3, 7]

    got = tier.serve(_reqs("A", "shortest", nodes))
    d_ref = bfs_ref(A, np.asarray(nodes))
    for i in range(len(nodes)):
        assert np.array_equal(got[i], d_ref[:, i]), i

    got = tier.serve(_reqs("A", "widest", nodes))
    for i in range(len(nodes)):
        assert np.array_equal(got[i] > 0, np.isfinite(d_ref[:, i])), i
        assert np.isposinf(got[i][nodes[i]])

    lab_ref = scc_labels_ref(A)
    got = tier.serve(_reqs("A", "scc", nodes))
    for i, q in enumerate(nodes):
        assert np.array_equal(got[i], (lab_ref == lab_ref[q]).astype(np.float32)), q

    t_ref = triangle_counts_ref(A).astype(np.float32)
    got = tier.serve(_reqs("A", "triangles", nodes))
    for i in range(len(nodes)):
        assert np.array_equal(got[i], t_ref), i

    # host-driven kinds hit the result cache on resubmit
    hits0 = tier.result_stats.hits
    res = tier.submit(ServeRequest(990, "A", "scc", nodes[0]))
    assert res is not None and res.cached
    assert tier.result_stats.hits == hits0 + 1


def test_tier_weighted_kinds_use_tenant_weights_not_shared_closure():
    """Two shape-identical tenants with different layer weights must get
    different `shortest` answers from the SAME cached executable — the
    regression for weights leaking into the shared closure."""
    import jax.numpy as jnp

    from repro.core import algorithms

    rng = np.random.default_rng(2)
    g = random_membership_graph(20, 7, 4, rng)
    sizes = [tuple(ch.layer_sizes) for ch in g.chains]
    w_a = tuple(
        tuple(np.full(s, 1.0, np.float32) for s in ls) for ls in sizes
    )
    w_b = tuple(
        tuple(np.full(s, 3.0, np.float32) for s in ls) for ls in sizes
    )
    tier = GraphServingTier(max_batch=4)
    tier.add_tenant("A", g, layer_weights=w_a)
    tier.add_tenant("B", g, layer_weights=w_b)
    got_a = tier.serve(_reqs("A", "shortest", [0, 5]))
    got_b = tier.serve(_reqs("B", "shortest", [0, 5], qid0=10))
    # one executable serves both (same kind/width/shape signature)
    assert tier.exec_stats.misses == 1
    dev = engine.to_device(g, correction=dedup.build_correction(g))
    for i, (qa, qb) in enumerate(((0, 10), (1, 11))):
        node = [0, 5][i]
        da = np.asarray(algorithms.shortest_paths(dev, node, layer_weights=w_a))
        db = np.asarray(algorithms.shortest_paths(dev, node, layer_weights=w_b))
        assert np.array_equal(got_a[qa], da), node
        assert np.array_equal(got_b[qb], db), node
    # the weights genuinely differ (2-virtual-hop paths cost 2 vs 6)
    finite = np.isfinite(got_a[0]) & (got_a[0] > 0)
    assert (got_b[10][finite] > got_a[0][finite]).all()


def test_tier_rejects_mismatched_weight_structure_at_admission():
    """Weight pytrees that don't match the host chain structure must fail
    at add_tenant (with the tenant's name) — not inside a jitted serve
    step.  Both arity mismatches: wrong chain count (a direct-only graph
    given per-chain weights) and wrong per-chain layer count."""
    rng = np.random.default_rng(3)
    g = random_membership_graph(16, 5, 4, rng)
    n_virt = len(g.chains[0].edges) - 1
    tier = GraphServingTier(max_batch=4)
    with pytest.raises(ValueError, match="tenant 'w'.*chains"):
        tier.add_tenant("w", g, layer_weights=[[1.0] * n_virt] * 3)
    with pytest.raises(ValueError, match="tenant 'c'.*virtual"):
        tier.add_tenant(
            "c", g, layer_capacities=[[1.0] * (n_virt + 1)] * len(g.chains)
        )
    # well-formed weights still admit and serve
    ok = [[1.0] * n_virt for _ in g.chains]
    tier.add_tenant("ok", g, layer_weights=ok, layer_capacities=ok)
    res = tier.serve(_reqs("ok", "shortest", [0]))
    assert np.asarray(res[0]).shape == (16,)


# ---------------------------------------------------------------------------
# What the tier records while repro.obs is on
# ---------------------------------------------------------------------------

@pytest.fixture
def recording():
    from repro import obs

    obs.reset()
    obs.enable()
    yield obs
    obs.disable()
    obs.reset()


@pytest.mark.parametrize("kind", sorted(serve_tier.KINDS))
def test_executables_are_named_per_kind(kind):
    tier = GraphServingTier()
    assert tier._build_executable(kind).fn.__name__ == f"serve_{kind}"


def test_executable_module_carries_the_kind_name():
    tier = _two_tenant_tier()
    tier.serve(_reqs("A", "ppr", range(3)))
    (key, entry), = tier._executables.items()
    graph = engine.with_graph_version(tier.tenants["A"].device, 0)
    text = entry.fn.lower(graph, np.zeros(key[1], np.int32)).as_text()
    assert "@jit_serve_ppr" in text


def test_kernel_layer_calls_count_every_call(monkeypatch, recording):
    """The dispatch counters of ``engine`` move only while a program is
    traced; the tier's per-call counters move with every batch."""
    import functools

    monkeypatch.setattr(
        engine, "to_device_packed",
        functools.partial(engine.to_device_packed, backend="pallas"),
    )
    rng = np.random.default_rng(5)
    tier = GraphServingTier(max_batch=4, result_cache=False)
    tier.add_tenant("A", random_membership_graph(16, 6, 3, rng), packed=True,
                    with_counts=False)
    engine.reset_kernel_dispatch_count()
    tier.serve(_reqs("A", "ppr", range(4)))
    (entry,) = tier._executables.values()
    traced = engine.KERNEL_DISPATCH_COUNT
    assert entry.kernel_layers == traced > 0
    for calls in (1, 2, 3):
        if calls > 1:
            tier.serve(_reqs("A", "ppr", range(4), qid0=10 * calls))
        counts = recording.snapshot()["counts"]
        assert counts["tier.kernel_layer_calls"] == calls * traced
        for reason, n in entry.standdowns.items():
            assert counts[f"tier.standdown.{reason}"] == calls * n
    assert engine.KERNEL_DISPATCH_COUNT == traced   # trace time only
    assert entry.traces[0] == 1


def _packed_ppr_tier(n_real):
    # a size of its own per test: a program traced before (jitted helpers
    # are cached by shape) records no trace-time decisions again
    rng = np.random.default_rng(11)
    tier = GraphServingTier(max_batch=4, result_cache=False)
    tier.add_tenant("A", random_membership_graph(n_real, 12, 5, rng),
                    packed=True, with_counts=False)
    return tier


def test_serve_ppr_correction_has_no_sort_or_scatter():
    """The compiled PPR program applies the DEDUP-C correction from its row
    layout: no instruction under ``engine.correction`` sorts or scatters."""
    import re

    tier = _packed_ppr_tier(41)
    tier.serve(_reqs("A", "ppr", range(4)))
    (key, entry), = tier._executables.items()
    assert entry.epilogues == ("rows",)
    graph = engine.with_graph_version(tier.tenants["A"].device, 0)
    text = entry.fn.lower(graph, np.zeros(key[1], np.int32)).compile().as_text()
    ops = re.findall(r"= \S+ (\w[\w-]*)\(.*op_name=\"([^\"]*)\"", text)
    correction = [op for op, name in ops if "engine.correction" in name]
    assert correction, "no instruction carries the engine.correction scope"
    assert not [op for op in correction if op in ("sort", "scatter")], correction


def test_correction_rows_count_once_per_ppr_call(recording):
    tier = _packed_ppr_tier(43)
    for calls in (1, 2, 3):
        tier.serve(_reqs("A", "ppr", range(4), qid0=10 * calls))
        counts = recording.snapshot()["counts"]
        assert counts["tier.correction.rows"] == calls
        assert "tier.correction.fused" not in counts
    (entry,) = tier._executables.values()
    assert entry.traces[0] == 1


def test_interior_steps_count_every_call(recording):
    """A chain of three virtual layers has two interior steps a hop; the
    traced program records them once and every call counts them."""
    tier = GraphServingTier(max_batch=4, result_cache=False)
    tier.add_tenant("T", copurchase_graph(47, 160, 31, np.random.default_rng(2)),
                    packed=True, with_counts=False)
    for calls in (1, 2, 3):
        tier.serve(_reqs("T", "ppr", range(4), qid0=10 * calls))
        (entry,) = tier._executables.values()
        assert recording.snapshot()["counts"]["tier.interior_steps"] == (
            calls * entry.interior_steps)
    # the loop body's two steps and the out-degrees' two: a graph of its
    # own size, so the jitted out-degrees is traced inside this program
    assert entry.interior_steps == 4
    assert entry.traces[0] == 1
    counted = recording.snapshot()["counts"]["tier.interior_steps"]
    # one virtual layer: no interior step, nothing more counted
    single = _packed_ppr_tier(45)
    single.serve(_reqs("A", "ppr", range(4)))
    (entry,) = single._executables.values()
    assert entry.interior_steps == 0
    assert recording.snapshot()["counts"]["tier.interior_steps"] == counted


def test_queue_wait_samples_match_requests_served(recording):
    tier = _two_tenant_tier()
    first = tier.serve(_reqs("A", "bfs", range(6)))
    waits = recording.snapshot()["samples"]["tier.queue_wait_s"]
    assert len(waits) == len(first) == 6
    assert all(0.0 <= w < 60.0 for w in waits)
    # answered from the result cache: no queue, a wait of 0
    hits = [tier.submit(r) for r in _reqs("A", "bfs", range(2), qid0=20)]
    assert all(h is not None and h.cached for h in hits)
    waits = recording.snapshot()["samples"]["tier.queue_wait_s"]
    assert len(waits) == 8 and waits[-2:] == [0.0, 0.0]
    assert tier._admitted == {}


def test_step_spans_and_batch_fill_agree_with_server_stats(recording):
    tier = _two_tenant_tier(result_cache=False)
    results = []
    for kind, n in (("bfs", 6), ("ppr", 3), ("bfs", 8), ("common_neighbors", 5)):
        for r in _reqs("A", kind, range(n), qid0=len(results) + 100):
            tier.submit(r)
        results += tier.drain()
    spans = recording.snapshot()["spans"]
    n_batches = tier.stats.n_batches
    assert n_batches == 4
    for name in ("tier.step", "tier.dispatch", "tier.fetch", "tier.record"):
        assert spans[name]["count"] == n_batches, name
    children = sum(spans[k]["seconds"] for k in ("tier.resident", "tier.dispatch",
                                                 "tier.fetch", "tier.record"))
    assert children <= spans["tier.step"]["seconds"]
    # one result per real query, each carrying its batch's fill and width
    batches = {(r.kind, r.batch_width, r.batch_fill) for r in results}
    fill = sum(f for _, _, f in batches)
    slots = sum(w for _, w, _ in batches)
    assert fill == tier.stats.queries_batched == len(results)
    assert fill / slots == pytest.approx(tier.stats.occupancy)
