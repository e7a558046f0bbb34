"""Drive the system's main path once on a TPU and check every answer.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # edge-sharded PageRank, 2x2 mesh

One chip: a seeded DBLP-shaped catalog (the paper's Table 1 shape, cut to
``SCALE`` of its size) goes through the Q1 co-author extraction in the
condensed mode, the DEDUP-C correction and the packed upload, and a
:class:`~repro.serve.tier.GraphServingTier` answers batches of ``bfs``,
``ppr`` and ``common_neighbors`` queries with Zipf-chosen seed nodes.
Every answer is checked against a plain host computation on the catalog
rows (NumPy, and SciPy's sparse product for the expanded co-author
graph).  A kernel phase then runs ``backend='pallas'`` propagation (the
``sum``, ``min`` and fused DEDUP-C kernels) on a graph small enough for
the kernels' slot tables, and compares it with the XLA path
(``backend='xla'``: the layers' row layouts) on the same chip.

``--four-chips`` runs only the edge-sharded PageRank of the same graph on
a 2x2 mesh and compares it with one-device PageRank in this process.

Each earlier line of standard output is one JSON object naming its phase.
The last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero and prints no result.  Everything runs in this one
process: a process that has touched JAX holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# The paper's Table 1 DBLP catalog.
TABLE1_AUTHORS = 1_600_000
TABLE1_PUBS = 3_000_000
TABLE1_ROWS = 8_600_000
# The largest cut whose device graph (exact plus counts operands) stays
# under 12 GB of the v5e's 16 GB: device bytes grow about linearly with
# the cut, about 1.4 GB per 0.01 of Table 1.
SCALE = 0.08
# The kernel phase's cut: about 19k slots per packed layer direction and
# 33k in the fused stream, inside the 1 MiB of SMEM the compiler allows.
KERNEL_SCALE = 0.01
SEED = 0
BATCH = 16
N_BATCHES = 3
ZIPF_A = 1.2
PPR_RTOL = 1e-5
FOUR_CHIP_ATOL = 1e-6

Q1_COAUTHOR = """
Nodes(ID, Name) :- Author(ID, Name).
Edges(ID1, ID2) :- AuthorPub(ID1, PubID), AuthorPub(ID2, PubID).
"""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu(n_chips: int):
    """The devices JAX found, or exit non-zero when they are not TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        sys.exit(
            f"chip_smoke: needs {n_chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)"
        )
    return devices


# ---------------------------------------------------------------------------
# Build: catalog -> condensed extraction -> DEDUP-C correction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Build:
    catalog: object
    result: object       # ExtractionResult
    correction: object   # StreamedCorrection
    seconds: dict

    @property
    def graph(self):
        return self.result.graph


def build(scale: float, seed: int = SEED) -> Build:
    from repro.core import dedup
    from repro.core.extract import extract
    from repro.data.synth import dblp_catalog

    seconds = {}
    t = time.perf_counter()
    catalog = dblp_catalog(
        n_authors=round(TABLE1_AUTHORS * scale),
        n_pubs=round(TABLE1_PUBS * scale),
        mean_authors_per_pub=TABLE1_ROWS / TABLE1_PUBS,
        seed=seed,
    )
    seconds["catalog"] = time.perf_counter() - t
    t = time.perf_counter()
    result = extract(catalog, Q1_COAUTHOR, mode="condensed")
    seconds["extract"] = time.perf_counter() - t
    t = time.perf_counter()
    correction = dedup.build_correction_streaming(result.graph)
    seconds["correction"] = time.perf_counter() - t
    return Build(catalog, result, correction, seconds)


def describe(b: Build, scale: float) -> dict:
    rows = len(b.catalog.table("AuthorPub"))
    return {
        "scale_of_table1": scale,
        "authors": len(b.catalog.table("Author")),
        "pubs": len(b.catalog.table("Pub")),
        "author_pub_rows": rows,
        "rows_of_table1": rows / TABLE1_ROWS,
        "condensed_edges": b.graph.n_edges_condensed,
        "correction_triples": int(b.correction.src.size),
        "seconds": b.seconds,
    }


# ---------------------------------------------------------------------------
# Host reference: the co-author graph straight from the AuthorPub rows
# ---------------------------------------------------------------------------

def _csr(rows: np.ndarray, cols: np.ndarray, n_rows: int):
    order = np.argsort(rows, kind="stable")
    ptr = np.zeros(n_rows + 1, dtype=np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=ptr[1:])
    return ptr, cols[order]


def _gather_rows(ptr: np.ndarray, idx: np.ndarray, rows: np.ndarray):
    starts = ptr[rows]
    lens = ptr[rows + 1] - starts
    first = np.repeat(starts - np.cumsum(lens) + lens, lens)
    return idx[first + np.arange(int(lens.sum()))]


class HostReference:
    """Authors u ~ v (u != v) when they share a publication; built from the
    catalog rows alone, with none of the code under test."""

    def __init__(self, b: Build):
        ap = b.catalog.table("AuthorPub")
        author, found = b.result.nodes.lookup(ap.column("aid"))
        assert found.all(), "an AuthorPub row names an unknown author"
        pub = np.unique(ap.column("pid"), return_inverse=True)[1]
        self.n = b.graph.n_real
        self.n_pubs = int(pub.max()) + 1
        self.a2p = _csr(author, pub, self.n)
        self.p2a = _csr(pub, author, self.n_pubs)
        self._author, self._pub = author, pub
        self._adjacency = None

    def bfs(self, source: int) -> np.ndarray:
        dist = np.full(self.n, np.inf)
        dist[source] = 0.0
        seen_pub = np.zeros(self.n_pubs, dtype=bool)
        frontier = np.array([source])
        hop = 0
        while frontier.size:
            hop += 1
            pubs = np.unique(_gather_rows(*self.a2p, frontier))
            pubs = pubs[~seen_pub[pubs]]
            seen_pub[pubs] = True
            nbrs = np.unique(_gather_rows(*self.p2a, pubs))
            frontier = nbrs[np.isinf(dist[nbrs])]
            dist[frontier] = hop
        return dist

    def common_neighbors(self, node: int) -> np.ndarray:
        """Publications each author shares with ``node`` (itself included)."""
        pubs = _gather_rows(*self.a2p, np.array([node]))
        return np.bincount(
            _gather_rows(*self.p2a, pubs), minlength=self.n
        ).astype(np.float64)

    def ppr(self, nodes, damping: float, iters: int) -> np.ndarray:
        """Float64 power iteration of personalized PageRank, one column per
        node, on the expanded simple co-author graph."""
        import scipy.sparse as sp

        if self._adjacency is None:
            inc = sp.csr_matrix(
                (np.ones(self._author.size), (self._author, self._pub)),
                shape=(self.n, self.n_pubs),
            )
            adj = (inc @ inc.T).tocsr()
            adj.data[:] = 1.0
            adj.setdiag(0.0)
            adj.eliminate_zeros()
            self._adjacency = adj
        adj = self._adjacency
        deg = np.asarray(adj.sum(axis=1)).ravel()[:, None]
        seeds = np.zeros((self.n, len(nodes)))
        seeds[np.asarray(nodes), np.arange(len(nodes))] = 1.0
        x = seeds
        for _ in range(iters):
            contrib = np.where(deg > 0, x / np.maximum(deg, 1.0), 0.0)
            dangling = np.where(deg > 0, 0.0, x).sum(axis=0)
            y = adj @ contrib + dangling * seeds
            x = (1.0 - damping) * seeds + damping * y
        return x


# ---------------------------------------------------------------------------
# Serving: GraphServingTier answers Zipf-seeded batches
# ---------------------------------------------------------------------------

def zipf_nodes(rng: np.random.Generator, n: int, size: int) -> np.ndarray:
    """``size`` nodes, node ``perm[k]`` drawn with weight ``(k + 1)^-a``."""
    perm = rng.permutation(n)
    ranks = np.empty(0, dtype=np.int64)
    while ranks.size < size:
        draw = rng.zipf(ZIPF_A, size=4 * size)
        ranks = np.concatenate([ranks, draw[draw <= n]])
    return perm[ranks[:size] - 1]


def serving_phase(b: Build, seed: int = SEED) -> dict:
    """Serve ``N_BATCHES`` batches of each kind and check every answer."""
    from repro.core import engine
    from repro.serve.tier import GraphServingTier, ServeRequest

    tier = GraphServingTier(max_batch=BATCH, bucket_widths=(BATCH,))
    tier.add_tenant("dblp", b.graph, correction=b.correction, packed=True)
    rng = np.random.default_rng(seed)
    ref = HostReference(b)
    engine.reset_kernel_dispatch_count()
    qid = 0
    report = {}
    for kind in ("bfs", "ppr", "common_neighbors"):
        batch_seconds, checked, cached = [], 0, 0
        for _ in range(N_BATCHES):
            nodes = zipf_nodes(rng, ref.n, BATCH)
            t = time.perf_counter()
            answers = {}
            for node in nodes:
                res = tier.submit(ServeRequest(qid, "dblp", kind, int(node)))
                if res is not None:
                    answers[qid] = res.value
                    cached += 1
                qid += 1
            for res in tier.drain():
                answers[res.qid] = res.value
            batch_seconds.append(time.perf_counter() - t)
            values = [answers[q] for q in range(qid - BATCH, qid)]
            checked += check_answers(kind, nodes, values, ref, tier)
        report[kind] = {
            "first_batch_seconds": batch_seconds[0],
            "steady_batch_seconds": batch_seconds[1:],
            "answers_checked": checked,
            "result_cache_hits": cached,
        }
    report["device_graph_bytes"] = tier.budget.resident_bytes
    report["kernel_dispatch_count"] = engine.KERNEL_DISPATCH_COUNT
    report["kernel_standdown_count"] = dict(engine.KERNEL_STANDDOWN_COUNT)
    return report


def check_answers(kind, nodes, values, ref: HostReference, tier) -> int:
    if kind == "ppr":
        want = ref.ppr(nodes, tier.damping, tier.ppr_iters)
        for i, got in enumerate(values):
            err = np.abs(got - want[:, i]).max() / np.abs(want[:, i]).max()
            assert err <= PPR_RTOL, f"ppr node {nodes[i]}: rel err {err}"
        return len(values)
    for node, got in zip(nodes, values):
        if kind == "bfs":
            want = ref.bfs(int(node)).astype(np.float32)
        else:
            want = ref.common_neighbors(int(node)).astype(np.float32)
        assert np.array_equal(got, want), f"{kind} node {node} disagrees"
    return len(values)


# ---------------------------------------------------------------------------
# Kernel phase: backend='pallas' against backend='xla', same chip
# ---------------------------------------------------------------------------

def kernel_phase(b: Build, seed: int = SEED) -> dict:
    """The ``sum``, fused and ``min`` kernels on one graph, each compared
    with the XLA path (``segment_seconds`` times it); every compared value
    is an exact integer in f32, so the two paths must agree bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.core import algorithms, engine
    from repro.core.semiring import PLUS_TIMES

    g = b.graph
    exact = engine.to_device_packed(g, correction=b.correction, backend="pallas")
    counts = engine.to_device_packed(g, drop_self_loops=False, backend="pallas")
    rng = np.random.default_rng(seed)
    # small integers: every sum of products stays exact in f32
    x = jnp.asarray(rng.integers(0, 4, size=(g.n_real, BATCH)), jnp.float32)
    sources = jnp.asarray(zipf_nodes(rng, g.n_real, BATCH), jnp.int32)
    ring = jax.jit(
        functools.partial(engine.propagate, semiring=PLUS_TIMES, allow_duplicates=True)
    )
    layer = exact.chains[0][0].fwd
    report = {
        "slots_per_layer_direction": int(layer.slot_src.shape[0]),
        "fused_stream_slots": int(exact.fused_fwd.kind.shape[0]),
    }
    cases = (
        ("sum", counts, lambda g: ring(g, x)),
        ("fused", exact, lambda g: ring(g, x)),
        # BFS ignores multiplicities, so it runs on the counts graph, which
        # carries no correction and so no fused epilogue to stand down
        ("min", counts, lambda g: algorithms.bfs_multi(g, sources)),
    )
    for name, graph, run in cases:
        engine.reset_kernel_dispatch_count()
        t = time.perf_counter()
        got = np.asarray(run(graph))
        first = time.perf_counter() - t
        dispatched = engine.KERNEL_DISPATCH_COUNT
        standdown = dict(engine.KERNEL_STANDDOWN_COUNT)
        assert dispatched > 0, f"{name}: the kernel never dispatched"
        assert not standdown, f"{name}: stood down {standdown}"
        segment = dataclasses.replace(graph, backend="xla")
        want = np.asarray(run(segment))
        assert np.array_equal(got, want), f"{name}: kernel != segment path"
        report[name] = {
            "kernel_first_call_seconds": first,
            "kernel_seconds": _timed(run, graph),
            "segment_seconds": _timed(run, segment),
            "kernel_dispatch_count": dispatched,
            "kernel_standdown_count": standdown,
        }
    return report


def _timed(run, graph) -> float:
    t = time.perf_counter()
    np.asarray(run(graph))
    return time.perf_counter() - t


# ---------------------------------------------------------------------------
# Four chips: edge-sharded PageRank against one device
# ---------------------------------------------------------------------------

def four_chip_phase(b: Build, devices) -> dict:
    import jax
    from repro.core import algorithms, engine
    from repro.distributed.sharding import edge_mesh, shard_graph_edges

    dev = engine.to_device(b.graph, correction=b.correction)
    t = time.perf_counter()
    want = np.asarray(algorithms.pagerank(dev, num_iters=20))
    one_device_seconds = time.perf_counter() - t
    mesh = edge_mesh((2, 2), ("data", "model"), devices=devices[:4])
    sharded = shard_graph_edges(dev, mesh)
    spans = set()
    rows = [sharded.correction.fwd]
    if sharded.correction.rev is not None:
        rows.append(sharded.correction.rev)
    for r in rows:
        assert r.node_row.sharding.is_fully_replicated, "a node map is split"
    for leaf in jax.tree_util.tree_leaves(
        (sharded.chains, [(r.idx, r.weight) for r in rows])
    ):
        owners = {s.device.id for s in leaf.addressable_shards}
        assert len(owners) == 4, f"an edge array sits on devices {owners}"
        assert all(
            s.data.shape[0] * 4 == leaf.shape[0] for s in leaf.addressable_shards
        ), "an edge array is replicated, not split"
        spans |= owners
    t = time.perf_counter()
    got = np.asarray(algorithms.pagerank(sharded, num_iters=20))
    sharded_seconds = time.perf_counter() - t
    diff = float(np.abs(got - want).max())
    assert diff <= FOUR_CHIP_ATOL, f"sharded PageRank differs by {diff}"
    return {
        "devices_spanned": sorted(spans),
        "max_abs_diff": diff,
        "max_rel_diff": diff / float(np.abs(want).max()),
        "one_device_first_call_seconds": one_device_seconds,
        "sharded_first_call_seconds": sharded_seconds,
    }


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the edge-sharded PageRank on a 2x2 mesh of 4 chips",
    )
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)

    from repro.kernels.bitmap_spmm import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    emit(
        "device",
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        count=len(devices),
        compile_cache=enable_compile_cache(),
    )
    assert not default_interpret(), "Pallas would run interpreted on the chip"
    b = build(SCALE)
    emit("build", **describe(b, SCALE))
    if args.four_chips:
        emit("four_chip_pagerank", **four_chip_phase(b, devices))
    else:
        emit("serving", **serving_phase(b))
        del b
        k = build(KERNEL_SCALE)
        emit("kernel_build", **describe(k, KERNEL_SCALE))
        emit("kernel", interpret=default_interpret(), **kernel_phase(k))
    stats = devices[0].memory_stats() or {}
    emit("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
