"""Shared dense-expansion differential oracle (ISSUE 9, DESIGN.md §11).

Every condensation-native algorithm in :mod:`repro.core.algorithms` is
checked against a NumPy reference that works on the *expanded* dense
adjacency matrix: expand the condensed graph via
:meth:`CondensedGraph.expand`, materialize ``A`` (or the multiplicity
matrix ``M``), and run a brute-force implementation with no JAX, no
semiring machinery, and no condensed representation anywhere — so a bug
in the engine/dedup/kernels stack cannot cancel out of both sides.

All references are deliberately naive (dense fixpoints, path
enumeration); they are oracles, not implementations.  Tests import this
module directly (``from oracle import ...`` — tests/ is on sys.path via
conftest).
"""
from __future__ import annotations

import numpy as np

from repro.core.condensed import CondensedGraph, ExpandedGraph

__all__ = [
    "dense_multiplicity",
    "dense_adjacency",
    "bipartite_semiring_ref",
    "propagate_ref",
    "bfs_ref",
    "reachable_ref",
    "connected_components_ref",
    "common_neighbors_ref",
    "personalized_pagerank_ref",
    "scc_labels_ref",
    "condensation_ref",
    "triangle_counts_ref",
    "clustering_coefficients_ref",
    "shortest_paths_ref",
    "widest_paths_ref",
    "weighted_dense_ref",
]


# ---------------------------------------------------------------------------
# Expansion: condensed -> dense matrices
# ---------------------------------------------------------------------------

def _expanded(graph) -> ExpandedGraph:
    if isinstance(graph, CondensedGraph):
        return graph.expand()
    if isinstance(graph, ExpandedGraph):
        return graph
    raise TypeError(f"cannot expand {type(graph).__name__}")


def dense_multiplicity(graph, drop_self_loops: bool = True) -> np.ndarray:
    """Dense path-multiplicity matrix ``M`` (int64) of the expanded graph."""
    exp = _expanded(graph)
    if drop_self_loops:
        exp = exp.without_self_loops()
    return exp.adjacency_multiplicity()


def dense_adjacency(graph, drop_self_loops: bool = True) -> np.ndarray:
    """Dense simple 0/1 adjacency ``A = min(M, 1)`` (float64)."""
    return np.minimum(dense_multiplicity(graph, drop_self_loops), 1).astype(
        np.float64
    )


# ---------------------------------------------------------------------------
# Single-layer semiring SpMM reference (the kernel-level oracle)
# ---------------------------------------------------------------------------

def bipartite_semiring_ref(edges, x, semiring, reverse: bool = False):
    """Dense NumPy y[d] = ⊕_{(s,d)∈E} x[s] for one bipartite layer —
    the pure-NumPy twin of ``repro.kernels.ref.segment_semiring_ref``,
    with no JAX segment ops anywhere."""
    src = np.asarray(edges.dst if reverse else edges.src)
    dst = np.asarray(edges.src if reverse else edges.dst)
    n_out = edges.n_src if reverse else edges.n_dst
    x = np.asarray(x, dtype=np.float64)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    y = np.full((n_out, x.shape[1]), float(semiring.zero), dtype=np.float64)
    if semiring.add_kind == "sum":
        np.add.at(y, dst, x[src])
    elif semiring.add_kind == "min":
        np.minimum.at(y, dst, x[src])
    elif semiring.add_kind == "max":
        np.maximum.at(y, dst, x[src])
    else:  # pragma: no cover - unknown semiring
        raise ValueError(semiring.add_kind)
    return y[:, 0] if squeeze else y


def propagate_ref(A: np.ndarray, x: np.ndarray, semiring, reverse=False):
    """Dense one-hop y[w] = ⊕_{u→w} x[u] ⊗ A[u,w] (the engine's Aᵀx
    orientation) over an explicit adjacency matrix."""
    T = A if reverse else A.T
    x = np.asarray(x, dtype=np.float64)
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    if semiring.add_kind == "sum":
        y = T @ x
    else:
        mask = T > 0
        vals = np.where(mask[:, :, None], x[None, :, :], float(semiring.zero))
        red = np.min if semiring.add_kind == "min" else np.max
        y = red(vals, axis=1) if mask.any() else np.full(
            (T.shape[0], x.shape[1]), float(semiring.zero)
        )
    return y[:, 0] if squeeze else y


# ---------------------------------------------------------------------------
# Traversal references
# ---------------------------------------------------------------------------

def bfs_ref(A: np.ndarray, sources) -> np.ndarray:
    """(n, B) hop distances (inf where unreachable) by frontier BFS."""
    n = A.shape[0]
    sources = np.atleast_1d(np.asarray(sources))
    D = np.full((n, sources.size), np.inf)
    for j, s in enumerate(sources.tolist()):
        dist = D[:, j]
        dist[s] = 0.0
        frontier = {int(s)}
        hops = 0
        while frontier:
            hops += 1
            nxt = set()
            for u in frontier:
                for v in np.flatnonzero(A[u]):
                    if dist[v] == np.inf:
                        dist[v] = hops
                        nxt.add(int(v))
            frontier = nxt
    return D


def reachable_ref(A: np.ndarray, sources, reverse: bool = False) -> np.ndarray:
    """(n, B) {0,1} reachability (source marked reachable from itself)."""
    D = bfs_ref(A.T if reverse else A, sources)
    return np.isfinite(D).astype(np.float64)


def connected_components_ref(A: np.ndarray, undirected: bool = True):
    """Component label = min member id; symmetrizes unless told not to
    (in which case it is forward-reachability labeling, the old buggy
    directed semantics — kept so the regression test can show the two
    genuinely differ on an asymmetric fixture)."""
    S = np.maximum(A, A.T) if undirected else A
    n = A.shape[0]
    labels = np.arange(n, dtype=np.float64)
    for _ in range(n):
        nxt = labels.copy()
        for u, v in zip(*np.nonzero(S)):
            nxt[v] = min(nxt[v], labels[u])
        if np.array_equal(nxt, labels):
            break
        labels = nxt
    return labels


def common_neighbors_ref(M: np.ndarray, nodes) -> np.ndarray:
    """(n, B) multiplicity-weighted common-neighbor counts: row ``s`` of
    the dense multiplicity matrix per queried node."""
    nodes = np.atleast_1d(np.asarray(nodes))
    return M[nodes].T.astype(np.float64)


def personalized_pagerank_ref(A: np.ndarray, nodes, damping: float = 0.85,
                              iters: int = 20) -> np.ndarray:
    """``(n, len(nodes))`` personalized PageRank by dense power iteration,
    one one-hot restart column per node; a node with no out-edge returns
    its mass to the restart column."""
    deg = A.sum(axis=1)[:, None]
    seeds = np.zeros((A.shape[0], len(nodes)))
    seeds[np.asarray(nodes), np.arange(len(nodes))] = 1.0
    x = seeds
    for _ in range(iters):
        contrib = np.where(deg > 0, x / np.maximum(deg, 1.0), 0.0)
        dangling = np.where(deg > 0, 0.0, x).sum(axis=0)
        x = (1 - damping) * seeds + damping * (A.T @ contrib + dangling * seeds)
    return x


# ---------------------------------------------------------------------------
# SCC / condensation references
# ---------------------------------------------------------------------------

def _closure(A: np.ndarray) -> np.ndarray:
    R = np.eye(A.shape[0], dtype=bool) | (A > 0)
    while True:
        nxt = R | (R @ R)
        if np.array_equal(nxt, R):
            return R
        R = nxt


def scc_labels_ref(A: np.ndarray) -> np.ndarray:
    """SCC label per node = min member id, via transitive closure."""
    R = _closure(A)
    same = R & R.T
    return np.array(
        [np.flatnonzero(same[i])[0] for i in range(A.shape[0])], dtype=np.int64
    )


def condensation_ref(A: np.ndarray):
    """(labels, component, sizes, dag edge set, layers) of the SCC DAG;
    layers = longest path to a sink, computed by brute relaxation."""
    labels = scc_labels_ref(A)
    uniq, comp = np.unique(labels, return_inverse=True)
    k = uniq.size
    sizes = np.bincount(comp, minlength=k)
    dag = set()
    for u, v in zip(*np.nonzero(A)):
        if comp[u] != comp[v]:
            dag.add((int(comp[u]), int(comp[v])))
    layers = np.zeros(k, dtype=np.int64)
    for _ in range(k + 1):
        nxt = np.zeros(k, dtype=np.int64)
        for s, d in dag:
            nxt[s] = max(nxt[s], layers[d] + 1)
        if np.array_equal(nxt, layers):
            break
        layers = nxt
    return labels, comp, sizes, dag, layers


# ---------------------------------------------------------------------------
# Triangle / clustering references
# ---------------------------------------------------------------------------

def triangle_counts_ref(A: np.ndarray) -> np.ndarray:
    """t[v] = ½ Σ_w A[v,w]·(A²)[v,w] on a symmetric simple adjacency."""
    return 0.5 * np.sum(A * (A @ A), axis=1)


def clustering_coefficients_ref(A: np.ndarray) -> np.ndarray:
    t = triangle_counts_ref(A)
    deg = A.sum(axis=1)
    denom = deg * (deg - 1.0)
    return np.where(denom > 0, 2.0 * t / np.maximum(denom, 1.0), 0.0)


# ---------------------------------------------------------------------------
# Weighted path references (min-plus / max-min)
# ---------------------------------------------------------------------------

def shortest_paths_ref(W: np.ndarray, sources) -> np.ndarray:
    """(n, B) min-plus distances by Bellman-Ford over a dense edge-cost
    matrix ``W`` (inf = no edge).  For unweighted hop counting pass
    ``np.where(A > 0, 1.0, np.inf)``."""
    n = W.shape[0]
    sources = np.atleast_1d(np.asarray(sources))
    D = np.full((n, sources.size), np.inf)
    D[sources, np.arange(sources.size)] = 0.0
    for _ in range(n):
        relaxed = np.min(D[:, None, :] + W[:, :, None], axis=0)
        nxt = np.minimum(D, relaxed)
        if np.array_equal(nxt, D):
            break
        D = nxt
    return D


def widest_paths_ref(C: np.ndarray, sources) -> np.ndarray:
    """(n, B) max-min path widths over a dense edge-capacity matrix ``C``
    (0 = no edge); sources get width inf."""
    n = C.shape[0]
    sources = np.atleast_1d(np.asarray(sources))
    W = np.zeros((n, sources.size))
    W[sources, np.arange(sources.size)] = np.inf
    for _ in range(n):
        relaxed = np.max(
            np.minimum(W[:, None, :], C[:, :, None]), axis=0
        )
        nxt = np.maximum(W, relaxed)
        if np.array_equal(nxt, W):
            break
        W = nxt
    return W


def weighted_dense_ref(
    graph: CondensedGraph, layer_weights, kind: str = "min_plus"
) -> np.ndarray:
    """Dense per-edge cost (``min_plus``) or capacity (``max_min``)
    matrix of a condensed graph whose virtual layers carry weights.

    Enumerates each chain level-by-level with dense semiring matrix
    products: the cost of a condensed edge u→w is the ⊗-product of the
    virtual-node weights along the best path u→…→w, exactly the quantity
    ``propagate(..., layer_weights=...)`` computes one hop of.  Direct
    edges and self-loops follow the engine's conventions (direct =
    weight identity; self-loops dropped).
    """
    n = graph.n_real
    if kind == "min_plus":
        zero, better = np.inf, np.minimum
        apply_w = lambda T, w: T + w[None, :]
    elif kind == "max_min":
        zero, better = 0.0, np.maximum
        apply_w = lambda T, w: np.minimum(T, w[None, :])
    else:
        raise ValueError(kind)

    def level_dense(e, n_src, n_dst):
        B = np.full((n_src, n_dst), zero)
        one = 0.0 if kind == "min_plus" else np.inf
        B[np.asarray(e.src), np.asarray(e.dst)] = one
        return B

    def semiring_matmul(T, B):
        # (a, b) ⊗ (b, c) with ⊕ = better over the middle axis
        if kind == "min_plus":
            return np.min(T[:, :, None] + B[None, :, :], axis=1)
        return np.max(np.minimum(T[:, :, None], B[None, :, :]), axis=1)

    W = np.full((n, n), zero)
    layer_weights = tuple(layer_weights) if layer_weights is not None else None
    for ci, chain in enumerate(graph.chains):
        sizes = [n] + list(chain.layer_sizes) + [n]
        T = None
        for li, e in enumerate(chain.edges):
            B = level_dense(e, sizes[li], sizes[li + 1])
            T = B if T is None else semiring_matmul(T, B)
            if layer_weights is not None and li < len(chain.edges) - 1:
                w = np.asarray(layer_weights[ci][li], dtype=np.float64)
                T = apply_w(T, w)
        W = better(W, T)
    if graph.direct is not None:
        e = graph.direct
        one = 0.0 if kind == "min_plus" else np.inf
        D = np.full((n, n), zero)
        D[np.asarray(e.src), np.asarray(e.dst)] = one
        W = better(W, D)
    np.fill_diagonal(W, zero)
    return W
