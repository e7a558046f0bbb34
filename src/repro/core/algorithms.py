"""Graph algorithms over any device representation (paper §3.4, §6.1.2).

Each algorithm is a pure function of a :class:`~repro.core.engine.DeviceGraph`
pytree, jit-compatible, and by construction produces identical results on
EXP / DEDUP-1 / DEDUP-C (duplicate-sensitive) or additionally on raw C-DUP
(duplicate-insensitive: BFS, connected components, reachability).

**Batched multi-source variants** (DESIGN.md §3): :func:`bfs_multi`,
:func:`reachable_multi`, :func:`personalized_pagerank` over a seed batch,
and :func:`common_neighbors_multi` run ``B`` independent analyses as one
``(n, B)`` frontier through the engine — a single factorized SpMM per
superstep instead of ``B`` serial traversals, with one *shared*
vote-to-halt across the batch (supersteps continue while any column is
still active; finished columns are fixed points of their own updates, so
extra supersteps cannot change them).  The batch axis carries the
``graph_batch`` logical axis for mesh sharding
(:data:`repro.distributed.sharding.GRAPH_RULES`).

The vertex-centric API of the paper maps to :func:`vertex_program`: the
user supplies ``compute(state, messages) -> state`` and a message semiring;
supersteps run under ``lax.while_loop`` with a vote-to-halt predicate.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import shard_frontier
from .correction_rows import upload_correction
from .engine import DeviceGraph, propagate, propagate_wedge
from .semiring import MAX_MIN, MIN_PLUS, OR_AND, PLUS_TIMES, Semiring

__all__ = [
    "n_nodes",
    "out_degrees",
    "in_degrees",
    "pagerank",
    "bfs",
    "bfs_multi",
    "reachable",
    "reachable_multi",
    "connected_components",
    "common_neighbor_counts",
    "common_neighbors_multi",
    "one_hot_frontier",
    "personalized_pagerank",
    "hits",
    "vertex_program",
    "shortest_paths",
    "shortest_paths_multi",
    "widest_paths",
    "widest_paths_multi",
    "scc_labels",
    "Condensation",
    "condensation",
    "triangle_counts",
    "clustering_coefficients",
]


def n_nodes(graph: DeviceGraph) -> int:
    """Number of real nodes in any device representation."""
    return graph.n if hasattr(graph, "n") else graph.n_real


_n = n_nodes


def one_hot_frontier(
    n: int,
    sources: jnp.ndarray,
    value: float = 1.0,
    fill: float = 0.0,
    dtype=jnp.float32,
) -> jnp.ndarray:
    """``(n, B)`` frontier matrix: column ``i`` is ``fill`` everywhere and
    ``value`` at ``sources[i]`` (the batched analogue of a one-hot seed).

    Precondition: ``0 <= sources[i] < n``.  Values cannot be checked under
    jit — JAX scatters silently drop out-of-bounds indices and wrap
    negative ones, leaving an all-``fill`` column — so validate at the
    boundary where sources are concrete (as ``GraphQueryServer.submit``
    does)."""
    sources = jnp.asarray(sources, dtype=jnp.int32)
    b = sources.shape[0]
    x = jnp.full((n, b), fill, dtype=dtype)
    return x.at[sources, jnp.arange(b)].set(value)


# ---------------------------------------------------------------------------
# Degree (duplicate-SENSITIVE: needs dedup; paper §6.4 Degree benchmark)
# ---------------------------------------------------------------------------

@jax.jit
def out_degrees(graph: DeviceGraph) -> jnp.ndarray:
    ones = jnp.ones((_n(graph),), dtype=jnp.float32)
    return propagate(graph, ones, PLUS_TIMES, reverse=True)


@jax.jit
def in_degrees(graph: DeviceGraph) -> jnp.ndarray:
    ones = jnp.ones((_n(graph),), dtype=jnp.float32)
    return propagate(graph, ones, PLUS_TIMES)


# ---------------------------------------------------------------------------
# PageRank (duplicate-SENSITIVE)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_iters",))
def pagerank(
    graph: DeviceGraph,
    damping: float = 0.85,
    num_iters: int = 20,
) -> jnp.ndarray:
    """Standard power-iteration PageRank with dangling redistribution."""
    n = _n(graph)
    deg = out_degrees(graph)
    x = jnp.full((n,), 1.0 / n, dtype=jnp.float32)

    def body(_, x):
        contrib = jnp.where(deg > 0, x / jnp.maximum(deg, 1.0), 0.0)
        y = propagate(graph, contrib, PLUS_TIMES)
        dangling = jnp.sum(jnp.where(deg > 0, 0.0, x))
        y = y + dangling / n
        return (1.0 - damping) / n + damping * y

    return jax.lax.fori_loop(0, num_iters, body, x)


# ---------------------------------------------------------------------------
# BFS & reachability (duplicate-INSENSITIVE: run directly on C-DUP)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iters",))
def bfs(graph: DeviceGraph, source: int, max_iters: Optional[int] = None) -> jnp.ndarray:
    """Hop distances from ``source`` (inf where unreachable); the ``B=1``
    column of :func:`bfs_multi` so there is one relaxation loop to
    maintain."""
    srcs = jnp.asarray(source, dtype=jnp.int32).reshape(1)
    return bfs_multi(graph, srcs, max_iters=max_iters)[:, 0]


@partial(jax.jit, static_argnames=("max_iters",))
def bfs_multi(
    graph: DeviceGraph,
    sources: jnp.ndarray,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Hop distances from every source at once: ``(n, B)`` for ``(B,)``
    sources; column ``i`` equals ``bfs(graph, sources[i])``.

    One min-plus SpMM relaxes all ``B`` frontiers per superstep; the
    vote-to-halt is shared (run while *any* column still changes — settled
    columns are fixed points, so they are unaffected by extra supersteps).
    Sources must satisfy ``0 <= sources[i] < n`` (see
    :func:`one_hot_frontier`).
    """
    n = _n(graph)
    max_iters = n if max_iters is None else max_iters
    dist0 = one_hot_frontier(n, sources, value=0.0, fill=jnp.inf)

    def cond(state):
        dist, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        dist, _, it = state
        relaxed = propagate(graph, dist, MIN_PLUS, hop_weight=1.0)
        new = jnp.minimum(dist, relaxed)
        return shard_frontier(new), jnp.any(new < dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.array(True), 0))
    return dist


@partial(jax.jit, static_argnames=("max_iters", "reverse"))
def reachable(
    graph: DeviceGraph,
    source: int,
    max_iters: Optional[int] = None,
    reverse: bool = False,
) -> jnp.ndarray:
    """Boolean (0/1) reachability from ``source`` under OR-AND; the
    ``B=1`` column of :func:`reachable_multi`."""
    srcs = jnp.asarray(source, dtype=jnp.int32).reshape(1)
    return reachable_multi(graph, srcs, max_iters=max_iters, reverse=reverse)[:, 0]


@partial(jax.jit, static_argnames=("max_iters", "reverse"))
def reachable_multi(
    graph: DeviceGraph,
    sources: jnp.ndarray,
    max_iters: Optional[int] = None,
    reverse: bool = False,
) -> jnp.ndarray:
    """Batched OR-AND reachability: ``(n, B)`` of 0/1 indicators.
    ``reverse=True`` follows edges backwards (ancestor reachability, via
    the packed reverse operands) — the other half of the SCC
    forward/backward intersection (:func:`scc_labels`).  Sources must
    satisfy ``0 <= sources[i] < n`` (see :func:`one_hot_frontier`)."""
    n = _n(graph)
    max_iters = n if max_iters is None else max_iters
    r0 = one_hot_frontier(n, sources, value=1.0, fill=0.0)

    def cond(state):
        r, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        r, _, it = state
        nxt = jnp.maximum(r, propagate(graph, r, OR_AND, reverse=reverse))
        return shard_frontier(nxt), jnp.any(nxt > r), it + 1

    r, _, _ = jax.lax.while_loop(cond, body, (r0, jnp.array(True), 0))
    return r


# ---------------------------------------------------------------------------
# Connected components (duplicate-INSENSITIVE) — min-label propagation
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iters", "undirected"))
def connected_components(
    graph: DeviceGraph,
    max_iters: Optional[int] = None,
    undirected: bool = True,
) -> jnp.ndarray:
    """Min-label propagation; labels = component representative ids.

    ``undirected=True`` (the default) symmetrizes every superstep by also
    propagating along reversed edges (the packed reverse operands on
    :class:`~repro.core.engine.DevicePacked`), so weakly connected
    components come out right on *asymmetric* graphs too.  The previous
    behaviour propagated forward only — treating the graph as directed,
    which splits weak components joined only against edge direction; pass
    ``undirected=False`` to get that directed min-label flow explicitly.
    (Graphs from symmetric extraction queries contain both directions, so
    either setting agrees there.)
    """
    n = _n(graph)
    max_iters = n if max_iters is None else max_iters
    labels0 = jnp.arange(n, dtype=jnp.float32)

    def cond(state):
        labels, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        labels, _, it = state
        nxt = jnp.minimum(labels, propagate(graph, labels, MIN_PLUS, hop_weight=0.0))
        if undirected:
            nxt = jnp.minimum(
                nxt, propagate(graph, labels, MIN_PLUS, hop_weight=0.0, reverse=True)
            )
        return nxt, jnp.any(nxt < labels), it + 1

    labels, _, _ = jax.lax.while_loop(cond, body, (labels0, jnp.array(True), 0))
    return labels


# ---------------------------------------------------------------------------
# Common-neighbor counting — the condensed rep's native strength:
# M = B·Bᵀ entries ARE co-occurrence counts, so *duplication is signal*
# (beyond-paper: link prediction / collaboration strength, free on C-DUP).
# ---------------------------------------------------------------------------

@jax.jit
def common_neighbor_counts(graph: DeviceGraph, seeds: jnp.ndarray) -> jnp.ndarray:
    """For a one-hot/indicator seed vector: per-node path-multiplicity mass.

    On C-DUP this counts shared virtual entities (e.g. #co-authored papers)
    — exactly the quantity dedup would destroy; exposed as a feature.
    ``seeds`` may also be an ``(n, B)`` indicator batch (one query per
    column), scored in a single SpMM.
    """
    return propagate(graph, seeds, PLUS_TIMES, allow_duplicates=True)


@jax.jit
def common_neighbors_multi(
    graph: DeviceGraph, query_nodes: jnp.ndarray
) -> jnp.ndarray:
    """Common-neighbor scores for a ``(B,)`` batch of query nodes.

    ``out[v, i]`` = number of shared virtual entities between ``v`` and
    ``query_nodes[i]`` — the recsys-serving scoring primitive, one
    propagation for the whole batch.  Query nodes must satisfy
    ``0 <= query_nodes[i] < n`` (see :func:`one_hot_frontier`).
    """
    seeds = one_hot_frontier(_n(graph), query_nodes)
    return common_neighbor_counts(graph, seeds)


# ---------------------------------------------------------------------------
# Vertex-centric API (paper §3.4) — superstep driver
# ---------------------------------------------------------------------------

class VertexProgram(NamedTuple):
    """``compute`` folds incoming aggregated messages into vertex state."""

    semiring: Semiring
    to_message: Callable[[jnp.ndarray], jnp.ndarray]
    compute: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


@partial(jax.jit, static_argnames=("program", "max_supersteps"))
def vertex_program(
    graph: DeviceGraph,
    program: VertexProgram,
    init_state: jnp.ndarray,
    max_supersteps: int = 50,
) -> jnp.ndarray:
    def cond(state):
        s, halted, it = state
        return jnp.logical_and(~halted, it < max_supersteps)

    def body(state):
        s, _, it = state
        msgs = propagate(graph, program.to_message(s), program.semiring)
        s_new = program.compute(s, msgs)
        halted = jnp.all(jnp.abs(s_new - s) < 1e-12)
        return s_new, halted, it + 1

    s, _, _ = jax.lax.while_loop(
        cond, body, (init_state, jnp.array(False), 0)
    )
    return s


# ---------------------------------------------------------------------------
# Extended analytics (beyond the paper's benchmarked set, same engine)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_iters",))
def personalized_pagerank(
    graph: DeviceGraph,
    seeds: jnp.ndarray,            # (n,) or (n, B) restart distribution(s)
    damping: float = 0.85,
    num_iters: int = 20,
) -> jnp.ndarray:
    """PageRank with restart at ``seeds`` (recommendation-style queries).

    ``seeds`` is one restart distribution ``(n,)`` (columns sum to 1) or a
    batch ``(n, B)`` — e.g. one one-hot column per user — iterated jointly
    so each power step is a single SpMM over all ``B`` queries; column
    ``i`` equals ``personalized_pagerank(graph, seeds[:, i])``.
    """
    deg = out_degrees(graph)
    degb = deg if seeds.ndim == 1 else deg[:, None]
    seeds = shard_frontier(seeds.astype(jnp.float32))
    x = seeds

    def body(_, x):
        with jax.named_scope("ppr.update"):
            contrib = jnp.where(degb > 0, x / jnp.maximum(degb, 1.0), 0.0)
        y = propagate(graph, contrib, PLUS_TIMES)
        with jax.named_scope("ppr.update"):
            dangling = jnp.sum(jnp.where(degb > 0, 0.0, x), axis=0)
            y = y + dangling * seeds
            return (1.0 - damping) * seeds + damping * y

    return jax.lax.fori_loop(0, num_iters, body, x)


@partial(jax.jit, static_argnames=("num_iters",))
def hits(
    graph: DeviceGraph, num_iters: int = 30
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Hubs & authorities by power iteration (duplicate-sensitive)."""
    n = _n(graph)
    h = jnp.full((n,), 1.0 / jnp.sqrt(n), dtype=jnp.float32)

    def body(_, carry):
        h, a = carry
        a = propagate(graph, h, PLUS_TIMES)            # auth = sum of in-hubs
        a = a / jnp.maximum(jnp.linalg.norm(a), 1e-12)
        h = propagate(graph, a, PLUS_TIMES, reverse=True)
        h = h / jnp.maximum(jnp.linalg.norm(h), 1e-12)
        return h, a

    h, a = jax.lax.fori_loop(0, num_iters, body, (h, jnp.zeros_like(h)))
    return h, a


# ---------------------------------------------------------------------------
# Weighted / temporal semiring analytics (DESIGN.md §11): edge properties
# ride on condensed chains as per-virtual-layer weights — every incidence
# step stays an unweighted kernelizable SpMM.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("max_iters",))
def shortest_paths_multi(
    graph: DeviceGraph,
    sources: jnp.ndarray,
    layer_weights=None,
    hop_weight: Optional[float] = None,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Batched min-plus shortest paths: ``(n, B)`` distances (inf where
    unreachable), relaxed to a fixed point à la Bellman-Ford.

    ``layer_weights`` (see :func:`~repro.core.engine.propagate`) carries
    non-negative per-virtual-layer costs: a condensed path costs the sum
    of its virtual-node weights, plus ``hop_weight`` per logical hop when
    given (direct real->real edges cost only ``hop_weight``).  Called
    with neither, it degrades to hop counting — identical to
    :func:`bfs_multi`.
    """
    n = _n(graph)
    max_iters = n if max_iters is None else max_iters
    if layer_weights is None and hop_weight is None:
        hop_weight = 1.0
    dist0 = one_hot_frontier(n, sources, value=0.0, fill=jnp.inf)

    def cond(state):
        dist, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        dist, _, it = state
        relaxed = propagate(
            graph, dist, MIN_PLUS,
            hop_weight=hop_weight, layer_weights=layer_weights,
        )
        new = jnp.minimum(dist, relaxed)
        return shard_frontier(new), jnp.any(new < dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.array(True), 0))
    return dist


@partial(jax.jit, static_argnames=("max_iters",))
def shortest_paths(
    graph: DeviceGraph,
    source: int,
    layer_weights=None,
    hop_weight: Optional[float] = None,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Single-source min-plus distances; the ``B=1`` column of
    :func:`shortest_paths_multi` (the looped oracle the batched path is
    benchmarked against)."""
    srcs = jnp.asarray(source, dtype=jnp.int32).reshape(1)
    return shortest_paths_multi(
        graph, srcs, layer_weights=layer_weights,
        hop_weight=hop_weight, max_iters=max_iters,
    )[:, 0]


@partial(jax.jit, static_argnames=("max_iters",))
def widest_paths_multi(
    graph: DeviceGraph,
    sources: jnp.ndarray,
    layer_capacities=None,
    hop_weight: Optional[float] = None,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Batched max-min widest (bottleneck) paths: ``(n, B)`` widths —
    0 where unreachable, ``inf`` at each source.

    ``layer_capacities`` carries non-negative per-virtual-layer
    capacities: a path's width is the min capacity along it, the answer
    the max over paths (the :data:`~repro.core.semiring.MAX_MIN`
    semiring).  Without capacities every edge has infinite capacity and
    the result is reachability scaled to {0, inf}.
    """
    n = _n(graph)
    max_iters = n if max_iters is None else max_iters
    w0 = one_hot_frontier(n, sources, value=jnp.inf, fill=0.0)

    def cond(state):
        w, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        w, _, it = state
        relaxed = propagate(
            graph, w, MAX_MIN,
            hop_weight=hop_weight, layer_weights=layer_capacities,
        )
        new = jnp.maximum(w, relaxed)
        return shard_frontier(new), jnp.any(new > w), it + 1

    w, _, _ = jax.lax.while_loop(cond, body, (w0, jnp.array(True), 0))
    return w


@partial(jax.jit, static_argnames=("max_iters",))
def widest_paths(
    graph: DeviceGraph,
    source: int,
    layer_capacities=None,
    hop_weight: Optional[float] = None,
    max_iters: Optional[int] = None,
) -> jnp.ndarray:
    """Single-source max-min widths; the ``B=1`` column of
    :func:`widest_paths_multi`."""
    srcs = jnp.asarray(source, dtype=jnp.int32).reshape(1)
    return widest_paths_multi(
        graph, srcs, layer_capacities=layer_capacities,
        hop_weight=hop_weight, max_iters=max_iters,
    )[:, 0]


# ---------------------------------------------------------------------------
# Strongly connected components + condensation DAG layering (DESIGN.md §11;
# the cppdep dependency-cycle workload): forward ∧ backward reachability
# over pivot batches, entirely on the condensed representation.
# ---------------------------------------------------------------------------

def scc_labels(
    graph: DeviceGraph, batch: int = 32, max_iters: Optional[int] = None
) -> np.ndarray:
    """SCC label per node: the minimum member id of its component.

    Batched forward/backward pivot sweep: each round takes the ``batch``
    lowest unassigned node ids as pivots, computes descendants
    (:func:`reachable_multi`) and ancestors (``reverse=True``, the packed
    reverse operands) for all of them in two batched OR-AND fixpoints,
    and labels each pivot's forward∧backward intersection — exactly its
    SCC.  Every pivot is a member of its own intersection, so each round
    assigns at least ``batch`` nodes; because pivots are the lowest
    unassigned ids and whole SCCs are labeled at once, every pivot is the
    minimum id of its component — labels are deterministic and
    representation-independent.  ``batch=1`` is the looped single-source
    oracle.
    """
    n = _n(graph)
    batch = max(1, min(int(batch), n))
    labels = np.full(n, -1, dtype=np.int64)
    while True:
        unassigned = np.flatnonzero(labels < 0)
        if unassigned.size == 0:
            break
        pivots = unassigned[:batch]
        # pad to the fixed batch width so every round reuses one compiled
        # executable; duplicate columns are skipped at assignment
        padded = np.concatenate(
            [pivots, np.full(batch - pivots.size, pivots[0], dtype=pivots.dtype)]
        )
        srcs = jnp.asarray(padded.astype(np.int32))
        fwd = np.asarray(reachable_multi(graph, srcs, max_iters=max_iters))
        bwd = np.asarray(
            reachable_multi(graph, srcs, max_iters=max_iters, reverse=True)
        )
        both = (fwd > 0) & (bwd > 0)
        for j, p in enumerate(padded.tolist()):
            if labels[p] >= 0:
                continue  # already labeled (same-SCC pivot or pad column)
            members = both[:, j] & (labels < 0)
            labels[members] = p
    return labels


class Condensation(NamedTuple):
    """SCC condensation of a graph: per-node labels, the component DAG,
    and its longest-path-to-sink topological layering (the cppdep
    package-dependency report: layer 0 = leaf components, each higher
    layer depends only on lower ones)."""

    labels: np.ndarray      # (n,) SCC label = min member id
    component: np.ndarray   # (n,) dense component index, ordered by label
    sizes: np.ndarray       # (k,) members per component
    dag_src: np.ndarray     # inter-component edges (dense ids), deduped
    dag_dst: np.ndarray
    layers: np.ndarray      # (k,) longest path length to a sink

    @property
    def n_components(self) -> int:
        return int(self.sizes.size)


def condensation(
    graph: DeviceGraph,
    labels: Optional[np.ndarray] = None,
    batch: int = 32,
) -> Condensation:
    """Condense SCCs to a DAG and layer it topologically — without
    expanding the graph: the component adjacency comes from ONE batched
    OR-AND propagation of the (n, k) membership indicator matrix (column
    c of the result marks every node with an in-edge from component c).
    """
    if labels is None:
        labels = scc_labels(graph, batch=batch)
    n = _n(graph)
    uniq, comp = np.unique(labels, return_inverse=True)
    k = uniq.size
    sizes = np.bincount(comp, minlength=k)
    member = np.zeros((n, k), dtype=np.float32)
    member[np.arange(n), comp] = 1.0
    hit = np.asarray(propagate(graph, jnp.asarray(member), OR_AND))
    node, from_comp = np.nonzero(hit > 0)
    to_comp = comp[node]
    keep = from_comp != to_comp
    if keep.any():
        pairs = np.unique(
            np.stack([from_comp[keep], to_comp[keep]], axis=1), axis=0
        )
        dag_src, dag_dst = pairs[:, 0], pairs[:, 1]
    else:
        dag_src = np.zeros(0, np.int64)
        dag_dst = np.zeros(0, np.int64)
    # longest-path-to-sink layering: sinks stay 0, everything else is
    # 1 + max over successors; monotone relaxation converges within the
    # DAG's longest path length
    layers = np.zeros(k, dtype=np.int64)
    for _ in range(k + 1):
        nxt = np.zeros(k, dtype=np.int64)
        if dag_src.size:
            np.maximum.at(nxt, dag_src, layers[dag_dst] + 1)
        if np.array_equal(nxt, layers):
            break
        layers = nxt
    return Condensation(labels, comp, sizes, dag_src, dag_dst, layers)


# ---------------------------------------------------------------------------
# Triangles & clustering coefficients (DESIGN.md §11): two-hop wedge
# counting needs the *quadratic* DEDUP correction — duplicate wedges
# through shared virtual nodes (engine.propagate_wedge).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("mode",))
def _triangle_block(graph, X, wedge, mode):
    a1 = propagate(graph, X, PLUS_TIMES)
    if mode == "wedge":
        a2 = propagate_wedge(graph, X, wedge=wedge)
    else:
        a2 = propagate(graph, a1, PLUS_TIMES)
    return 0.5 * jnp.sum(a1 * a2, axis=0)


def triangle_counts(
    graph: DeviceGraph,
    block: int = 128,
    mode: str = "per_step",
    wedge=None,
) -> np.ndarray:
    """Per-node triangle counts ``t[v] = ½ Σ_w A[v,w]·(A²)[v,w]`` on a
    symmetric simple graph (A = dedup'd adjacency, zero diagonal).

    Runs condensation-native: identity columns in blocks of ``block``
    through two exact ring propagations per block — never materializing
    A.  ``mode='per_step'`` corrects each hop linearly (DEDUP-C);
    ``mode='wedge'`` runs both hops RAW (plain kernel-path SpMMs) and
    subtracts the quadratic wedge correction once
    (:func:`~repro.core.engine.propagate_wedge`; pass ``wedge`` triples
    from :func:`~repro.core.dedup.build_wedge_correction` to make the
    correction a single sparse pass).  Both modes are byte-identical on
    integer counts.  ``block=1`` is the looped per-node oracle.
    """
    n = _n(graph)
    block = max(1, min(int(block), n))
    wedge_dev = None
    if wedge is not None:
        wedge_dev = upload_correction(*tuple(wedge), n)
        mode = "wedge"
    t = np.zeros(n, dtype=np.float64)
    for lo in range(0, n, block):
        cols = np.arange(lo, min(lo + block, n))
        X = np.zeros((n, block), dtype=np.float32)
        X[cols, np.arange(cols.size)] = 1.0
        contrib = np.asarray(
            _triangle_block(graph, jnp.asarray(X), wedge_dev, mode)
        )
        t[cols] += contrib[: cols.size]
    return t


def clustering_coefficients(
    graph: DeviceGraph,
    block: int = 128,
    mode: str = "per_step",
    wedge=None,
) -> np.ndarray:
    """Local clustering coefficient ``c[v] = 2·t[v] / (deg[v]·(deg[v]−1))``
    (0 where degree < 2), from :func:`triangle_counts` and the exact
    dedup'd degrees (:func:`out_degrees` on a corrected graph)."""
    t = triangle_counts(graph, block=block, mode=mode, wedge=wedge)
    deg = np.asarray(out_degrees(graph), dtype=np.float64)
    denom = deg * (deg - 1.0)
    return np.where(denom > 0, 2.0 * t / np.maximum(denom, 1.0), 0.0)
