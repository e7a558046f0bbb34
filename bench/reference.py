"""Plain host reference for the served kinds, from the catalog rows alone.

Every configuration's query joins its node table to an item table
through a chain of relations (DBLP: author -> publication; TPC-H:
customer -> order -> part).  The configuration reduces its rows to one
``(node, item)`` pair per path through that chain (its ``incidence``),
and two nodes are neighbours when they share an item.  From that bag of
pairs this module answers BFS, common-neighbour counts and personalized
PageRank in float64 with NumPy and SciPy.  It imports nothing of the
system under test and uses nothing it made.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


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


class Reference:
    """Nodes ``u ~ v`` (``u != v``) when some item holds both."""

    def __init__(self, n: int, node: np.ndarray, item: np.ndarray):
        node = np.asarray(node, dtype=np.int64)
        item = np.asarray(item, dtype=np.int64)
        self.n = int(n)
        self.n_items = int(item.max()) + 1 if item.size else 0
        self.n2i = _csr(node, item, self.n)
        self.i2n = _csr(item, node, self.n_items)
        # paths through the chain: one per (node, item) pair in the bag
        self.paths = sp.csr_matrix(
            (np.ones(node.size), (node, item)), shape=(self.n, self.n_items)
        )
        self._surplus = None

    def bfs(self, source: int) -> np.ndarray:
        dist = np.full(self.n, np.inf)
        dist[source] = 0.0
        seen = np.zeros(self.n_items, dtype=bool)
        frontier = np.array([source])
        hop = 0
        while frontier.size:
            hop += 1
            items = np.unique(_gather_rows(*self.n2i, frontier))
            items = items[~seen[items]]
            seen[items] = True
            nbrs = np.unique(_gather_rows(*self.i2n, items))
            frontier = nbrs[np.isinf(dist[nbrs])]
            dist[frontier] = hop
        return dist

    def common_neighbors(self, node: int) -> np.ndarray:
        """Paths from ``node`` to each node through the chain, itself
        included: the multiplicity of the condensed graph's edge."""
        row = self.paths[node]
        return np.asarray((self.paths @ row.T).todense()).ravel()

    def surplus(self):
        """``(S, deg)``: the path counts ``M = P P^T`` less the simple
        graph ``A`` (1 where two distinct nodes share an item), so that
        ``A x = P (P^T x) - S x``; and each node's degree in ``A``.  The
        product runs over ``P``'s few entries rather than ``A``'s many."""
        if self._surplus is None:
            m = (self.paths @ self.paths.T).tocsr()
            diag = m.diagonal()
            deg = np.diff(m.indptr) - (diag > 0)
            m.data -= 1.0
            m.setdiag(diag)
            m.eliminate_zeros()
            self._surplus = (m, deg.astype(np.float64))
        return self._surplus

    def ppr(self, nodes, damping: float, iters: int) -> np.ndarray:
        """Float64 power iteration of personalized PageRank on the simple
        graph, one column per node; a node with no neighbour returns its
        mass to the seed."""
        s, deg = self.surplus()
        deg = deg[:, None]
        seeds = np.zeros((self.n, len(nodes)))
        seeds[np.asarray(nodes), np.arange(len(nodes))] = 1.0
        x = seeds
        for _ in range(iters):
            contrib = np.where(deg > 0, x / np.maximum(deg, 1.0), 0.0)
            dangling = np.where(deg > 0, 0.0, x).sum(axis=0)
            y = self.paths @ (self.paths.T @ contrib) - s @ contrib
            x = (1.0 - damping) * seeds + damping * (y + dangling * seeds)
        return x


def ppr_gap(got: np.ndarray, want: np.ndarray) -> float:
    """The widest gap of one answer: max |got - want| over max |want|."""
    return float(np.abs(got - want).max() / np.abs(want).max())
