"""Seeded generators the benchmark owns: catalog helpers and seed nodes.

A catalog is a plain ``{table: {column: ndarray}}`` dict, so the host
reference reads it without touching the system under test.  Each
configuration draws its catalog's structure once, from the structure seed
in its file, and the run's ``--seed`` relabels the rows inside each
128-row tile (:func:`tile_relabel`).  Relabelling inside a tile keeps
every packed shape of the served graph, so every run does the same work
and finds its compiled programs in the cache, while the rows each node
belongs to, and so every answer, change with the seed.
"""
from __future__ import annotations

import itertools

import numpy as np

# The packing tile of the system under test: the served graph's shapes
# depend only on which 128x128 tiles hold an edge.
TILE = 128


def zipf_sizes(n: int, mean: float, rng: np.random.Generator, a: float = 2.5) -> np.ndarray:
    """Heavy-tailed group sizes, at least 1 each, that sum to
    ``round(n * mean)``: Zipf(a) scaled to the mean, each rounded up with
    the chance of its fraction, and the last few rows of the total settled
    on groups drawn at random.  (Truncating the scaled draw, as
    ``repro.data.synth.zipf_sizes`` does, sends most groups of size 1.47 to
    1 and loses a fifth of the rows.)"""
    raw = rng.zipf(a, size=n).astype(np.float64)
    raw *= mean / raw.mean()
    sizes = np.floor(raw).astype(np.int64)
    sizes += rng.random(n) < raw - sizes
    sizes = np.maximum(sizes, 1)
    short = int(round(n * mean)) - int(sizes.sum())
    if short > 0:
        sizes[rng.choice(n, size=short, replace=False)] += 1
    elif short < 0:
        sizes[rng.choice(np.flatnonzero(sizes > 1), size=-short, replace=False)] -= 1
    return sizes


def weighted_draws_without_replacement(
    sizes: np.ndarray, p: np.ndarray, rng: np.random.Generator
) -> np.ndarray:
    """Group ``g`` gets ``sizes[g]`` distinct items of ``range(p.size)``,
    drawn as ``rng.choice(p.size, sizes[g], replace=False, p=p)`` draws
    them: the first ``sizes[g]`` distinct values of an i.i.d. stream from
    ``p``.  Returns the items grouped by ``g``, each group in pick order."""
    sizes = np.asarray(sizes, dtype=np.int64)
    n = p.size
    if np.any(sizes > np.count_nonzero(p > 0)):
        raise ValueError("a group asks for more items than have weight")
    cdf = np.cumsum(p, dtype=np.float64)
    cdf /= cdf[-1]
    done_keys, done_pos = [], []
    keys = np.empty(0, dtype=np.int64)
    pos = np.empty(0, dtype=np.int64)
    have = np.zeros(sizes.size, dtype=np.int64)
    drawn = 0
    for rounds in itertools.count():
        need = sizes - have
        short = np.flatnonzero(need > 0)
        if short.size == 0:
            break
        group = np.repeat(short, need[short] << min(rounds, 10))
        item = cdf.searchsorted(rng.random(group.size), side="right")
        keys = np.concatenate([keys, group * n + item])
        pos = np.concatenate([pos, drawn + np.arange(group.size)])
        drawn += group.size
        keys, first = np.unique(keys, return_index=True)
        pos = pos[first]
        order = np.lexsort((pos, keys // n))
        keys, pos = keys[order], pos[order]
        g = keys // n
        keep = np.arange(g.size) - np.searchsorted(g, g) < sizes[g]
        keys, pos, g = keys[keep], pos[keep], g[keep]
        have = np.maximum(have, np.bincount(g, minlength=sizes.size))
        full = have[g] == sizes[g]
        done_keys.append(keys[full])
        done_pos.append(pos[full])
        keys, pos = keys[~full], pos[~full]
    keys = np.concatenate(done_keys) if done_keys else keys
    pos = np.concatenate(done_pos) if done_pos else pos
    order = np.lexsort((pos, keys // n))
    return keys[order] % n


def tile_relabel(n: int, rng: np.random.Generator, fixed=None) -> np.ndarray:
    """A permutation of ``range(n)`` that moves each id only inside its
    128-id tile.  Ids where ``fixed`` is true stay where they are."""
    perm = np.arange(n)
    keep = np.zeros(n, dtype=bool) if fixed is None else np.asarray(fixed, bool)
    tile = np.arange(n) // TILE
    free = np.flatnonzero(~keep)
    # a random key inside each tile, sorted within the tile, shuffles it
    order = free[np.lexsort((rng.random(free.size), tile[free]))]
    perm[free] = order
    return perm


def zipf_ranks(rng: np.random.Generator, n: int, size: int, a: float) -> np.ndarray:
    """``size`` ranks in ``range(n)``, rank ``k`` drawn with weight
    ``(k + 1)^-a``; a permutation of the nodes then names each rank's node
    (``chip_smoke.zipf_nodes`` draws both at once)."""
    ranks = np.empty(0, dtype=np.int64)
    while ranks.size < size:
        draw = rng.zipf(a, size=4 * size)
        ranks = np.concatenate([ranks, draw[draw <= n]])
    return ranks[:size] - 1
