"""The one traffic generator: a mix file's parameters -> an open-loop
schedule.

A mix file (``bench/traffic/<name>.json``) gives:

- ``rate_qps``: the offered load in queries per second;
- ``kinds``: the share of each query kind, summing to 1;
- ``nodes``: ``"uniform"`` over the served nodes, or ``{"zipf": a}`` for
  Zipf(a) over a fixed permutation of the configuration's structure;
- ``check``: how many answers of each kind the reference checks.

The work is the same for every seed: the window of ``seconds`` holds
exactly ``round(rate_qps * seconds)`` requests, each kind exactly its
share, and the multiset of (kind, rank) pairs and the arrival instants
(Poisson, conditioned on their count: uniform times) are drawn once,
from a fixed seed.  A Zipf rank names the same node of the structure on
every seed (``node_of``, the seed's relabelling of it), so the popular
nodes are as deep and as connected on every seed.  The seed draws which
request comes at which instant, the nodes of uniform ranks, and the
checked sample.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import gen


@dataclasses.dataclass
class Schedule:
    due: np.ndarray      # (N,) seconds after the window opens, ascending
    kinds: list          # (N,) kind of each request
    nodes: np.ndarray    # (N,) seed node of each request
    checked: np.ndarray  # indices of the requests whose answers are checked


# the seed of the work every run offers
WORK_SEED = 0


def schedule(mix: dict, node_of: np.ndarray, seed: int, seconds: float) -> Schedule:
    """``node_of[k]``: the served node of the structure's node ``k``."""
    n_nodes = node_of.size
    work = np.random.default_rng([WORK_SEED, 2])
    n = int(round(mix["rate_qps"] * seconds))
    names = sorted(mix["kinds"])
    counts = [int(round(mix["kinds"][k] * n)) for k in names]
    counts[-1] = n - sum(counts[:-1])
    kinds = np.repeat(np.arange(len(names)), counts)
    work.shuffle(kinds)
    spec = mix["nodes"]
    if spec == "uniform":
        ranks = work.integers(0, n_nodes, size=n)
    else:
        ranks = gen.zipf_ranks(work, n_nodes, n, spec["zipf"])
        popular = node_of[work.permutation(n_nodes)]
    due = np.sort(work.uniform(0.0, seconds, size=n))
    rng = np.random.default_rng([seed, 2])
    order = rng.permutation(n)
    kinds, ranks = kinds[order], ranks[order]
    nodes = rng.permutation(n_nodes)[ranks] if spec == "uniform" else popular[ranks]
    picked = []
    for i, k in enumerate(names):
        of_kind = np.flatnonzero(kinds == i)
        take = min(int(mix["check"].get(k, 0)), of_kind.size)
        picked.append(rng.choice(of_kind, size=take, replace=False))
    return Schedule(
        due=due,
        kinds=[names[i] for i in kinds],
        nodes=np.asarray(nodes, dtype=np.int64),
        checked=np.sort(np.concatenate(picked)) if picked else np.empty(0, int),
    )
