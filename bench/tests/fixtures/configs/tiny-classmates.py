"""Test-only configuration: each student takes a few distinct courses."""
import numpy as np

import gen


def tables(cfg, seed):
    rng = np.random.default_rng(cfg["structure_seed"])
    n, c, k = cfg["students"], cfg["courses"], cfg["courses_per_student"]
    sid = np.repeat(np.arange(n), k)
    cid = np.concatenate([rng.choice(c, size=k, replace=False) for _ in range(n)])
    relabel = np.random.default_rng([seed, 1])
    sid = gen.tile_relabel(n, relabel)[sid]
    return {
        "Student": {"sid": np.arange(n), "name": np.arange(n)},
        "Takes": {"sid": sid, "cid": cid + 10_000},
    }


def node_of(cfg, seed):
    return gen.tile_relabel(cfg["students"], np.random.default_rng([seed, 1]))


def incidence(t):
    keys = np.unique(t["Student"]["sid"])
    node = np.searchsorted(keys, t["Takes"]["sid"])
    item = np.unique(t["Takes"]["cid"], return_inverse=True)[1]
    return keys.size, node, item
