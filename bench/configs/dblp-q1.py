"""DBLP co-authorship (the paper's Table 1 DBLP catalog, query Q1).

The catalog's structure is the generator of ``repro.data.synth.dblp_catalog``
(copied into :mod:`gen`, so a later change to the program's generator does
not move the benchmark), except that the publication sizes keep Table 1's
rows per publication (:func:`gen.zipf_sizes`), at the sizes in
``dblp-q1.json``, drawn from the structure seed.  ``--seed`` relabels authors and publications inside their
128-id tiles.
"""
import numpy as np

import gen


def tables(cfg: dict, seed: int) -> dict:
    rng = np.random.default_rng(cfg["structure_seed"])
    n_authors, n_pubs = cfg["authors"], cfg["pubs"]
    sizes = np.minimum(
        gen.zipf_sizes(n_pubs, cfg["mean_authors_per_pub"], rng), n_authors
    )
    pub_ids = np.repeat(np.arange(n_pubs), sizes)
    w = 1.0 / np.arange(1, n_authors + 1) ** 0.8
    w /= w.sum()
    author_ids = gen.weighted_draws_without_replacement(sizes, w, rng)
    years = rng.integers(1990, 2024, size=n_pubs)
    relabel = np.random.default_rng([seed, 1])
    author_ids = gen.tile_relabel(n_authors, relabel)[author_ids]
    pub_ids = gen.tile_relabel(n_pubs, relabel)[pub_ids]
    return {
        "Author": {
            "aid": np.arange(n_authors),
            "name": np.array([f"author_{i}" for i in range(n_authors)]),
        },
        "Pub": {"pid": np.arange(n_pubs) + 1_000_000, "year": years},
        "AuthorPub": {"aid": author_ids, "pid": pub_ids + 1_000_000},
    }


def node_of(cfg: dict, seed: int) -> np.ndarray:
    """The served node of each author of the structure: ``tables``' first
    relabelling draw."""
    return gen.tile_relabel(cfg["authors"], np.random.default_rng([seed, 1]))


def incidence(t: dict):
    """Q1 from the rows: node ``u`` (the rank of its Author key) meets
    item ``p`` (a publication) once per AuthorPub row."""
    keys = np.unique(t["Author"]["aid"])
    node = np.searchsorted(keys, t["AuthorPub"]["aid"])
    if not np.array_equal(keys[node], t["AuthorPub"]["aid"]):
        raise ValueError("an AuthorPub row names an unknown author")
    item = np.unique(t["AuthorPub"]["pid"], return_inverse=True)[1]
    return keys.size, node, item
