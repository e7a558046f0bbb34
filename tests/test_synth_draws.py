"""The batched weighted draw behind ``dblp_catalog``: one call for all
publications, with the distribution of one ``rng.choice(..., replace=False,
p=p)`` per publication."""
import itertools

import numpy as np
import pytest

from repro.data.synth import dblp_catalog, weighted_draws_without_replacement

P5 = np.array([0.4, 0.25, 0.15, 0.12, 0.08])


def _successive_sampling_probs(p, k):
    """Exact probability of every ordered k-tuple under successive
    sampling: each pick drawn from ``p`` renormalized over the rest."""
    out = {}
    for perm in itertools.permutations(range(p.size), k):
        prob, left = 1.0, 1.0
        for i in perm:
            prob *= p[i] / left
            left -= p[i]
        out[perm] = prob
    return out


@pytest.mark.parametrize("k", [1, 2, 3])
def test_ordered_picks_follow_successive_sampling(k):
    trials = 200_000
    picks = weighted_draws_without_replacement(
        np.full(trials, k), P5, np.random.default_rng(0)
    ).reshape(trials, k)
    ordered = np.sort(picks, axis=1)
    assert (ordered[:, 1:] != ordered[:, :-1]).all(), "a tuple repeats an item"
    place = 5 ** np.arange(k)[::-1]
    counts = np.bincount(picks @ place, minlength=5**k)
    want = _successive_sampling_probs(P5, k)
    chi2 = sum(
        (counts[np.array(perm) @ place] - trials * prob) ** 2 / (trials * prob)
        for perm, prob in want.items()
    )
    dof = len(want) - 1
    # far beyond the 99.99th percentile of chi-square at these dof
    assert chi2 < dof + 10 * np.sqrt(2 * dof)


@pytest.mark.parametrize(
    "sizes",
    [[0, 5, 1, 3], [5, 5, 5], [2] * 50, [0, 0]],
    ids=["mixed", "all-items", "many-small", "empty"],
)
def test_groups_are_distinct_and_sized(sizes):
    out = weighted_draws_without_replacement(
        np.array(sizes), P5, np.random.default_rng(1)
    )
    assert out.size == sum(sizes)
    for group in np.split(out, np.cumsum(sizes)[:-1]):
        assert np.unique(group).size == group.size
        assert ((group >= 0) & (group < P5.size)).all()


def test_zero_weight_items_are_never_drawn():
    p = np.array([0.5, 0.0, 0.3, 0.0, 0.2])
    out = weighted_draws_without_replacement(
        np.full(1000, 3), p, np.random.default_rng(2)
    )
    assert set(out.tolist()) == {0, 2, 4}


def test_more_items_than_have_weight_is_refused():
    with pytest.raises(ValueError, match="more items than have weight"):
        weighted_draws_without_replacement(
            np.array([4]), np.array([0.5, 0.5, 0.0, 0.0]),
            np.random.default_rng(3),
        )


def test_dblp_catalog_rows_are_distinct_author_pub_pairs():
    cat = dblp_catalog(n_authors=500, n_pubs=2000, seed=4)
    ap = cat.table("AuthorPub")
    pairs = np.stack([ap.column("aid"), ap.column("pid")], axis=1)
    assert np.unique(pairs, axis=0).shape[0] == pairs.shape[0]
    assert np.unique(ap.column("pid")).size == 2000
