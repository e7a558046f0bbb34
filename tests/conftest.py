import os
import sys
import types

# Allow `pytest tests/` without PYTHONPATH=src (docs still recommend it).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Optional-dependency shim: `hypothesis` is not installable in the offline
# container.  When absent, install a stub into sys.modules *before* test
# modules import it, so each module still collects; property-based tests
# (anything decorated with the stub `@given`) skip at runtime while the
# plain tests in the same module run normally.
# ---------------------------------------------------------------------------

try:  # pragma: no cover - trivial branch
    import hypothesis

    # Fixed-seed profile for the check.sh --tier2-oracle gate: derandomized
    # example generation, so a red run reproduces locally with the same
    # command (select with HYPOTHESIS_PROFILE=oracle-ci).
    hypothesis.settings.register_profile(
        "oracle-ci", hypothesis.settings(derandomize=True, deadline=None)
    )
    if os.environ.get("HYPOTHESIS_PROFILE"):
        hypothesis.settings.load_profile(os.environ["HYPOTHESIS_PROFILE"])
except ModuleNotFoundError:

    class _Anything:
        """Stands in for strategy objects; inert under any fluent call."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    def _given(*strategy_args, **strategy_kwargs):
        """Replace the test with a skipper whose signature drops the
        strategy-filled arguments (so ``@pytest.mark.parametrize`` stacked
        outside ``@given`` keeps working).  Positional strategies fill the
        *rightmost* parameters (hypothesis semantics), keyword strategies
        fill by name."""

        def deco(fn):
            import functools
            import inspect

            @functools.wraps(fn)
            def skipped(*a, **k):
                pytest.skip("hypothesis not installed")

            sig = inspect.signature(fn)
            params = list(sig.parameters.values())
            if strategy_args:
                params = params[: len(params) - len(strategy_args)]
            kept = [p for p in params if p.name not in strategy_kwargs]
            skipped.__signature__ = sig.replace(parameters=kept)
            return skipped

        return deco

    def _identity_decorator(*_args, **_kwargs):
        def deco(fn):
            return fn

        return deco

    _stub = types.ModuleType("hypothesis")
    _stub.given = _given
    _stub.settings = _identity_decorator
    _stub.assume = lambda *a, **k: True
    _stub.note = lambda *a, **k: None
    _stub.HealthCheck = _Anything()
    _strategies = types.ModuleType("hypothesis.strategies")

    def _strategies_getattr(name):
        return _Anything()

    _strategies.__getattr__ = _strategies_getattr
    _stub.strategies = _strategies
    sys.modules["hypothesis"] = _stub
    sys.modules["hypothesis.strategies"] = _strategies

from repro.core.condensed import BipartiteEdges, Chain, CondensedGraph
from repro.core.dedup import graph_from_membership


def random_membership_graph(n_real, n_virt, avg_size, rng):
    """Random symmetric single-layer condensed graph (membership sets)."""
    sets = []
    for _ in range(n_virt):
        k = max(2, int(rng.poisson(avg_size)))
        sets.append(
            set(rng.choice(n_real, size=min(k, n_real), replace=False).tolist())
        )
    return graph_from_membership(n_real, sets)


def random_bipartite(n_src, n_dst, n_edges, rng, unique=True):
    total = n_src * n_dst
    n_edges = min(n_edges, total)
    if unique:
        key = rng.choice(total, size=n_edges, replace=False)
    else:
        key = rng.integers(0, total, size=n_edges)
    return BipartiteEdges(key % n_src, key // n_src, n_src, n_dst)


Q_COPURCHASE = """
Nodes(ID, Name) :- Customer(ID, Name).
Edges(ID1, ID2) :- Orders(ok1, ID1), LineItem(ok1, pk),
                   Orders(ok2, ID2), LineItem(ok2, pk).
"""


def copurchase_graph(n_customers, n_orders, n_parts, rng):
    """A TPC-H-shaped co-purchase graph: each order belongs to a customer
    whose key is not a multiple of 3, holds 1-7 distinct parts, and two
    customers meet through a part of an order of each.  A chain of three
    virtual layers (orders, parts, orders), condensed by ``extract``."""
    from repro.core.extract import extract
    from repro.core.relational import Catalog, Table

    ordering = np.flatnonzero(np.arange(n_customers) % 3 != 0)
    lines = rng.integers(1, 8, size=n_orders)
    parts = [rng.choice(n_parts, size=k, replace=False) for k in lines]
    catalog = Catalog([
        Table("Customer", {"ckey": np.arange(n_customers),
                           "name": np.arange(n_customers).astype(str)}),
        Table("Orders", {"okey": np.arange(n_orders) + 1000,
                         "ckey": rng.choice(ordering, size=n_orders)}),
        Table("LineItem", {"okey": np.repeat(np.arange(n_orders), lines) + 1000,
                           "pkey": np.concatenate(parts) + 5000}),
    ])
    return extract(catalog, Q_COPURCHASE, mode="condensed").graph


def random_multilayer_graph(n_real, layer_sizes, density, rng):
    levels = [n_real] + list(layer_sizes) + [n_real]
    edges = []
    for a, b in zip(levels, levels[1:]):
        n_e = max(2, int(a * b * density))
        edges.append(random_bipartite(a, b, n_e, rng))
    return CondensedGraph(n_real, [Chain(edges)])


def expanded_simple_pairs(g):
    s, d, m = g.multiplicities()
    off = s != d
    return set(zip(s[off].tolist(), d[off].tolist()))


@pytest.fixture
def rng():
    return np.random.default_rng(0)
