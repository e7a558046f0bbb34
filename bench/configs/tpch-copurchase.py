"""TPC-H co-purchase (query ``Q_TPCH`` of ``benchmarks/bench_extraction.py``):
two customers are neighbours when an order of each holds the same part,
read over Customer -> Orders -> LineItem -> Orders -> Customer.

The rows keep the shapes of the TPC-H specification's clause 4.2 (dbgen)
at the scale factor in ``tpch-copurchase.json``: CUSTOMER 150,000 x SF,
ORDERS 1,500,000 x SF with ``o_custkey`` uniform over the customer keys
that are not multiples of 3 (a third of the customers never order),
LINEITEM 1 to 7 lines per order, uniform, and ``l_partkey`` uniform over
the 200,000 x SF parts, a part at most once per order.  The structure is
drawn from the structure seed; ``--seed`` relabels customers, orders and
parts inside their 128-id tiles, customers that never order staying where
they are.
"""
import numpy as np

import gen

ORDER_KEY0 = 10_000_000
PART_KEY0 = 20_000_000


def sizes(cfg: dict) -> tuple:
    """``(customers, orders, parts)`` at the configuration's scale."""
    spec, sf = cfg["clause_4_2"], cfg["scale"]
    return tuple(int(round(spec[k] * sf))
                 for k in ("customers_per_sf", "orders_per_sf", "parts_per_sf"))


def _silent(cfg: dict, n_customers: int) -> np.ndarray:
    """Customers that never order: keys that are multiples of 3."""
    return np.arange(n_customers) % cfg["clause_4_2"]["customer_not_ordering_mod"] == 0


def tables(cfg: dict, seed: int) -> dict:
    rng = np.random.default_rng(cfg["structure_seed"])
    n_cust, n_orders, n_parts = sizes(cfg)
    ordering = np.flatnonzero(~_silent(cfg, n_cust))
    cust_of_order = ordering[rng.integers(0, ordering.size, size=n_orders)]
    lo, hi = cfg["clause_4_2"]["lines_per_order"]
    lines = rng.integers(lo, hi + 1, size=n_orders)
    order_of_line = np.repeat(np.arange(n_orders), lines)
    part_of_line = gen.weighted_draws_without_replacement(
        lines, np.full(n_parts, 1.0 / n_parts), rng)
    relabel = np.random.default_rng([seed, 1])
    cust = gen.tile_relabel(n_cust, relabel, fixed=_silent(cfg, n_cust))
    order = gen.tile_relabel(n_orders, relabel)
    part = gen.tile_relabel(n_parts, relabel)
    return {
        "Customer": {
            "ckey": np.arange(n_cust),
            "name": np.array([f"customer_{i}" for i in range(n_cust)]),
        },
        "Orders": {"okey": order + ORDER_KEY0, "ckey": cust[cust_of_order]},
        "LineItem": {"okey": order[order_of_line] + ORDER_KEY0,
                     "pkey": part[part_of_line] + PART_KEY0},
        "Part": {"pkey": np.arange(n_parts) + PART_KEY0},
    }


def node_of(cfg: dict, seed: int) -> np.ndarray:
    """The served node of each customer of the structure: ``tables``'
    first relabelling draw."""
    n_cust = sizes(cfg)[0]
    return gen.tile_relabel(n_cust, np.random.default_rng([seed, 1]),
                            fixed=_silent(cfg, n_cust))


def incidence(t: dict):
    """``Q_TPCH`` from the rows: customer ``u`` (the rank of its key) meets
    item ``p`` (a part) once per lineitem of one of its orders."""
    keys = np.unique(t["Customer"]["ckey"])
    okey, ocust = t["Orders"]["okey"], t["Orders"]["ckey"]
    by_key = np.argsort(okey)
    at = np.searchsorted(okey, t["LineItem"]["okey"], sorter=by_key)
    at = by_key[np.minimum(at, okey.size - 1)]
    if not np.array_equal(okey[at], t["LineItem"]["okey"]):
        raise ValueError("a LineItem row names an unknown order")
    node = np.searchsorted(keys, ocust[at])
    if not np.array_equal(keys[np.minimum(node, keys.size - 1)], ocust[at]):
        raise ValueError("an order names an unknown customer")
    item = np.unique(t["LineItem"]["pkey"], return_inverse=True)[1]
    return keys.size, node, item
