"""Serving launcher: batched LM generation, or the multi-tenant graph tier.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --requests 6
    PYTHONPATH=src python -m repro.launch.serve --graphs 3 --requests 64

``--graphs N`` serves N extracted graphs from one
:class:`~repro.serve.tier.GraphServingTier` under a device-byte budget,
prints the replica placement plan
(:func:`~repro.launch.cells.place_serving_replicas`) for the local device
count, runs a mixed bfs/ppr/common-neighbors workload, and reports batch
occupancy plus cache hit rates.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..configs import registry
from ..models import transformer
from ..serve.server import BatchedServer, Request
from .compile_cache import enable_compile_cache


def _serve_lm(args) -> int:
    cfg = registry.get_arch(args.arch).SMOKE
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    server = BatchedServer(params, cfg, batch_slots=args.slots, max_len=64)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12)),
            max_new_tokens=args.new_tokens,
        )
        for i in range(args.requests)
    ]
    out = server.run(reqs)
    for rid in sorted(out):
        print(f"request {rid}: {out[rid]}")
    assert len(out) == args.requests
    print("served", len(out), "requests")
    return 0


def _serve_graphs(args) -> int:
    from ..core.dedup import graph_from_membership
    from ..core.engine import ResidencyBudget, device_graph_bytes, to_device
    from ..serve.tier import GraphServingTier, ServeRequest, KINDS
    from .cells import place_serving_replicas

    rng = np.random.default_rng(args.seed)
    tenants = {}
    for g in range(args.graphs):
        n_real, n_virt = 60 + 10 * g, 18 + 2 * g
        sets = [
            rng.choice(n_real, size=rng.integers(2, 6), replace=False)
            for _ in range(n_virt)
        ]
        tenants[f"graph{g}"] = graph_from_membership(n_real, sets)

    # budget: fit roughly two of the tenants at a time
    per_tenant = [
        2 * device_graph_bytes(to_device(g)) for g in tenants.values()
    ]
    budget = ResidencyBudget(
        max_device_bytes=int(sum(sorted(per_tenant)[-2:]) * 1.25)
    )
    tier = GraphServingTier(max_batch=args.slots, budget=budget)
    for name, g in tenants.items():
        tier.add_tenant(name, g)

    placements = place_serving_replicas(
        sorted(tenants), n_devices=max(jax.device_count(), 1),
        replicas=min(args.replicas, max(jax.device_count(), 1)),
    )
    for p in placements:
        print(f"placement: {p.tenant} replica {p.replica} -> devices {p.devices}")

    names = sorted(tenants)
    reqs = [
        ServeRequest(
            qid=i,
            tenant=names[int(rng.integers(len(names)))],
            kind=KINDS[int(rng.integers(len(KINDS)))],
            node=int(rng.integers(40)),
        )
        for i in range(args.requests)
    ]
    out = tier.serve(reqs)
    assert len(out) == args.requests
    print(
        f"served {len(out)} requests over {len(tenants)} tenants: "
        f"occupancy={tier.stats.occupancy:.2f} "
        f"result_cache_hit_rate={tier.result_stats.hit_rate:.2f} "
        f"exec_cache_hit_rate={tier.exec_stats.hit_rate:.2f} "
        f"resident={budget.resident_bytes}B/"
        f"{budget.max_device_bytes}B evictions={budget.n_evictions}"
    )
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--graphs", type=int, default=0,
                    help="serve N graph tenants from one tier instead of the LM")
    ap.add_argument("--replicas", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.graphs > 0:
        return _serve_graphs(args)
    return _serve_lm(args)


if __name__ == "__main__":
    raise SystemExit(main())
