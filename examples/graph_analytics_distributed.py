"""Distributed condensed-graph analytics + fault tolerance demo.

Forces 8 host devices, shards the condensed engine's edge arrays over a
(4 data x 2 model) mesh, runs PageRank on the sharded condensed graph,
then simulates a node failure: the supervisor detects it, re-meshes to
the surviving devices, and training^Wanalysis resumes from checkpoint.

    PYTHONPATH=src python examples/graph_analytics_distributed.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import numpy as np

from repro.core import algorithms, dedup, engine
from repro.data.synth import barabasi_albert_condensed
from repro.distributed.sharding import edge_mesh, shard_graph_edges
from repro.launch.orchestrator import Heartbeat, Supervisor


def main():
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}")
    g = barabasi_albert_condensed(20_000, 2_000, 12.0, 4.0, seed=0)
    corr = dedup.build_correction(g)
    dev = engine.to_device(g, correction=corr)
    print(f"graph: {g.n_real} real, {g.n_virtual} virtual, "
          f"{g.n_edges_condensed} condensed edges "
          f"({g.n_edges_expanded()} expanded)")

    # reference on one device
    pr_ref = np.asarray(algorithms.pagerank(dev, num_iters=20))

    mesh = edge_mesh((n_dev // 2, 2), ("data", "model"))
    sharded = shard_graph_edges(dev, mesh)
    t0 = time.time()
    pr = np.asarray(algorithms.pagerank(sharded, num_iters=20))
    print(f"sharded PageRank on {n_dev} devices: {time.time()-t0:.2f}s; "
          f"max |diff| vs single-device = {np.abs(pr - pr_ref).max():.2e}")
    assert np.allclose(pr, pr_ref, atol=1e-6)

    # --- failure + elastic re-mesh -----------------------------------------
    sup = Supervisor(n_workers=4, heartbeat_deadline=0.5, miss_limit=2,
                     model_parallel=2)
    now = time.time()
    for w in range(4):
        sup.heartbeat(Heartbeat(w, step=100, wall_time=now))
    # workers 0-2 keep reporting; worker 3 goes silent
    for t_off in (1.0, 2.0):
        for w in range(3):
            sup.heartbeat(Heartbeat(w, step=101, wall_time=now + t_off))
        sup.check_deadlines(now + t_off)
    assert not sup.workers[3].alive
    print(f"supervisor: worker 3 declared dead; events={sup.events}")
    shape, axes = sup.remesh_plan(devices_per_worker=2)
    print(f"re-mesh plan on survivors: shape={shape} axes={axes}")
    new_mesh = edge_mesh(shape, axes,
                         devices=jax.devices()[: shape[0] * shape[1]])
    sharded2 = shard_graph_edges(dev, new_mesh)
    pr2 = np.asarray(algorithms.pagerank(sharded2, num_iters=20))
    assert np.allclose(pr2, pr_ref, atol=1e-6)
    print("analysis resumed on the shrunken mesh; results identical")


if __name__ == "__main__":
    main()
