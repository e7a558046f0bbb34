"""The control of the PPR comparison: the reference in bfloat16.

    python bench/control.py --workload dblp-q1.ppr-uniform --seconds 30 --seeds 11 12 13

The served PPR is float32.  The control puts the reference in the
program's place one precision lower: the same power iteration over the
same factored simple graph, ``A x = P (P^T x) - S x``, with every array
and every sum in bfloat16, run with JAX on the local device.  For each
seed it draws the cell's schedule as a run does, answers the same checked
PPR requests, and passes those answers through the run's own comparison
(``harness.check``) in the window's place.  It prints, per seed, each
compared number beside its limit and ``correct``, which has to come out
false.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import harness
import reference
import traffic


def ppr_lowered(ref: reference.Reference, nodes, damping: float, iters: int,
                dtype) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    p = ref.paths.tocoo()
    s, deg = ref.surplus()
    s = s.tocoo()
    n, n_items = ref.n, ref.n_items
    pr, pc = jnp.asarray(p.row, jnp.int32), jnp.asarray(p.col, jnp.int32)
    pv = jnp.asarray(p.data, dtype)[:, None]
    sr, sc = jnp.asarray(s.row, jnp.int32), jnp.asarray(s.col, jnp.int32)
    sv = jnp.asarray(s.data, dtype)[:, None]
    degb = jnp.asarray(deg, dtype)[:, None]
    seeds = jnp.zeros((n, len(nodes)), dtype).at[
        jnp.asarray(nodes), jnp.arange(len(nodes))].set(1)
    d = jnp.asarray(damping, dtype)

    def body(_, x):
        contrib = jnp.where(degb > 0, x / jnp.maximum(degb, 1), 0).astype(dtype)
        t = jax.ops.segment_sum(pv * contrib[pr], pc, num_segments=n_items)
        y = jax.ops.segment_sum(pv * t[pc], pr, num_segments=n)
        y = y - jax.ops.segment_sum(sv * contrib[sc], sr, num_segments=n)
        dangling = jnp.sum(jnp.where(degb > 0, 0, x), axis=0).astype(dtype)
        return ((1 - d) * seeds + d * (y + dangling * seeds)).astype(dtype)

    x = jax.jit(lambda s0: jax.lax.fori_loop(0, iters, body, s0))(seeds)
    return np.asarray(x.astype(jnp.float32), dtype=np.float64)


def control_run(cfg, module, mix, seed: int, seconds: float, damping: float,
                iters: int, dtype) -> dict:
    """One seed of the control: its answers judged as a run's are."""
    tables = module.tables(cfg, seed)
    ref = reference.Reference(*module.incidence(tables))
    sched = traffic.schedule(mix, module.node_of(cfg, seed), seed, seconds)
    q = [int(i) for i in sched.checked if sched.kinds[i] == "ppr"]
    got = ppr_lowered(ref, sched.nodes[q], damping, iters, dtype)
    # every request answered, the checked ones by the control
    answered = np.zeros(sched.due.size)
    win = harness.Window(done=answered, started=answered,
                         values={i: got[:, j] for j, i in enumerate(q)},
                         steps=[], max_lag_s=0.0, closed_s=0.0)
    checks = harness.check(ref, sched, win, {"damping": damping, "ppr_iters": iters},
                           cfg["limits"])
    return {"seed": seed, "answers": len(q), "correct": harness.passed(checks),
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    cell = harness.find(harness.load_benchmark()["workloads"], args.workload, "workload")
    cfg, module = harness.load_config(cell["config"])
    mix = harness.load_mix(cell["traffic"])
    from repro.serve.tier import GraphServingTier

    tier = GraphServingTier()   # the served damping and iterations
    dev = jax.devices()[0]
    for seed in args.seeds:
        out = control_run(cfg, module, mix, seed, args.seconds, tier.damping,
                          tier.ppr_iters, jnp.bfloat16)
        out.update(platform=dev.platform, device_kind=dev.device_kind)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
