"""Sweep a cell's offered rate once, to find its knee.

    python bench/sweep.py --workload dblp-q1.ppr-uniform --seed 5 --seconds 20 --rates 4 8 12

Builds and warms the cell once, then drives one open-loop window per
rate, with the cell's mix at that rate, and prints one JSON line per
rate: requests, answers, the last answer's time past the close, the
median latency of the first and last third of the window's requests, and
whether the backlog held.  The last line gives the knee, the highest rate
below the first whose backlog grew, and 0.8 of it.  Needs the
accelerator, as the benchmark does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import harness
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.tier import GraphServingTier

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: needs a TPU")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.find(harness.load_benchmark()["workloads"], args.workload, "workload")
    cfg, module = harness.load_config(cell["config"])
    mix = harness.load_mix(cell["traffic"])
    spans = harness.Spans()
    t = time.perf_counter()
    built = harness.build(cfg, module, args.seed, spans)
    tier = GraphServingTier()
    tier.add_tenant(cfg["name"], built.graph, correction=built.correction, packed=True)
    harness.warm(tier, cfg["name"], mix["kinds"], built.shape["n_real"], spans)
    print(json.dumps({"phase": "setup", "seconds": time.perf_counter() - t,
                      "spans": spans.seconds, "shape": built.shape,
                      "device_graph_bytes": tier.budget.resident_bytes}), flush=True)
    knee, broke = None, False
    for rate in sorted(args.rates):
        sched = traffic.schedule(dict(mix, rate_qps=rate), module.node_of(cfg, args.seed),
                                 args.seed, args.seconds)
        win = harness.drive(tier, cfg["name"], sched, args.seconds, harness.Spans(),
                            drain_limit=10.0)
        lat = (win.done - sched.due) * 1e3
        third = max(lat.size // 3, 1)
        widths = [s.fill for s in win.steps]
        step_s = [s.t1 - s.t0 for s in win.steps]
        row = {
            "rate_qps": rate, "requests": int(lat.size),
            "answered": int(np.isfinite(lat).sum()),
            "past_close_s": win.closed_s - args.seconds,
            "p50_first_third_ms": harness.nearest_rank(lat[:third], 0.5),
            "p50_last_third_ms": harness.nearest_rank(lat[-third:], 0.5),
            "p50_ms": harness.nearest_rank(lat, 0.5),
            "p95_ms": harness.nearest_rank(lat, 0.95),
            "steps": len(win.steps),
            "mean_fill": float(np.mean(widths)) if widths else 0.0,
            "step_ms_max": 1e3 * max(step_s, default=0.0),
        }
        # the backlog held: every answer came, the queue emptied within two
        # of the longest batches of the close, and the last third of the
        # window waited no longer than the first (with a quarter of room)
        row["held"] = bool(
            row["answered"] == row["requests"]
            and row["past_close_s"] <= 2 * max(step_s, default=0.0) + 0.5
            and row["p50_last_third_ms"] <= 1.25 * row["p50_first_third_ms"] + 250
        )
        # the knee: the highest rate below the first one that did not hold
        if not row["held"]:
            broke = True
        elif not broke:
            knee = rate
        print(json.dumps(row), flush=True)
        tier.invalidate_results()
    print(json.dumps({"knee_qps": knee,
                      "rate_qps": round(0.8 * knee, 1) if knee else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
