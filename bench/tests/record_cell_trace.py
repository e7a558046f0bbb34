"""Record a small chip trace of one benchmark cell, with the program's
recorder on, and beside it what a reader needs of the run that made it.

    python bench/tests/record_cell_trace.py tpch-copurchase.ppr-uniform \
        bench/tests/data/trace_tpch_copurchase.xplane.pb

Runs the cell for two seconds with the profiler on, on the accelerator it
finds, copies the trace file to the path given, and writes
``<path less .xplane.pb>.run.json``: the graph's shape numbers, the
tier's settings and each batch step's kind and width.
"""
import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import trace  # noqa: E402

SEED = 20261018


def _keeping(fn, kept: list):
    def wrapper(*args, **kwargs):
        kept.append(fn(*args, **kwargs))
        return kept[-1]
    return wrapper


def main(workload: str, out: str) -> int:
    from repro import obs
    from repro.serve.tier import GraphServingTier

    obs.enable()
    built, windows = [], []
    harness.build = _keeping(harness.build, built)
    harness.drive = _keeping(harness.drive, windows)
    result = harness.run_cell(workload, SEED, 2.0, True, time.perf_counter(), log=print)
    tier = GraphServingTier()
    src = trace.newest_xplane(str(harness.RESULTS / "trace" / workload))
    out = Path(out)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    side = out.with_name(out.name.replace(".xplane.pb", ".run.json"))
    side.write_text(json.dumps({
        "workload": workload, "seed": SEED, "shape": built[0].shape,
        "tier": {"ppr_iters": tier.ppr_iters, "damping": tier.damping},
        "steps": [[s.kind, s.width] for s in windows[0].steps],
    }, indent=1) + "\n")
    print({"device": result["device"], "correct": result["correct"],
           "metrics": result["metrics"], "bytes": out.stat().st_size})
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
