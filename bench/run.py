"""Run one benchmark cell on the accelerator and print its result line.

    python bench/run.py --workload dblp-q1.ppr-uniform --seed 7 --seconds 30 --trace 0

Builds the cell's catalog from ``--seed``, extracts and serves it through
``GraphServingTier``, offers the cell's open-loop traffic for
``--seconds``, checks the answers against a host reference, and prints
one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit,
which also close standard error.  Earlier lines of standard output are
one JSON object per phase.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.find(harness.load_benchmark()["workloads"], args.workload, "workload")
    # the TPU runtime's logs go inside the checkout, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", str(harness.RESULTS / "tpu_logs"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        sys.exit(
            f"bench: cell {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} {devices[0].platform} device(s)"
        )
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              log=lambda s: print(s, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
