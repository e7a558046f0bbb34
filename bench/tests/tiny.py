"""The test-only cell: ``tiny-classmates`` under ``tiny-mix``, with one
test-only per-layer metric, all from ``bench/tests/fixtures`` and added
to the benchmark's entries as a later change would add them."""
import copy
from pathlib import Path

import harness

TESTS = Path(__file__).resolve().parent
SEARCH = (TESTS / "fixtures", harness.BENCH)
CELL = "tiny-classmates.tiny-mix"


def benchmark() -> dict:
    bench = copy.deepcopy(harness.load_benchmark())
    bench["workloads"].append({
        "name": CELL, "config": "tiny-classmates", "traffic": "tiny-mix",
        "chips": 1, "why": "test-only",
    })
    for m in bench["per_layer"]:
        m["workloads"] = m["workloads"] + [CELL]
    bench["per_layer"].append({
        "name": "tiny_requests_answered", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "serving tier", "moves": "query_p50_ms",
        "workloads": [CELL],
    })
    return bench
