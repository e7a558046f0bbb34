"""Record the small chip trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py bench/tests/data/trace_small.xplane.pb

Runs the test-only cell for two seconds with the profiler on, on the
accelerator it finds, and copies the trace file to the path given.
"""
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import tiny  # noqa: E402
import trace  # noqa: E402


def main(out: str) -> int:
    result = harness.run_cell(tiny.CELL, 20261016, 2.0, True, time.perf_counter(),
                              bench=tiny.benchmark(), search=tiny.SEARCH)
    src = trace.newest_xplane(str(harness.RESULTS / "trace" / tiny.CELL))
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    print({"device": result["device"], "correct": result["correct"],
           "bytes": Path(out).stat().st_size})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
