"""Record the small chip trace that ``test_modules.py`` reads, with the
program's recorder on, so its host spans are in the trace.

    python bench/tests/record_program_trace.py bench/tests/data/trace_program.xplane.pb

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
    from repro import obs

    obs.enable()
    result = harness.run_cell(tiny.CELL, 20261017, 2.0, True, time.perf_counter(),
                              bench=tiny.benchmark(), search=tiny.SEARCH)
    src = trace.newest_xplane(str(harness.RESULTS / "trace" / tiny.CELL))
    out = Path(out)
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out)
    print({"device": result["device"], "correct": result["correct"],
           "metrics": result["metrics"], "bytes": out.stat().st_size})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
