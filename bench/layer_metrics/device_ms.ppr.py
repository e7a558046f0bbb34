"""Median device time of one ``serve_ppr`` execution in the traced window:
the length of its event on the trace's ``XLA Modules`` line, in ms.  The
serving tier names each kind's executable ``serve_<kind>``; a program
whose executables carry no such name reads nothing."""
import numpy as np

import harness
import modules


def read(run):
    if run.trace is None or run.window_ns is None:
        return None
    runs = modules.in_window(modules.of_run(harness.RESULTS / "trace", run.window_ns),
                             *run.window_ns, "jit_serve_ppr")
    if not runs:
        return None
    return float(np.median([(e.end_ns - e.start_ns) / 1e6 for e in runs]))
