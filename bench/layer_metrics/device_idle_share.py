"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / the window, in %."""


def read(run):
    if run.trace is None or run.window_ns is None or not run.trace.devices:
        return None
    lo, hi = run.window_ns
    return 100.0 * (1.0 - run.trace.busy_ns(lo, hi) / (hi - lo))
