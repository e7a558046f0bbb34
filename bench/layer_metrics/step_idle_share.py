"""Share of the batch steps' host time in which the device ran nothing:
1 - device-busy time inside the ``bench.step`` spans / their length, from
the trace, in %.  It is the host work inside a batch (dispatch, argument
handling, the answer's copy back) that holds the device idle."""


def read(run):
    steps = [s for s in run.window.steps if s.busy_s is not None]
    span = sum(s.t1 - s.t0 for s in steps)
    if not steps or span <= 0:
        return None
    return 100.0 * (1.0 - sum(s.busy_s for s in steps) / span)
