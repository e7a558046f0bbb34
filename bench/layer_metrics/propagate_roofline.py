"""Roofline share of PPR propagation, in %: the least time the chip needs
for the window's PPR batches (bytes from the graph's shape numbers,
:mod:`roofline`, over the device's HBM bandwidth) over the device-busy
time inside their ``bench.step`` spans.  The tier runs every kind as one
jitted function, so device time is told apart by the host span it falls
in: ``step`` blocks until the answers are on the host."""
import roofline


def read(run):
    steps = [s for s in run.window.steps if s.kind == "ppr" and s.busy_s]
    if not steps or run.peak is None:
        return None
    least = sum(
        roofline.ppr_least_seconds(run.shape, s.width, run.tier["ppr_iters"], run.peak)
        for s in steps
    )
    return 100.0 * least / sum(s.busy_s for s in steps)
