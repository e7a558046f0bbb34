"""Median host time of a ``ppr`` batch step in the window (bench span
around ``GraphServingTier.step``, which returns with the answers on the
host), in ms."""
import numpy as np


def read(run):
    ms = [(s.t1 - s.t0) * 1e3 for s in run.window.steps if s.kind == "ppr"]
    return float(np.median(ms)) if ms else None
