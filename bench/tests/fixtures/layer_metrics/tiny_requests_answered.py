"""Test-only per-layer metric: requests answered in the window."""
import numpy as np


def read(run):
    return float(np.isfinite(run.window.done).sum())
