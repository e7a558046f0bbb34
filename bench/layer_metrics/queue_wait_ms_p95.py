"""95th percentile of the wait from a request's due time to the start of
the batch step that ran it (or to its admission, for a result-cache hit),
by nearest rank over the window's requests, in ms.  A request that never
ran ranks last."""
import math

import numpy as np


def read(run):
    wait = (run.window.started - run.schedule.due) * 1e3
    if wait.size == 0:
        return None
    s = np.sort(np.where(np.isnan(wait), np.inf, wait))
    return float(s[max(math.ceil(0.95 * s.size) - 1, 0)])
