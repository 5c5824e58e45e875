"""99th percentile of how late the request sender sent, on its own
clock: a sender starved of the host would otherwise read as a fast
server."""

import numpy as np


def read(run):
    return float(np.percentile(run.lag_ms, 99)) if len(run.lag_ms) else None
