"""Median latency of every request the window sent, from its scheduled
send to its answer (an unanswered request counts as never answered)."""

import numpy as np


def read(run):
    lat = np.nan_to_num(run.latency_ms, nan=np.inf)
    v = float(np.percentile(lat, 50)) if len(lat) else None
    return v if v is not None and np.isfinite(v) else None
