"""95th percentile latency of the window's completed requests, from when
each was due to its last token (host clock)."""

import numpy as np


def read(run):
    lat = run["record"].get("latency_s")
    if run["traffic"]["driver"] != "serve" or lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 95) * 1e3)
