"""99th percentile, over all requests due in the window, of the time from
when a request was due to when its verdict arrived; an unanswered request
counts as waiting until the run gave up on it."""
import numpy as np


def read(run):
    lat = run["latency_s"]
    if not lat:
        return None
    return float(np.percentile(np.asarray(lat), 99, method="higher")) * 1e3
