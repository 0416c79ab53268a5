"""99th percentile of the service's queue wait (enqueue to dispatch start,
``ServeResult.queue_wait_s``) over the requests of the window."""
import numpy as np


def read(run):
    w = run["queue_wait_s"]
    if not w:
        return None
    return float(np.percentile(np.asarray(w), 99, method="higher")) * 1e3
