"""Kept rows over dispatched bucket rows in the window: the pipeline's
packet counter against the service's padding counter."""


def read(run):
    kept = run["pipeline"]["packets"]
    rows = kept + run["service"]["padded"]
    return 100.0 * kept / rows if rows else None
