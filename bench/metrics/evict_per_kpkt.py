"""Flows evicted from the hot table by a colliding tuple, per 1000 packets
in the window (pipeline counter, summed on the device)."""


def read(run):
    p = run["pipeline"]
    return 1e3 * p["evicted"] / p["packets"] if p["packets"] else None
