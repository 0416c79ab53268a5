"""Records spilled to and promoted from the cold tier per 1000 packets in
the window (pipeline counters, summed on the device)."""


def read(run):
    p = run["pipeline"]
    if not run["config"]["cold_size"] or not p["packets"]:
        return None
    return 1e3 * (p["spilled"] + p["promoted"]) / p["packets"]
