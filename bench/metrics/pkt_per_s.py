"""Packets whose verdict came back inside the window, per second of it."""


def read(run):
    return run["packets_in_window"] / run["seconds"]
