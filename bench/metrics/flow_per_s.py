"""Flows classified inside the window (the pipeline's flow counter), per
second of it."""


def read(run):
    return run["flows_in_window"] / run["seconds"]
