"""Process start to the first timed request: imports, weights, compilation
(or the compile cache), and the warm-up traffic."""


def read(run):
    return run["setup_s"]
