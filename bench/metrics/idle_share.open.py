"""idle_share in the open-loop cell, where it moves the latency tail.  A
per-layer metric names the one end-to-end metric it moves, so the same
reading takes a second name, and this file, for the open-loop cell."""
from bench.metrics.idle_share import read  # noqa: F401
