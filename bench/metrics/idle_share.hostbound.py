"""idle_share in a cell whose pace the host sets, where it moves
pkt_per_s.hostbound.  A per-layer metric names the one end-to-end metric
it moves, so the same reading takes a second name, and this file."""
from bench.metrics.idle_share import read  # noqa: F401
