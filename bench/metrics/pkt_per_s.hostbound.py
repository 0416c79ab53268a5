"""pkt_per_s in a cell whose pace the host sets.  Such a cell's runs spread
wider than a device-bound cell's, so its rate takes a name, and a bound,
of its own."""
from bench.metrics.pkt_per_s import read  # noqa: F401
