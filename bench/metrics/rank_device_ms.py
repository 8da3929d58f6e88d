"""Device milliseconds per rank launch (cached and full) in the traced
window."""

from bench.lib.readings import device_ms_per_launch


def read(run):
    return device_ms_per_launch(run, "rank")
