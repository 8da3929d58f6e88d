"""Device milliseconds per side-path prefill launch in the traced
window."""

from bench.lib.readings import device_ms_per_launch


def read(run):
    return device_ms_per_launch(run, "prefill")
