"""Share of the chip's roofline that the rank launches reach: their
least possible time (the work counts of bench/models/<arch>.py) over
their device time."""

from bench.lib.readings import roofline_pct


def read(run):
    return roofline_pct(run, "rank")
