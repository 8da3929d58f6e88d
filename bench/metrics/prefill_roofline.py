"""Share of the chip's roofline that the prefill launches reach."""

from bench.lib.readings import roofline_pct


def read(run):
    return roofline_pct(run, "prefill")
