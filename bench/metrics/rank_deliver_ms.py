"""Median over completed requests of the program's ``sink`` mark less
its ``launched`` mark: completion ordering, and a DRAM spill's copy,
between a rank's return and its scores reaching the sink."""

from bench.lib.program_trace import rank_deliver_ms


def read(run):
    if run.program is None:
        return None
    return rank_deliver_ms(run.program.marks)
