"""Median over completed requests of the program's ``launch`` mark less
its ``due`` mark: how late the event loop, the slots and the batcher
start a rank that fell due."""

from bench.lib.program_trace import rank_wait_ms


def read(run):
    return None if run.program is None else rank_wait_ms(run.program.marks)
