"""Share of the prefix tokens the window's rank launches computed that
were padding (bucket and batch rows), from the executors' counters."""

from bench.lib.program_trace import rank_pad_share


def read(run):
    if run.program is None:
        return None
    return rank_pad_share(run.program.counters)
