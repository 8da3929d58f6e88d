"""Host milliseconds in the program's psi spans (``window.stage``,
``window.scatter``, ``window.materialize``, ``dram.spill``) per psi
staged into the window."""

from bench.lib.program_trace import psi_host_ms


def read(run):
    return None if run.program is None else psi_host_ms(run.program.spans)
