"""Share of the traced window in which the device was idle, the host
was not waiting for an arrival and was in no span of the program: idle
time that no layer's name accounts for."""

from bench.lib.program_trace import idle_unattributed_share


def read(run):
    p = run.program
    if p is None or p.trace is None:
        return None
    return idle_unattributed_share(p.trace, p.profiled, p.n_devices)
