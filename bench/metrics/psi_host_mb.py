"""Megabytes of psi that crossed between host and device or were
copied on the host, per psi staged into the window: the page pools'
pulls, host page-buffer writes, landings put from the host and, on a
host pool, its dense copies (``program_trace.psi_host_bytes``).  A
landing from psi already on the device counts nothing."""

from bench.lib.program_trace import psi_host_mb


def read(run):
    if run.program is None:
        return None
    staged = sum(s.name == "window.stage" for s in run.program.spans)
    return psi_host_mb(run.program.h2d, staged)
