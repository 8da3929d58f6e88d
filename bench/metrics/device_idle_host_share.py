"""Share of the traced window in which the device was idle while the
host was not waiting for an arrival: idle time that the host path
causes."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_host_s / run.trace.window_s
