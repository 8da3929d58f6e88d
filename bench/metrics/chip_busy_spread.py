"""The busiest chip's device seconds in the traced window over the
mean of the cell's chips (1.0 is an even load; a chip that ran nothing
counts 0 in the mean): what pinning each user to one chip costs when a
few users send most of the traffic.  Nothing on one chip."""


def read(run):
    t = run.trace
    if t is None or t.n_devices < 2 or not t.device_busy_s:
        return None
    mean = sum(t.device_busy_s.values()) / t.n_devices
    return max(t.device_busy_s.values()) / mean if mean > 0 else None
