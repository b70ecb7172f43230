"""Share of the traced window, in percent, in which no operation ran on the
device (1 - union of device-op intervals / window), in closed-loop cells."""


def read(r):
    if r.trace is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s() / r.window_s)
