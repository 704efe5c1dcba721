"""device_idle_pct: share, in %, of the traced window in which no
operation ran on the device: 1 - busy / window, from the trace."""


def read(r):
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return r.trace.idle_pct
