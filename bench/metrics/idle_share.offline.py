"""Share of the traced window in which no operation ran on the device."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops:
        return None
    return 100.0 * trace.idle_share(r.trace)
