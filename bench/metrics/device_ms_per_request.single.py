"""Device busy time in the traced window per request served."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops or not r.window.completed:
        return None
    return trace.busy_s(r.trace) * 1e3 / r.window.completed
