"""Device operations that start in the traced window per wave served."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops or not r.window.waves:
        return None
    return trace.op_count(r.trace) / r.window.waves
