"""Device time in XLA operations other than the Pallas kernels
(tpu_custom_call) over device busy time, in the traced window."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops:
        return None
    return 100.0 * trace.glue_share(r.trace)
