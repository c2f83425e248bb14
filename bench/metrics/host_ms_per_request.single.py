"""Per request, the part of its round (submit to logits on the host) in
which no operation ran on the device: the host's share of the latency, on
the trace's clock."""
from bench import trace


def read(r):
    if r.trace is None or not r.trace.ops or not r.window.completed:
        return None
    idle = trace.idle_in_spans(r.trace, "round")
    if not idle:
        return None
    return sum(idle) * 1e-6 / r.window.completed
