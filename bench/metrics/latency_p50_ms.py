"""Median latency over all requests of the window, submit to logits on the
host."""
import numpy as np


def read(r):
    lat = r.window.latency_s
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
