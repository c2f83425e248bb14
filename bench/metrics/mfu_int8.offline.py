"""The model's int8 operations (2 x MACs of the configuration's layer list)
times images per second of the traced window, over the chip's int8 peak."""
from bench import model


def read(r):
    w = r.window
    if r.trace is None or not r.trace.ops or not w.completed:
        return None
    ops = model.work(r.cfg)["ops"]
    return 100.0 * ops * w.completed / w.seconds / r.peak["int8_ops_per_s"]
