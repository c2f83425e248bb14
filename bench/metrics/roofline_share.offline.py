"""The least time the chip could take for the images of the traced window
(per layer of the configuration, the larger of ops over the int8 peak and
int8 bytes over HBM bandwidth, weights read once a wave) over the device's
busy time in that window."""
from bench import model, trace


def read(r):
    w = r.window
    if r.trace is None or not r.trace.ops or not w.completed:
        return None
    least = model.roofline_s_per_image(
        r.cfg, r.mix["wave_size"], r.peak["int8_ops_per_s"],
        r.peak["hbm_bytes_per_s"]) * w.completed
    return 100.0 * least / trace.busy_s(r.trace)
