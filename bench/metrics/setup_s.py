"""Set-up: process start to the first timed request (weights, image pool,
calibration, compilation and warm-up)."""


def read(r):
    return r.setup_s
