"""Images completed over the whole window, on the host clock."""


def read(r):
    w = r.window
    return w.completed / w.seconds if w.completed else None
