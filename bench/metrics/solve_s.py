"""Seconds per solve: the whole window over the solves started and
finished in it (host clock; each solve ends with its beliefs ready)."""


def read(o):
    return o.window_s / len(o.solves) if o.solves else None
