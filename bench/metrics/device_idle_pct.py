"""Share of the traced window in which no operation ran on the device
(profiler trace)."""


def read(o):
    if o.trace is None:
        return None
    busy = o.trace.busy_s()
    return None if busy is None else 100.0 * (1.0 - busy / o.trace.window_s)
