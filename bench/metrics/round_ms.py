"""Device-busy milliseconds per round in the traced window: the union of
device op intervals (profiler trace) over the rounds of the solves traced."""


def read(o):
    if o.trace is None or not o.traced_rounds:
        return None
    busy = o.trace.busy_s()
    return None if busy is None else 1e3 * busy / o.traced_rounds
