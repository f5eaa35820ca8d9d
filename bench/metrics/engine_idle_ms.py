"""Device-idle milliseconds per traced solve spent inside ``BPEngine.run``:
the first device's idle time in the traced window (profiler trace) that a
host ``bp.run`` span of the program covers, over the solves traced. The
engine's own host overhead, apart from the client's between solves. Also
prints its split by the innermost ``bp.*`` span (``engine idle:``)."""

from bench import scopes


def read(o):
    if o.trace is None or not o.traced_solves:
        return None
    idle = scopes.idle_by_span(o.trace)
    if idle is None:
        return None
    print("engine idle: " + scopes.format_ms(idle, o.traced_solves, "solve"),
          flush=True)
    inside = sum(v for k, v in idle.items() if k != scopes.OUTSIDE)
    return inside * 1e-6 / o.traced_solves
