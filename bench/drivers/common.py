"""What the drivers share: the outcome record, the tracer, the compile
counter and the device memory reading."""

from __future__ import annotations

import dataclasses
import importlib
import os
import tempfile
from typing import List, Tuple

from bench import trace as trace_mod


@dataclasses.dataclass
class Outcome:
    """Everything a run measured; the metric readers take their numbers
    from it. Drivers fill the fields their loop has."""

    setup_s: float
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    #: programs compiled or loaded in set-up, of them loaded from the
    #: persistent cache, and compiled anew
    setup_programs: Tuple[int, int, int] = (0, 0, 0)
    memory_peak_bytes: int = 0
    #: (name, value, "<=" or ">=", limit) of each number compared for
    #: ``correct``
    checks: List[Tuple[str, float, str, float]] = dataclasses.field(
        default_factory=list)
    trace: trace_mod.Trace | None = None
    #: (operations, bytes) of the algorithm's updates in the traced window
    traced_work: Tuple[float, float] | None = None
    peaks: dict | None = None
    solves: List[dict] = dataclasses.field(default_factory=list)
    traced_solves: int = 0
    traced_rounds: int = 0
    n_real_edges: int = 0


def family(cfg: dict):
    """The graph-family generator module the configuration names."""
    return importlib.import_module(f"bench.traffic.families.{cfg['family']}")


class Tracer:
    """The profiler over one window of a ``--trace 1`` run; a no-op when
    ``enabled`` is false. The window is a host ``TraceAnnotation`` that the
    reduction reads its bounds from. The Python tracer stays off: it slows
    the host, whose gaps between device work are part of what is read."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled, self.seconds = enabled, seconds
        self.active = False
        self._dir = None
        self._ann = None
        self._trace = None

    def start(self) -> None:
        import jax

        if not self.enabled:
            return
        self._dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._dir.name, profiler_options=options)
        self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
        self._ann.__enter__()
        self.active = True

    def stop(self) -> None:
        import glob

        import jax

        if not self.active:
            return
        self.active = False
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self._dir.name, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self._trace = trace_mod.load(paths[0])
        self._dir.cleanup()

    def result(self) -> trace_mod.Trace | None:
        return self._trace


class CompileCounter:
    """Counts, while the block runs, the programs JAX compiles or loads
    (``count``; the backend-compile event fires for both), and of those the
    ones its persistent cache held (``cache_hits``) and did not hold
    (``cache_misses``: compiled anew)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.count = self.cache_hits = self.cache_misses = 0
        self._on = False

    def _listener(self, event, duration, **kwargs):
        if self._on and event == self.EVENT:
            self.count += 1

    def _event(self, event, **kwargs):
        if self._on and event == self.HIT:
            self.cache_hits += 1
        elif self._on and event == self.MISS:
            self.cache_misses += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listener)
        jax.monitoring.register_event_listener(self._event)
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def memory_peak() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))
