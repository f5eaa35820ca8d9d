"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The benchmark wraps its traced window in a host ``TraceAnnotation`` named
``WINDOW``; every number here is taken inside that span, on the trace's
own clock. Device planes are the planes named ``/device:<kind>:<n>``; on
each, the line of XLA operations holds one event per operation that ran.
Busy time is the union of those events' intervals, averaged over the
devices; a kernel's time is the summed duration of its events.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
#: The line of a device plane that holds one event per XLA operation.
OPS_LINE = "XLA Ops"

Event = Tuple[str, int, int]            # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]             # (start_ns, end_ns) of WINDOW
    device_ops: Dict[str, List[Event]]  # device plane -> its op events
    host: List[Event]                   # events of the window's host thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clipped(self, events: List[Event]) -> List[Event]:
        a, b = self.window
        return [(n, max(s, a), min(e, b)) for n, s, e in events
                if e > a and s < b]

    def busy_intervals(self, plane: str) -> List[Tuple[int, int]]:
        """Union of the op intervals of one device plane, in the window."""
        out: List[Tuple[int, int]] = []
        for _, s, e in sorted(self._clipped(self.device_ops[plane]),
                              key=lambda x: x[1]):
            if out and s <= out[-1][1]:
                out[-1] = (out[-1][0], max(out[-1][1], e))
            else:
                out.append((s, e))
        return out

    def busy_s(self) -> float | None:
        """Seconds in which an operation ran, averaged over the devices;
        None when the trace holds no device."""
        if not self.device_ops:
            return None
        total = sum(e - s for p in self.device_ops
                    for s, e in self.busy_intervals(p))
        return total * 1e-9 / len(self.device_ops)

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the ops whose name matches ``pattern``,
        over all devices."""
        rx = re.compile(pattern)
        return sum(e - s for evs in self.device_ops.values()
                   for n, s, e in self._clipped(evs) if rx.search(n)) * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device operations that took most time, by short name;
        loops are left out, since their time is that of the ops in them."""
        acc: Dict[str, int] = {}
        for evs in self.device_ops.values():
            for n, s, e in self._clipped(evs):
                short = short_name(n)
                if short.split(" ")[1:2] != ["while"]:
                    acc[short] = acc.get(short, 0) + (e - s)
        top = sorted(acc.items(), key=lambda x: -x[1])[:k]
        return [[n, t * 1e-9] for n, t in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps between device operations (first device),
        each named by the innermost event of the window's host thread that
        covers at least half of it (else the one that overlaps it most)."""
        if not self.device_ops:
            return []
        plane = sorted(self.device_ops)[0]
        busy = self.busy_intervals(plane)
        a, b = self.window
        edges = [a] + [x for iv in busy for x in iv] + [b]
        gaps = sorted(((edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]),
                      key=lambda g: g[0] - g[1])[:k]
        out = []
        for s, e in gaps:
            best, name, inner = 0, "host: no event", None
            for n, hs, he in self.host:
                ov = min(e, he) - max(s, hs)
                if ov > best:
                    best, name = ov, n
                if 2 * ov >= e - s and (inner is None or he - hs < inner[1]):
                    inner = (n, he - hs)
            out.append([(inner[0] if inner else name)[:80], (e - s) * 1e-9])
        return out


_HLO = re.compile(r"^%?([\w.\-]+) = (?:\(?([a-z0-9]+\[[\d,]*\]))?.*?"
                  r"\b([a-z][a-z\-]*)\(")


def short_name(hlo: str) -> str:
    """``name kind shape`` of an op event, whose name is its HLO text:
    ``%fusion.65 = f32[40008,2]{...} fusion(...)`` -> ``fusion.65 fusion
    f32[40008,2]``."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    return " ".join(x for x in (m.group(1), m.group(3), m.group(2)) if x)


def _is_device(plane_name: str) -> bool:
    return (plane_name.startswith("/device:")
            and not plane_name.startswith("/device:CPU"))


def load(path: str) -> Trace:
    """Read the trace at ``path`` (gzipped where it ends in ``.gz``); raise
    if it has no ``WINDOW`` span."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    window = None
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (ev.name, int(ev.start_ns),
                         int(ev.start_ns + ev.duration_ns))
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.name, int(ev.start_ns),
                        int(ev.start_ns + ev.duration_ns))
                       for ev in line.events]
                spans = [(s, e) for n, s, e in evs if n == WINDOW]
                if spans:
                    window = spans[0]
                    host = [x for x in evs if x[0] != WINDOW]
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span in the trace")
    return Trace(window=window, device_ops=device_ops, host=host)
