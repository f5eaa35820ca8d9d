"""Read the program's device scopes and host spans from a profiler trace.

The program names the phases of a BP round with ``jax.named_scope``
(``bp.prelude``, ``bp.layout``, ``bp.update``, ``bp.select``, ``bp.commit``)
and those of ``BPEngine.run`` with host ``TraceAnnotation`` spans
(``bp.run`` holding ``bp.init``, ``bp.step``, ``bp.finished``,
``bp.result``).

A scope reaches the trace as part of an XLA op's scope path (the ``tf_op``
stat, ``jit(f)/while/body/jit(g)/bp.prelude/gather``). That stat belongs to
the event's *metadata*, which ``jax.profiler.ProfileData`` does not expose,
so ``load`` decodes the XSpace itself with ``google.protobuf``, from a
descriptor of the few fields it reads (unknown fields are skipped): no
TensorFlow, no xprof. Its times are those ``bench.trace.load`` reads.

    python3 bench/scopes.py <trace.xplane.pb[.gz]>

prints, for the benchmark's window (``bench.trace.WINDOW``), the ``scopes:``
line (device ms of the round loop under each scope) and the
``engine idle:`` line (device idle ms under each host span).
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import os
import sys
from typing import Dict, List, Tuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from bench import trace  # noqa: E402

#: The program's scope and span names start with this.
PREFIX = "bp."
#: What the round loop's ops fall under where no ``bp.*`` scope names them.
UNSCOPED = "unscoped"
#: Device idle time outside every ``bp.run`` span.
OUTSIDE = "outside bp.run"
#: The stat of an XLA op's event metadata that holds its scope path.
SCOPE_STAT = "tf_op"

# The subset of ``tsl/profiler/protobuf/xplane.proto`` read here, as
# (message, [(field, number, type, label, message type)]); proto2, so that a
# stat says which of its values it holds. Maps are in their wire form:
# repeated entries of (key = 1, value = 2).
_I64, _U64, _DBL, _STR, _BYT, _MSG = 3, 4, 1, 9, 12, 11
_OPT, _REP = 1, 3
_SCHEMA = [
    ("XSpace", [("planes", 1, _MSG, _REP, "XPlane")]),
    ("XPlane", [("name", 2, _STR, _OPT, None),
                ("lines", 3, _MSG, _REP, "XLine"),
                ("event_metadata", 4, _MSG, _REP, "EventMetadataEntry"),
                ("stat_metadata", 5, _MSG, _REP, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, _I64, _OPT, None),
                            ("value", 2, _MSG, _OPT, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, _I64, _OPT, None),
                           ("value", 2, _MSG, _OPT, "XStatMetadata")]),
    ("XLine", [("id", 1, _I64, _OPT, None), ("name", 2, _STR, _OPT, None),
               ("timestamp_ns", 3, _I64, _OPT, None),
               ("events", 4, _MSG, _REP, "XEvent")]),
    ("XEvent", [("metadata_id", 1, _I64, _OPT, None),
                ("offset_ps", 2, _I64, _OPT, None),
                ("duration_ps", 3, _I64, _OPT, None)]),
    ("XStat", [("metadata_id", 1, _I64, _OPT, None),
               ("double_value", 2, _DBL, _OPT, None),
               ("uint64_value", 3, _U64, _OPT, None),
               ("int64_value", 4, _I64, _OPT, None),
               ("str_value", 5, _STR, _OPT, None),
               ("bytes_value", 6, _BYT, _OPT, None),
               ("ref_value", 7, _U64, _OPT, None)]),
    ("XEventMetadata", [("name", 2, _STR, _OPT, None),
                        ("stats", 5, _MSG, _REP, "XStat")]),
    ("XStatMetadata", [("name", 2, _STR, _OPT, None)]),
]


def _space_class():
    pkg = "bench_xplane"
    f = descriptor_pb2.FileDescriptorProto(name=f"{pkg}.proto", package=pkg,
                                           syntax="proto2")
    for msg, fields in _SCHEMA:
        m = f.message_type.add(name=msg)
        for name, number, typ, label, ref in fields:
            fd = m.field.add(name=name, number=number, type=typ, label=label)
            if ref:
                fd.type_name = f".{pkg}.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


Op = Tuple[str, int, int, str]          # (name, start_ns, end_ns, scope path)


@dataclasses.dataclass
class Space:
    """The window, the device op events with their scope paths, and the
    events of the window's host thread."""

    window: Tuple[int, int]
    device_ops: Dict[str, List[Op]]     # device plane -> its op events
    host: List[trace.Event]

    def trace(self) -> trace.Trace:
        """The same ``Trace`` that ``bench.trace.load`` reads."""
        return trace.Trace(
            window=self.window, host=self.host,
            device_ops={p: [ev[:3] for ev in evs]
                        for p, evs in self.device_ops.items()})


def _scope_path(meta, scope_id: int, stat_names: Dict[int, str]) -> str:
    """The op's scope path without its ``:<type>`` suffix; "" if none."""
    for st in meta.stats:
        if st.metadata_id == scope_id:
            value = (stat_names.get(st.ref_value, "")
                     if st.HasField("ref_value") else st.str_value)
            return value.rpartition(":")[0] if ":" in value else value
    return ""


def load(path: str) -> Space:
    """Read the trace at ``path`` (gzipped where it ends in ``.gz``); raise
    if it has no ``WINDOW`` span. Times are nanoseconds on the profiler's
    clock, rounded as ``ProfileData`` rounds them."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = _space_class().FromString(f.read())
    window, host = None, []
    device_ops: Dict[str, List[Op]] = {}
    for plane in space.planes:
        device = trace._is_device(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_id = next((k for k, v in stat_names.items()
                         if v == SCOPE_STAT), None)
        meta = {e.key: (e.value.name,
                        _scope_path(e.value, scope_id, stat_names))
                for e in plane.event_metadata}
        for line in plane.lines:
            if device and line.name != trace.OPS_LINE:
                continue
            evs = []
            for ev in line.events:
                name, scope = meta.get(ev.metadata_id, ("", ""))
                start = line.timestamp_ns + ev.offset_ps // 1000
                evs.append((name, start,
                            int(start + ev.duration_ps / 1000), scope))
            if device:
                device_ops[plane.name] = evs
                continue
            spans = [(s, e) for n, s, e, _ in evs if n == trace.WINDOW]
            if spans:
                window = spans[0]
                host = [ev[:3] for ev in evs if ev[0] != trace.WINDOW]
    if window is None:
        raise ValueError(f"{path}: no {trace.WINDOW!r} span in the trace")
    return Space(window=window, device_ops=device_ops, host=host)


def _is_loop(name: str) -> bool:
    return trace.short_name(name).split(" ")[1:2] == ["while"]


def innermost_scope(path: str) -> str:
    """The innermost ``bp.*`` component of a scope path, else
    ``UNSCOPED``. Scopes nest where the kernel's padding (``bp.layout``)
    sits inside the kernel call (``bp.update``): the padding counts as
    layout."""
    for part in reversed(path.split("/")):
        if part.startswith(PREFIX):
            return part
    return UNSCOPED


def loop_scope_ns(space: Space) -> Dict[str, int]:
    """Device nanoseconds, in the window and over all devices, of the ops
    that ran inside a ``while`` op (the round loop), by innermost scope;
    ``UNSCOPED`` holds ops with no ``bp.*`` scope, ops without metadata
    among them. The ``while`` op itself is left out, so the values sum to
    the loop's op time."""
    a, b = space.window
    out: Dict[str, int] = {}
    for evs in space.device_ops.values():
        loops = sorted((s, e) for n, s, e, _ in evs if _is_loop(n))
        starts = [s for s, _ in loops]
        reach = []                      # latest end of the loops so far
        for _, e in loops:
            reach.append(max(e, reach[-1]) if reach else e)
        for n, s, e, path in evs:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or e > reach[i] or _is_loop(n):
                continue
            ns = min(e, b) - max(s, a)
            if ns > 0:
                key = innermost_scope(path)
                out[key] = out.get(key, 0) + ns
    return out


def idle_by_span(t: trace.Trace) -> Dict[str, int] | None:
    """Device idle nanoseconds in the window (first device, as
    ``Trace.idle_gaps``), by the innermost ``bp.*`` host span of the
    window's thread that covers them; ``OUTSIDE`` holds the rest. None
    where the trace has no device, or no ``bp.run`` span."""
    spans = sorted(((n, s, e) for n, s, e in t.host if n.startswith(PREFIX)),
                   key=lambda x: (x[1], -x[2]))
    if not t.device_ops or not any(n == "bp.run" for n, _, _ in spans):
        return None
    a, b = t.window
    busy = t.busy_intervals(sorted(t.device_ops)[0])
    edges = [a] + [x for iv in busy for x in iv] + [b]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    starts = [s for s, _ in gaps]
    before = [0]
    for s, e in gaps:
        before.append(before[-1] + e - s)

    def idle_to(x: int) -> int:
        """Idle nanoseconds from the window's start to ``x``."""
        i = bisect.bisect_right(starts, x)
        if i == 0:
            return 0
        s, e = gaps[i - 1]
        return before[i - 1] + min(x, e) - s

    def idle(s: int, e: int) -> int:
        return idle_to(min(max(e, a), b)) - idle_to(min(max(s, a), b))

    out = {OUTSIDE: idle(a, b)}
    stack: List[Tuple[str, int, int]] = []
    for n, s, e in spans:           # spans nest: a parent comes first
        while stack and stack[-1][2] <= s:
            stack.pop()
        own = idle(s, e)
        out[stack[-1][0] if stack else OUTSIDE] -= own
        out[n] = out.get(n, 0) + own
        stack.append((n, s, e))
    return out


def format_ms(ns: Dict[str, int], per: float, unit: str) -> str:
    """``name value`` pairs, largest first, in ms over ``per``."""
    items = sorted(ns.items(), key=lambda x: -x[1])
    return ", ".join(f"{k} {ns_ * 1e-6 / per:.4f}" for k, ns_ in items) + \
        f" (ms per {unit})"


def main(argv=None) -> None:
    paths = sys.argv[1:] if argv is None else argv
    if len(paths) != 1:
        sys.exit("usage: python3 bench/scopes.py <trace.xplane.pb[.gz]>")
    space = load(paths[0])
    print("scopes: " + format_ms(loop_scope_ns(space), 1.0, "window"))
    idle = idle_by_span(space.trace())
    if idle is not None:
        runs = sum(1 for n, _, _ in space.host if n == "bp.run")
        print("engine idle: " + format_ms(idle, runs, "solve"))


if __name__ == "__main__":
    main()
