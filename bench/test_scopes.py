"""Self-test of the scope and span reduction (``bench/scopes.py``), on the
CPU:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench

It checks the XSpace decoder against ``jax.profiler.ProfileData`` on the
recorded chip trace that predates the program's scopes, the scope paths it
reads there, the idle attribution on a made-up trace, and every number the
reduction reads from a chip trace recorded with the scopes and spans.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import scopes, trace  # noqa: E402
from bench.drivers import common  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata")
#: The solve cell's traced window before the program had scopes or spans.
UNSCOPED_TRACE = os.path.join(TESTDATA, "ising_small.xplane.pb.gz")
#: A traced window of the solve cell on a TPU v5e with the scopes and spans
#: (a ``--trace 1`` run with ``trace_s`` 0.05, its ``.xplane.pb`` gzipped),
#: and what the reduction read from it when it was recorded.
SCOPED_TRACE = os.path.join(TESTDATA, "ising_scoped.xplane.pb.gz")
ROUND_SCOPES = ("bp.prelude", "bp.layout", "bp.update", "bp.select",
                "bp.commit")


def _reader(name):
    from bench import run

    return run.metric_reader(name)


def test_decoder_reads_what_profiledata_reads():
    assert scopes.load(UNSCOPED_TRACE).trace() == trace.load(UNSCOPED_TRACE)


def test_decoder_reads_each_ops_scope_path():
    paths = {}
    for name, _, _, path in scopes.load(UNSCOPED_TRACE).device_ops[
            "/device:TPU:0"]:
        paths.setdefault(trace.short_name(name).split(" ")[0], path)
    body = "jit(_chunk_single)/while/body/jit(pallas_update)"
    assert paths["sort.2"] == paths["fusion.65"] == f"{body}/scatter-add"
    assert paths["fused_update_t.9"] == (
        f"{body}/jit(fused_update_t)/pallas_call")
    assert paths["copy.31"] == ""


def test_a_trace_without_scopes_or_spans_reads_nothing_new():
    space = scopes.load(UNSCOPED_TRACE)
    assert set(scopes.loop_scope_ns(space)) == {scopes.UNSCOPED}
    t = space.trace()
    assert scopes.idle_by_span(t) is None
    o = common.Outcome(setup_s=1.0, trace=t, traced_solves=1)
    assert _reader("engine_idle_ms").read(o) is None


def test_idle_goes_to_the_innermost_span():
    ms = 1_000_000
    t = trace.Trace(
        window=(0, 100 * ms),
        # idle: [0, 10), [20, 40), [50, 100) ms
        device_ops={"/device:TPU:0": [("a", 10 * ms, 20 * ms),
                                      ("b", 40 * ms, 50 * ms)]},
        host=[("bp.run", 5 * ms, 60 * ms), ("bp.step", 5 * ms, 8 * ms),
              ("bp.finished", 30 * ms, 55 * ms), ("other", 0, 100 * ms)])
    got = scopes.idle_by_span(t)
    assert got == {"bp.step": 3 * ms, "bp.finished": 15 * ms,
                   "bp.run": 35 * ms - 3 * ms - 15 * ms,
                   scopes.OUTSIDE: 80 * ms - 35 * ms}
    assert sum(got.values()) == 80 * ms
    o = common.Outcome(setup_s=1.0, trace=t, traced_solves=2)
    assert _reader("engine_idle_ms").read(o) == pytest.approx(35 / 2)


def _scoped():
    with open(SCOPED_TRACE.replace(".xplane.pb.gz", ".json")) as f:
        return scopes.load(SCOPED_TRACE), json.load(f)


def test_the_scoped_trace_reads_as_recorded():
    space, want = _scoped()
    t = space.trace()
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert scopes.loop_scope_ns(space) == want["loop_scope_ns"]
    assert scopes.idle_by_span(t) == want["idle_by_span_ns"]
    o = common.Outcome(setup_s=1.0, trace=t,
                       traced_solves=want["traced_solves"],
                       traced_rounds=want["traced_rounds"])
    assert _reader("engine_idle_ms").read(o) == pytest.approx(
        want["engine_idle_ms"], rel=1e-9)
    assert _reader("round_ms").read(o) == pytest.approx(want["round_ms"],
                                                         rel=1e-9)


def test_the_scopes_cover_the_round_loop():
    space, want = _scoped()
    got = scopes.loop_scope_ns(space)
    assert set(got) == set(ROUND_SCOPES) | {scopes.UNSCOPED}
    # The loop's op time, summed here without the reduction: every op
    # inside a while op's interval, the while op left out.
    loop = 0
    for evs in space.device_ops.values():
        loops = [(s, e) for n, s, e, _ in evs if scopes._is_loop(n)]
        loop += sum(e - s for n, s, e, _ in evs if not scopes._is_loop(n)
                    and any(a <= s and e <= b for a, b in loops))
    assert sum(got.values()) == loop
    assert got[scopes.UNSCOPED] <= 0.05 * loop
    # The loop's ops run in the rounds the solves report.
    per_round = loop * 1e-6 / want["traced_rounds"]
    assert 0.9 * want["round_ms"] < per_round <= want["round_ms"]


def test_engine_idle_is_part_of_the_idle_time():
    space, want = _scoped()
    t = space.trace()
    idle_per_solve = (t.window_s - t.busy_s()) * 1e3 / want["traced_solves"]
    o = common.Outcome(setup_s=1.0, trace=t,
                       traced_solves=want["traced_solves"])
    engine = _reader("engine_idle_ms").read(o)
    assert 0 < engine <= idle_per_solve
    split = scopes.idle_by_span(t)
    assert sum(split.values()) * 1e-6 == pytest.approx(
        idle_per_solve * want["traced_solves"], rel=1e-9)
    assert {"bp.init", "bp.step", "bp.finished", "bp.result"} <= set(split)
