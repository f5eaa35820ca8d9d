"""The names the program puts in traces: device scopes on the round's phases
(``jax.named_scope``), host spans on ``BPEngine.run``'s phases
(``jax.profiler.TraceAnnotation``) and the graph-build duration event
(``jax.monitoring``). Tracing must not change a result."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BPConfig, BPEngine
from repro.core import engine as E
from repro.core.batch import BatchedPGM
from repro.core.graph import BUILD_EVENT, build_pgm, build_pgm_uniform
from repro.pgm import ising_grid_fast

ROUND_SCOPES = ("bp.prelude", "bp.update", "bp.select", "bp.commit")
#: Only the Pallas paths reshape operands into the kernel's (S, E) layout.
LAYOUT_SCOPE = {"ref": (), "pallas": ("bp.layout",), "maxprod": (),
                "pallas_max": ("bp.layout",)}
RUN_SPANS = ("bp.init", "bp.step", "bp.finished", "bp.result")


def _engine(backend):
    return BPEngine(BPConfig(scheduler="rnbp", eps=1e-3, max_rounds=200,
                             backend=backend))


def _chunk_text(eng, state):
    """Lowered text, with its source locations, of the chunk ``step`` runs."""
    cfg = eng.config
    kw = dict(scheduler=eng.scheduler, damping=cfg.damping,
              update_fn=eng.update_fn, track_history=cfg.history)
    limit = jnp.minimum(state.rounds + cfg.max_rounds, cfg.max_rounds)
    if state.batched:
        lowered = E._chunk_batch.lower(
            state.graph, E._carry_of(state), limit, cfg.eps,
            batch_update_fn=eng.batch_update_fn, **kw)
    else:
        lowered = E._chunk_single.lower(state.graph, E._carry_of(state),
                                        limit, cfg.eps, **kw)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("backend", sorted(LAYOUT_SCOPE))
def test_round_phases_are_scoped_in_both_chunks(backend):
    eng = _engine(backend)
    pgms = [ising_grid_fast(4, 2.0, seed=s) for s in (0, 1)]
    single = eng.init(pgms[0], jax.random.key(0))
    batch = eng.init(BatchedPGM.from_pgms(pgms), jax.random.key(0))
    for state in (single, batch):
        text = _chunk_text(eng, state)
        for scope in ROUND_SCOPES + LAYOUT_SCOPE[backend]:
            # A path component; a nested jit's locations start a new path.
            assert re.search(rf'["/]{re.escape(scope)}/', text), (
                backend, state.batched, scope)
    if not LAYOUT_SCOPE[backend]:
        assert "bp.layout" not in _chunk_text(eng, single)


def _xplane(logdir):
    return jax.profiler.ProfileData.from_file(
        glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                               "*.xplane.pb"))[0])


CALLER = "test.caller"


def _spans_on_the_callers_line(logdir):
    """Events named ``bp.*`` on the host line that holds the test's own
    ``CALLER`` span, that is on the calling thread: name -> [(start, end)]
    in ns."""
    for plane in _xplane(logdir).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            if not any(ev.name == CALLER for ev in events):
                continue
            out = {}
            for ev in events:
                if ev.name.startswith("bp."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
            return out
    raise AssertionError(f"no {CALLER!r} span in the trace")


def test_run_spans_nest_on_the_calling_thread(tmp_path):
    eng = _engine("ref")
    pgm = ising_grid_fast(4, 2.0, seed=0)
    jax.block_until_ready(eng.run(pgm, jax.random.key(1)).beliefs)  # compile
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(CALLER):
            jax.block_until_ready(eng.run(pgm, jax.random.key(1)).beliefs)
    spans = _spans_on_the_callers_line(str(tmp_path))
    assert len(spans.get("bp.run", [])) == 1, spans
    (a, b), = spans["bp.run"]
    for name in RUN_SPANS:
        assert spans.get(name), (name, spans)
        assert all(a <= s <= e <= b for s, e in spans[name]), name
    # One chunk to the end: finished is asked before and after it.
    assert len(spans["bp.step"]) == 1 and len(spans["bp.finished"]) == 2


def _uniform():
    rng = np.random.default_rng(0)
    return build_pgm_uniform(3, np.array([[0, 1], [1, 2]]),
                             rng.uniform(0.5, 1.0, (3, 2)),
                             rng.uniform(0.5, 1.0, (2, 2, 2)))


def _general():
    rng = np.random.default_rng(0)
    return build_pgm(3, np.array([[0, 1], [1, 2]]),
                     [rng.uniform(0.5, 1.0, s) for s in (2, 3, 2)],
                     [rng.uniform(0.5, 1.0, (2, 3)),
                      rng.uniform(0.5, 1.0, (3, 2))])


@pytest.mark.parametrize("build", [_uniform, _general])
def test_build_records_its_duration_once(build):
    heard = []

    def listen(event, duration, **kwargs):
        if event == BUILD_EVENT:
            heard.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        build()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(heard) == 1 and heard[0] > 0


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_results_are_bitwise_equal_with_the_profiler_on(backend, tmp_path):
    eng = _engine(backend)
    pgm = ising_grid_fast(5, 2.0, seed=2)
    off = eng.run(pgm, jax.random.key(3))
    with jax.profiler.trace(str(tmp_path)):
        on = eng.run(pgm, jax.random.key(3))
    for a, b in zip(jax.tree.leaves(off), jax.tree.leaves(on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
