"""The message prelude's source-state mask. ``edge_prelude`` reads the
(E, S) mask from ``PGM.src_mask`` where the engine filled it once per chunk,
and gathers it itself where the PGM lacks it. Both must leave ``pre``, and so
every trajectory, bitwise as the per-edge formula has it:
``log_psi_v[src] + vsum[src] - logm[rev]``, masked by ``state_mask[src]``."""

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

from repro.core import BPConfig, BPEngine
from repro.core import engine as E
from repro.core import messages as M
from repro.core.batch import BatchedPGM
from repro.core.graph import NEG_INF
from repro.kernels import ops
from repro.pgm import WORKLOADS


def oracle_prelude(pgm, logm):
    """The prelude's formula, the mask gathered with the terms."""
    vsum = M.vertex_logprod(pgm, logm)
    pre = (pgm.log_psi_v[pgm.edge_src] + vsum[pgm.edge_src]
           - logm[pgm.edge_rev])
    return jnp.where(pgm.state_mask[pgm.edge_src], pre, NEG_INF)


#: Zoo members at small sizes. ``ldpc`` (bits 2 states, checks 8) and
#: ``protein`` (2-39 states, no in-edge table) mix state counts, so their
#: PGMs hold invalid states; every graph has a dummy vertex.
GRAPHS = {
    "ising": dict(n=6, C=2.0),
    "chain": dict(n=120),
    "ldpc": dict(n=24, dv=2, dc=4),
    "protein": dict(n_vertices=24),
    "stereo": dict(height=6, width=8, n_disp=4),
}


def _graph(name, seed=0):
    return WORKLOADS[name](seed=seed, **GRAPHS[name])


def _messages(pgm, seed):
    """Random log-messages, NEG_INF at states the destination lacks."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(pgm.n_edges, pgm.n_states_max)).astype(np.float32)
    return jnp.where(pgm.state_mask[pgm.edge_dst], x, NEG_INF)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("mode", ["single", "vmap"])
@pytest.mark.parametrize("mask", ["gathered", "precomputed"])
@pytest.mark.parametrize("table", ["in_edges", "segment_sum"])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_prelude_is_bitwise_the_per_edge_formula(name, table, mask, mode):
    pgm = _graph(name)
    if table == "segment_sum":
        pgm = dataclasses.replace(pgm, in_edges=None)
    if mode == "single":
        logm = _messages(pgm, 1)
        prep, run = M.with_src_mask, jax.jit
    else:
        pgm = jax.tree.map(lambda x: jnp.stack([x, x]), pgm)
        logm = jnp.stack([_messages(_graph(name), s) for s in (1, 2)])
        prep, run = jax.vmap(M.with_src_mask), lambda f: jax.jit(jax.vmap(f))
    want = run(oracle_prelude)(pgm, logm)
    if mask == "precomputed":
        pgm = prep(pgm)
        assert pgm.src_mask.shape == logm.shape
    got = run(M.edge_prelude)(pgm, logm)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _reads(pgm, logm):
    """``SRC_GATHERS`` as recorded by a fresh trace of ``edge_prelude``."""
    jax.clear_caches()
    heard = []

    def listen(event, value, **kwargs):
        if event == M.SRC_GATHERS:
            heard.append(value)

    jax.monitoring.register_scalar_listener(listen)
    try:
        jax.jit(M.edge_prelude).lower(pgm, logm)
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    return heard


def test_prelude_counts_its_source_gathers():
    pgm = _graph("ising")
    logm = _messages(pgm, 0)
    assert _reads(pgm, logm) == [3]
    assert _reads(M.with_src_mask(pgm), logm) == [2]


# ------------------------------------------------ engine trajectories --

#: Each backend's update body, called unjitted so that it traces whatever
#: ``M.edge_prelude`` is at the time: the oracle, under monkeypatch.
BODIES = {
    "ref": lambda p, m: M.ref_update(p, m),
    "maxprod": lambda p, m: M.max_product_update(p, m),
    "pallas": lambda p, m: ops.pallas_update.__wrapped__(p, m,
                                                         interpret=True),
    "pallas_max": lambda p, m: ops.pallas_update.__wrapped__(
        p, m, interpret=True, semiring="max"),
}

#: Sum-product on a mixed-state graph and a grid; max-product on stereo
#: grids, as the MAP cell.
SOLVE_GRAPHS = {"ref": ("ldpc", "ising"), "pallas": ("ldpc", "ising"),
                "maxprod": ("stereo", "stereo"),
                "pallas_max": ("stereo", "stereo")}


def _assert_same_trajectory(a, b):
    assert int(a.rounds) > 1
    np.testing.assert_array_equal(np.asarray(a.rounds), np.asarray(b.rounds))
    np.testing.assert_array_equal(np.asarray(a.updates),
                                  np.asarray(b.updates))
    np.testing.assert_array_equal(_bits(a.logm), _bits(b.logm))


@pytest.mark.parametrize("path", ["run", "run_many"])
@pytest.mark.parametrize("backend", sorted(BODIES))
def test_engine_trajectory_is_the_oracle_loops(monkeypatch, backend, path):
    first, second = SOLVE_GRAPHS[backend]
    pgms = [_graph(first, 0), _graph(second, 1)]
    cfg = dict(scheduler="rnbp", eps=1e-3, max_rounds=300)
    eng = BPEngine(BPConfig(backend=backend, **cfg))
    if path == "run":
        got = [eng.run(pgms[0], jax.random.key(2))]
    else:
        got = eng.run_many(pgms, jax.random.key(2))
    with monkeypatch.context() as mp:
        mp.setattr(M, "edge_prelude", oracle_prelude)
        oracle = BPEngine(BPConfig(backend=BODIES[backend], **cfg))
        if path == "run":
            want = [oracle.run(pgms[0], jax.random.key(2))]
        else:
            want = oracle.run_many(pgms, jax.random.key(2))
    for a, b in zip(got, want):
        _assert_same_trajectory(a, b)


# ------------------------------------------- the round loop's gathers --

def _gathers(jaxpr, labels, found, in_body=False):
    """Walk ``jaxpr`` (invars labelled by the PGM fields they derive from)
    into nested jits and ``while`` loops; append ``(dtype, shape, fields of
    the indices)`` for each gather inside a ``while`` body."""
    env = dict(zip(jaxpr.invars, labels))

    def get(a):
        return env.get(a, frozenset()) if isinstance(a, Var) else frozenset()

    for eqn in jaxpr.eqns:
        ins = [get(a) for a in eqn.invars]
        name = eqn.primitive.name
        if name == "while":
            n = eqn.params["cond_nconsts"]
            _gathers(eqn.params["body_jaxpr"].jaxpr, ins[n:], found, True)
        elif name in ("pjit", "jit"):
            _gathers(eqn.params["jaxpr"].jaxpr, ins, found, in_body)
        elif name == "gather" and in_body:
            out = eqn.outvars[0].aval
            found.append((out.dtype, out.shape, ins[1]))
        for o in eqn.outvars:
            env[o] = frozenset().union(*ins)


def _loop_gathers(graph, state_args, chunk, kw):
    """Gathers in the ``while`` body of ``chunk`` run on ``graph``."""
    closed = jax.make_jaxpr(partial(chunk, **kw))(graph, *state_args)
    paths = jax.tree_util.tree_flatten_with_path(graph)[0]
    labels = [frozenset({path[-1].name}) for path, _ in paths]
    labels += [frozenset()] * (len(closed.jaxpr.invars) - len(labels))
    found = []
    _gathers(closed.jaxpr, labels, found)
    return found


@pytest.mark.parametrize("backend,batched", [
    ("ref", False), ("ref", True), ("pallas", False), ("pallas", True)])
def test_round_loop_gathers_no_source_mask(backend, batched):
    pgm = _graph("ising")
    eng = BPEngine(BPConfig(scheduler="rnbp", eps=1e-3, max_rounds=50,
                            backend=backend))
    graph = BatchedPGM.from_pgms([pgm, _graph("ising", 1)]) if batched \
        else pgm
    state = eng.init(graph, jax.random.key(0))
    cfg = eng.config
    kw = dict(scheduler=eng.scheduler, damping=cfg.damping,
              update_fn=eng.update_fn, track_history=cfg.history)
    chunk = E._chunk_batch if batched else E._chunk_single
    heard = []

    def listen(event, value, **kwargs):
        if event == M.SRC_GATHERS:
            heard.append(value)

    jax.clear_caches()
    jax.monitoring.register_scalar_listener(listen)
    try:
        found = _loop_gathers(graph, (E._carry_of(state), state.rounds,
                                      cfg.eps), chunk, kw)
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    assert heard and set(heard) == {2}
    edges = state.size * pgm.n_edges
    edge_rows = [(dtype, fields) for dtype, shape, fields in found
                 if len(shape) >= 2 and shape[-1] == pgm.n_states_max
                 and math.prod(shape[:-1]) == edges]
    f32 = sorted(tuple(sorted(f)) for d, f in edge_rows if d == jnp.float32)
    assert f32 == [("edge_rev",), ("edge_src",), ("edge_src",)], edge_rows
    assert not [f for d, f in edge_rows
                if d == jnp.bool_ and "edge_src" in f], edge_rows
