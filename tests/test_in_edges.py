"""The builders' per-vertex in-edge table (``PGM.in_edges``) and the gather
sum it feeds. With the table, ``vertex_logprod`` and ``edge_prelude`` must be
bitwise those of ``segment_sum`` on the CPU, so trajectories do not move;
every path that re-pads, stacks, folds or shards a PGM drops the table."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BPConfig, BPEngine
from repro.core import messages as M
from repro.core.batch import BatchedPGM
from repro.core.graph import (IN_EDGE_FILL, IN_EDGE_SLACK, IN_EDGE_WIDTH,
                              NEG_INF, build_pgm, pad_pgm)
from repro.pgm import chain_graph, ising_grid_fast, ldpc_graph


def _mixed_grid():
    """4x5 grid built by ``build_pgm`` with 2-4 states per vertex."""
    rng = np.random.default_rng(3)
    idx = np.arange(20).reshape(4, 5)
    edges = np.concatenate([
        np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], 1),
        np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], 1)])
    s = rng.integers(2, 5, size=20)
    return build_pgm(20, edges, [rng.uniform(0.1, 1.0, k) for k in s],
                     [rng.uniform(0.1, 1.0, (s[a], s[b])) for a, b in edges])


def _star(leaves: int = 20):
    rng = np.random.default_rng(4)
    edges = np.array([[0, k] for k in range(1, leaves + 1)])
    return build_pgm(leaves + 1, edges,
                     [rng.uniform(0.1, 1.0, 2) for _ in range(leaves + 1)],
                     [rng.uniform(0.1, 1.0, (2, 2)) for _ in edges])


GRAPHS = {
    "ising200": lambda: ising_grid_fast(200, 2.0, seed=0),
    "chain": lambda: chain_graph(300, seed=0),
    "ldpc": lambda: ldpc_graph(seed=0),
    "mixed_states": _mixed_grid,
    "no_padded_edge": lambda: chain_graph(65, seed=1),   # 128 directed edges
    "star": _star,
}


def _loop_table(pgm):
    """The in-edge table built one edge at a time: the reference."""
    dst, mask = np.asarray(pgm.edge_dst), np.asarray(pgm.edge_mask)
    rows = [[] for _ in range(pgm.n_vertices)]
    for e in range(pgm.n_edges):
        if mask[e]:
            rows[dst[e]].append(e)
    width = max(len(r) for r in rows)
    table = np.full((pgm.n_vertices, width), IN_EDGE_FILL, np.int32)
    for v, r in enumerate(rows):
        table[v, :len(r)] = r
    return table


def _messages(pgm, seed=0):
    """Random log-messages, NEG_INF at states the destination lacks."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(pgm.n_edges, pgm.n_states_max)).astype(np.float32)
    return jnp.where(pgm.state_mask[pgm.edge_dst], x, NEG_INF)


def _without_table(pgm):
    return dataclasses.replace(pgm, in_edges=None)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_gather_sum_is_bitwise_the_segment_sum(name):
    pgm = GRAPHS[name]()
    table = _loop_table(pgm)
    compact = (pgm.n_vertices * table.shape[1]
               <= IN_EDGE_SLACK * pgm.n_real_edges)
    if name == "star":
        assert not compact
    if name == "no_padded_edge":
        assert bool(np.all(np.asarray(pgm.edge_mask)))
    if not compact:
        assert pgm.in_edges is None
        return
    np.testing.assert_array_equal(np.asarray(pgm.in_edges), table)
    plain = _without_table(pgm)
    logm = _messages(pgm)
    for fn in (M.vertex_logprod, M.edge_prelude, M.beliefs,
               M.map_assignment):
        np.testing.assert_array_equal(np.asarray(jax.jit(fn)(pgm, logm)),
                                      np.asarray(jax.jit(fn)(plain, logm)))


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_engine_trajectory_is_bitwise_the_same(backend):
    pgm = ising_grid_fast(12, 2.0, seed=5)
    assert pgm.in_edges is not None
    eng = BPEngine(BPConfig(scheduler="rnbp", eps=1e-3, max_rounds=500,
                            backend=backend))
    a = eng.run(pgm, jax.random.key(1))
    b = eng.run(_without_table(pgm), jax.random.key(1))
    assert bool(a.converged)
    assert int(a.rounds) == int(b.rounds)
    assert int(a.updates) == int(b.updates)
    np.testing.assert_array_equal(np.asarray(a.logm), np.asarray(b.logm))
    np.testing.assert_array_equal(np.asarray(a.beliefs),
                                  np.asarray(b.beliefs))


def test_repadded_stacked_folded_and_sharded_graphs_drop_the_table():
    from repro.dist import make_bp_mesh, shard_pgm
    pgm = ising_grid_fast(6, 2.0, seed=0)
    assert pgm.in_edges is not None
    padded = pad_pgm(pgm, n_edges=pgm.n_edges + 128,
                     n_vertices=pgm.n_vertices + 8,
                     n_states=pgm.n_states_max)
    batch = BatchedPGM.from_pgms([pgm, chain_graph(10, seed=0)])
    for p in (padded, batch.pgm, batch.folded(), batch.take([1]).pgm,
              batch.graph(0), shard_pgm(pgm, make_bp_mesh(1))):
        assert p.in_edges is None


@pytest.mark.parametrize("name,width", [("chain", 2), ("star", 0)])
def test_build_records_the_table_width(name, width):
    heard = []

    def listen(event, value, **kwargs):
        if event == IN_EDGE_WIDTH:
            heard.append(value)

    jax.monitoring.register_scalar_listener(listen)
    try:
        GRAPHS[name]()
    finally:
        jax.monitoring.unregister_scalar_listener(listen)
    assert heard == [width]


def test_sharded_run_of_a_table_carrying_graph(run_on_cpu_devices):
    """A PGM that carries the table runs sharded over 8 host devices exactly
    as the same PGM without it: ``shard_pgm`` drops the table."""
    code = r"""
import dataclasses
import jax, numpy as np
from repro.core import LBP, run_bp
from repro.pgm import ising_grid_fast
from repro.dist import make_bp_mesh, make_sharded_engine, run_bp_sharded, shard_pgm

pgm = ising_grid_fast(16, 2.0, seed=0)
assert pgm.in_edges is not None
mesh = make_bp_mesh()
spgm = shard_pgm(pgm, mesh)
assert spgm.in_edges is None
engine = make_sharded_engine("rnbp", mesh, eps=1e-4, max_rounds=1000)
a = engine.run(spgm, jax.random.key(3))
b = engine.run(shard_pgm(dataclasses.replace(pgm, in_edges=None), mesh),
               jax.random.key(3))
assert bool(a.converged)
assert int(a.rounds) == int(b.rounds)
np.testing.assert_array_equal(np.asarray(a.logm), np.asarray(b.logm))
np.testing.assert_array_equal(np.asarray(a.beliefs), np.asarray(b.beliefs))
ref = run_bp(pgm, LBP(), jax.random.key(0), eps=1e-5, max_rounds=2000)
res = run_bp_sharded(pgm, LBP(), mesh, jax.random.key(0), eps=1e-5,
                     max_rounds=2000)
assert bool(res.converged) and int(res.rounds) == int(ref.rounds)
np.testing.assert_allclose(np.asarray(res.beliefs)[:256],
                           np.asarray(ref.beliefs)[:256], atol=1e-4)
print("OK")
"""
    run_on_cpu_devices(code)
