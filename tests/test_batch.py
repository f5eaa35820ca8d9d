"""Batched multi-graph engine (bucketed BPEngine) vs per-graph runs.

The contract under test: a graph inside a padded bucket reproduces its solo
trajectory -- same rounds, same committed messages, beliefs equal to float
tolerance -- for every scheduler, and the disjoint-union fold / Pallas batch
path match the reference update.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BPConfig, BPEngine, LBP, RBP, RS, RnBP, BatchedPGM,
                        batch_keys, bucket_pgms, messages as M, pad_pgm)
from repro.kernels.ops import pallas_update
from repro.pgm import chain_graph, ising_grid, loop_graph, protein_like_graph

SCHEDULERS = [LBP(), RBP(p=1.0 / 16), RS(p=0.05), RnBP(low_p=0.4, high_p=0.9)]


def engine(sched, **cfg) -> BPEngine:
    return BPEngine(BPConfig(scheduler=sched, **cfg))


def mixed_pgms():
    """16-graph mixed-size grid/chain/loop set (one padded bucket)."""
    return ([ising_grid(n, 2.0, seed=n) for n in (5, 6, 7, 8, 9)]
            + [chain_graph(n, seed=n) for n in (30, 50, 80, 120, 160)]
            + [loop_graph(n, seed=n) for n in (16, 24, 40, 64, 96, 128)])


def _belief_diff(a, b):
    return float(jnp.max(jnp.abs(jnp.where(jnp.isfinite(b), a - b, 0.0))))


class TestBatchParity:
    @pytest.mark.parametrize("sched", SCHEDULERS,
                             ids=lambda s: type(s).__name__)
    def test_batch_matches_per_graph(self, sched):
        pgms = mixed_pgms()
        batch = BatchedPGM.from_pgms(pgms)
        assert batch.size == 16
        keys = batch_keys(jax.random.key(0), batch)
        eng = engine(sched, eps=1e-4, max_rounds=600, history=False)
        res = eng.run(batch, keys)
        for i in range(batch.size):
            solo = eng.run(batch.graph(i), keys[i])
            assert int(res.rounds[i]) == int(solo.rounds), f"graph {i}"
            assert bool(res.converged[i]) == bool(solo.converged)
            assert _belief_diff(res.beliefs[i], solo.beliefs) < 1e-5, \
                f"graph {i}"

    def test_padding_is_inert(self):
        """BP on a bucket-padded graph == BP on the original
        (LBP: deterministic, shape-independent selection)."""
        pgm = ising_grid(7, 2.0, seed=3)
        padded = pad_pgm(pgm, n_edges=pgm.n_edges + 256,
                         n_vertices=pgm.n_vertices + 16,
                         n_states=pgm.n_states_max + 3)
        eng = engine(LBP(), eps=1e-4)
        a = eng.run(pgm, jax.random.key(0))
        b = eng.run(padded, jax.random.key(0))
        assert int(a.rounds) == int(b.rounds)
        v, s = pgm.n_real_vertices, pgm.n_states_max
        np.testing.assert_allclose(np.asarray(a.beliefs[:v]),
                                   np.asarray(b.beliefs[:v, :s]), atol=1e-5)

    def test_per_graph_convergence_and_rounds(self):
        """Fast graphs freeze (rounds, updates) while stragglers finish."""
        pgms = [chain_graph(20, seed=1), ising_grid(9, 2.5, seed=11)]
        batch = BatchedPGM.from_pgms(pgms)
        keys = batch_keys(jax.random.key(2), batch)
        res = engine(RnBP(low_p=0.4, high_p=0.9), eps=1e-4,
                     max_rounds=800, history=False).run(batch, keys)
        r = np.asarray(res.rounds)
        assert bool(res.converged[0]) and bool(res.converged[1])
        assert r[0] < r[1]  # the chain converged first and froze


class TestBucketing:
    def test_buckets_cover_and_bound_padding(self):
        pgms = mixed_pgms() + [protein_like_graph(40, seed=5)]
        buckets = bucket_pgms(pgms)
        seen = sorted(i for b in buckets for i in b.indices)
        assert seen == list(range(len(pgms)))
        for b in buckets:
            for i in b.indices:
                # pow2 bucketing: <= 2x padding on the edge axis
                assert b.batch.n_edges <= 2 * max(pgms[i].n_edges, 128)
        # the 81-state protein graph must not share a bucket with S=2 graphs
        for b in buckets:
            smax = {pgms[i].n_states_max for i in b.indices}
            assert len({1 << (s - 1).bit_length() for s in smax}) == 1

    def test_growth_inf_single_bucket(self):
        pgms = mixed_pgms()
        buckets = bucket_pgms(pgms, growth=math.inf)
        assert len(buckets) == 1 and len(buckets[0].indices) == len(pgms)

    def test_max_batch_splits(self):
        pgms = [chain_graph(30, seed=s) for s in range(7)]
        buckets = bucket_pgms(pgms, max_batch=3)
        assert [len(b.indices) for b in buckets] == [3, 3, 1]

    def test_run_many_order_and_bucket_invariance(self):
        pgms = mixed_pgms()
        eng = engine(LBP(), eps=1e-4, max_rounds=600, history=False)
        res_fine = eng.run_many(pgms, jax.random.key(0))
        res_one = eng.run_many(pgms, jax.random.key(0), growth=math.inf)
        assert len(res_fine) == len(pgms)
        for i, pgm in enumerate(pgms):
            assert bool(res_fine[i].converged)
            v, s = pgm.n_real_vertices, pgm.n_states_max
            np.testing.assert_allclose(
                np.asarray(res_fine[i].beliefs[:v, :s]),
                np.asarray(res_one[i].beliefs[:v, :s]), atol=1e-5)


class TestFoldedUpdates:
    def test_union_fold_matches_vmapped_ref(self):
        batch = BatchedPGM.from_pgms(
            [ising_grid(6, 2.0, seed=s) for s in range(3)]
            + [chain_graph(40, seed=7)])
        union = batch.folded()
        b, e, s = batch.size, batch.n_edges, batch.n_states_max
        logm = jax.vmap(M.init_messages)(batch.pgm)
        c_v, r_v = jax.vmap(M.ref_update)(batch.pgm, logm)
        c_u, r_u = M.ref_update(union, logm.reshape(b * e, s))
        np.testing.assert_array_equal(np.asarray(c_v.reshape(b * e, s)),
                                      np.asarray(c_u))
        np.testing.assert_array_equal(np.asarray(r_v.reshape(-1)),
                                      np.asarray(r_u))

    def test_pallas_batch_fold_matches_ref(self):
        batch = BatchedPGM.from_pgms(
            [ising_grid(6, 2.0, seed=s) for s in range(3)]
            + [chain_graph(40, seed=7)])
        b, e, s = batch.size, batch.n_edges, batch.n_states_max
        logm = jax.vmap(M.init_messages)(batch.pgm)
        c_ref, r_ref = jax.vmap(M.ref_update)(batch.pgm, logm)
        c_k, r_k = pallas_update(batch.folded(), logm.reshape(b * e, s),
                                 interpret=True)
        c_k, r_k = c_k.reshape(b, e, s), r_k.reshape(b, e)
        mask = np.asarray(
            jax.vmap(lambda p: p.state_mask[p.edge_dst])(batch.pgm))
        np.testing.assert_allclose(
            np.where(mask, np.asarray(c_k), 0.0),
            np.where(mask, np.asarray(c_ref), 0.0), atol=1e-5)
        np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_ref),
                                   atol=1e-5)

    def test_e2e_batch_with_pallas_update(self):
        """Whole-bucket BP through the folded Pallas kernel converges to the
        reference fixed point (trajectories may differ within eps)."""
        batch = BatchedPGM.from_pgms([ising_grid(6, 2.0, seed=s)
                                      for s in range(3)])
        keys = batch_keys(jax.random.key(1), batch)
        ref = engine(RnBP(), eps=1e-4, max_rounds=400,
                     history=False).run(batch, keys)
        ker = engine(RnBP(), eps=1e-4, max_rounds=400, history=False,
                     backend="pallas").run(batch, keys)
        assert bool(jnp.all(ker.converged))
        assert _belief_diff(ker.beliefs, ref.beliefs) < 1e-3
