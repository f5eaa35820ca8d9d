"""Workload-zoo acceptance: generator properties, exact-reference
conformance per domain, and the heterogeneous serving stream.

- Property tests (hypothesis, optional extra -- the property classes skip
  via ``pytest.importorskip`` when it is missing; the structural tests
  below them always run): every registered generator yields a PGM with
  valid edge indices, strictly positive potentials (finite log-potentials
  on valid states), in-bounds state counts, and is deterministic under a
  fixed seed; ``pad_pgm`` to bucket ceilings is trajectory-inert.
- Differential conformance: small LDPC codewords decoded by the
  max-product backend match the exact MAP read off
  ``brute_force_marginals``/``ve_marginals`` (``repro.core.exact``); small
  stereo grids match exact marginals within tolerance for *every*
  registered scheduler (``list_schedulers()``, so new registrations are
  auto-covered).
- Heterogeneous-stream regression: the mixed ``zoo_stream`` through
  ``serve_async`` under each admission policy, and through the router
  tier under each routing policy (stealing on and off), is bitwise
  identical per request to solo ``BPEngine.run`` calls on identically
  padded graphs.
"""

import jax
import numpy as np
import pytest

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                       # degrade: property tests skip
    def given(*_a, **_k):
        return lambda f: f

    def settings(*_a, **_k):
        return lambda f: f

    class st:  # noqa: N801 - stand-in namespace, never executed
        integers = sampled_from = staticmethod(lambda *a, **k: None)

from repro.core import (BPConfig, BPEngine, list_admission_policies,
                        list_schedulers, serve_async)
from repro.core.batch import bucket_shape
from repro.core.exact import brute_force_marginals, ve_marginals
from repro.core.graph import NEG_INF, pad_pgm
from repro.core.messages import beliefs, map_assignment
from repro.pgm import (WORKLOADS, ldpc_code, list_workloads, stereo_mrf,
                       zoo_stream)
from repro.serve import list_routing_policies, serve_routed

#: small, fast size kwargs per kind -- property/structure tests sweep these
_SMALL = {
    "ising": dict(n=4),
    "chain": dict(n=12),
    "protein": dict(n_vertices=10),
    "ldpc": dict(n=12, dv=2, dc=4),
    "stereo": dict(height=4, width=5, n_disp=3),
    "stereo_pair": dict(height=4, width=5, n_disp=3),
}


def _check_pgm(pgm):
    """Structural invariants every zoo PGM must satisfy."""
    nv, ne = int(pgm.n_real_vertices), int(pgm.n_real_edges)
    src = np.asarray(pgm.edge_src)
    dst = np.asarray(pgm.edge_dst)
    rev = np.asarray(pgm.edge_rev)
    emask = np.asarray(pgm.edge_mask)
    smask = np.asarray(pgm.state_mask)
    nstates = np.asarray(pgm.n_states)
    assert int(emask.sum()) == ne
    assert np.all(src[emask] < nv) and np.all(dst[emask] < nv)
    assert np.all(src[emask] >= 0) and np.all(dst[emask] >= 0)
    # directed-pair convention: rev is an involution mapping real edges to
    # real edges, never to themselves
    real = np.flatnonzero(emask)
    assert np.array_equal(rev[rev[real]], real)
    assert np.all(rev[real] != real)
    # state counts in bounds and consistent with the state mask
    assert np.all(nstates[:nv] >= 2)
    assert np.all(nstates <= smask.shape[1])
    assert np.array_equal(smask.sum(axis=1), np.maximum(nstates, 1))
    # positive potentials: finite log-potentials on every valid entry
    lpv = np.asarray(pgm.log_psi_v)
    assert np.all(np.isfinite(lpv[smask]))
    lpe = np.asarray(pgm.log_psi_e)
    valid = (smask[src][:, :, None] & smask[dst][:, None, :]
             & emask[:, None, None])
    assert np.all(lpe[valid] > NEG_INF)
    assert np.all(np.isfinite(lpe[valid]))


class TestZooProperties:
    """Hypothesis sweeps over seeds: structural validity and determinism
    hold for every registered generator, not just the default seeds."""

    # class-scoped: a function-scoped autouse fixture would trip
    # Hypothesis's function_scoped_fixture health check when installed
    @pytest.fixture(autouse=True, scope="class")
    def _require_hypothesis(self):
        pytest.importorskip("hypothesis")

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16),
           kind=st.sampled_from(sorted(_SMALL)))
    def test_generators_structurally_valid(self, seed, kind):
        _check_pgm(WORKLOADS[kind](seed=seed, **_SMALL[kind]))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def test_ldpc_code_is_regular(self, seed):
        inst = ldpc_code(12, dv=2, dc=4, seed=seed)
        counts = np.zeros(inst.n_bits, dtype=int)
        for members in inst.checks:
            assert len(set(members)) == len(members) == 4
            for b in members:
                counts[b] += 1
        assert np.all(counts == 2)          # every bit in exactly dv checks

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def test_stereo_scene_in_bounds(self, seed):
        inst = stereo_mrf(4, 5, 3, seed=seed)
        assert inst.truth.shape == inst.obs.shape == (4, 5)
        assert inst.truth.min() >= 0 and inst.truth.max() < 3
        assert np.all(inst.unary > 0) and np.all(inst.pairwise > 0)
        assert inst.accuracy(inst.truth) == 1.0


class TestZooStructure:
    """Always-run structural checks (no hypothesis dependency)."""

    @pytest.mark.parametrize("kind", sorted(_SMALL))
    def test_default_and_small_instances_valid(self, kind):
        _check_pgm(WORKLOADS[kind](seed=0, **_SMALL[kind]))
        _check_pgm(WORKLOADS[kind](seed=3))

    @pytest.mark.parametrize("kind", sorted(_SMALL))
    def test_deterministic_under_fixed_seed(self, kind):
        a = WORKLOADS[kind](seed=5, **_SMALL[kind])
        b = WORKLOADS[kind](seed=5, **_SMALL[kind])
        for la, lb in zip(jax.tree_util.tree_leaves(a),
                          jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        c = WORKLOADS[kind](seed=6, **_SMALL[kind])
        assert not np.array_equal(np.asarray(a.log_psi_v),
                                  np.asarray(c.log_psi_v))

    def test_zoo_stream_mixes_kinds_and_sizes(self):
        items = list(zoo_stream(11, seed=0))
        kinds = {k for k, _ in items}
        assert kinds == set(list_workloads())
        shapes = {(int(p.n_edges), int(p.n_vertices)) for _, p in items}
        assert len(shapes) > len(kinds)     # sizes vary within kinds too
        with pytest.raises(KeyError):
            list(zoo_stream(2, kinds=["nope"]))
        only = list(zoo_stream(4, kinds=["ldpc", "stereo"]))
        assert {k for k, _ in only} == {"ldpc", "stereo"}

    @pytest.mark.parametrize("kind", ["ldpc", "stereo"])
    def test_pad_pgm_roundtrip_is_inert(self, kind):
        """Padding a zoo graph to its bucket ceilings must not change the
        LBP trajectory on real edges (the serving tier pads every
        request; a generator whose padding leaks would break serving)."""
        pgm = WORKLOADS[kind](seed=1, **_SMALL[kind])
        e, v, s, re_, rv = bucket_shape(pgm, 2.0)
        padded = pad_pgm(pgm, n_edges=e, n_vertices=v, n_states=s,
                         n_real_edges=re_, n_real_vertices=rv)
        assert padded.log_psi_e.shape[0] >= pgm.log_psi_e.shape[0]
        engine = BPEngine(BPConfig(scheduler="lbp", eps=1e-4,
                                   max_rounds=400, history=False))
        a = engine.run(pgm, jax.random.key(0))
        b = engine.run(padded, jax.random.key(0))
        assert int(a.rounds) == int(b.rounds)
        nv, s0 = int(pgm.n_real_vertices), a.beliefs.shape[1]
        np.testing.assert_allclose(np.asarray(b.beliefs)[:nv, :s0],
                                   np.asarray(a.beliefs)[:nv], atol=1e-6)


def _exact_marginal_probs(n_vertices, edges, unary, pairwise, fn):
    margs = fn(n_vertices, edges, unary, pairwise)
    return [np.asarray(m, dtype=np.float64) for m in margs]


class TestLDPCConformance:
    """Max-product decoding of small codes against the exact oracles."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_maxprod_decode_matches_exact_map(self, seed):
        # n=8, dv=2, dc=4: 4 checks of 8 aux states; the joint space is
        # 2^8 * 8^4 ~ 1e6, inside brute force's budget.
        inst = ldpc_code(8, dv=2, dc=4, snr_db=3.0, seed=seed)
        engine = BPEngine(BPConfig(scheduler="lbp", backend="maxprod",
                                   eps=1e-5, max_rounds=600, history=False))
        res = engine.run(inst.pgm, jax.random.key(seed))
        decoded = np.asarray(
            map_assignment(inst.pgm, res.logm))[: inst.n_bits]
        nv, edges, unary, pairwise = inst.raw()
        bf = _exact_marginal_probs(nv, edges, unary, pairwise,
                                   brute_force_marginals)
        ve = _exact_marginal_probs(nv, edges, unary, pairwise, ve_marginals)
        for b, v in zip(bf, ve):            # the two oracles agree
            np.testing.assert_allclose(b, v, atol=1e-8)
        exact_bits = np.array([int(np.argmax(bf[i]))
                               for i in range(inst.n_bits)])
        np.testing.assert_array_equal(decoded, exact_bits)

    def test_decoding_beats_uncoded(self):
        # The benchmark acceptance in miniature: across a few words at
        # moderate SNR, max-product fixes channel errors.
        engine = BPEngine(BPConfig(scheduler="lbp", backend="maxprod",
                                   eps=1e-4, max_rounds=400, history=False))
        coded = uncoded = 0
        for w in range(3):
            inst = ldpc_code(48, snr_db=2.0, seed=1000 * w + 7)
            res = engine.run(inst.pgm, jax.random.key(w))
            decoded = np.asarray(map_assignment(inst.pgm, res.logm))
            coded += inst.coded_errors(decoded)
            uncoded += inst.uncoded_errors
        assert uncoded > 0                  # the channel actually erred
        assert coded < uncoded


class TestStereoConformance:
    """Every registered scheduler's sum-product marginals on a small
    stereo grid match variable elimination within loopy-BP tolerance."""

    @pytest.fixture(scope="class")
    def small_stereo(self):
        inst = stereo_mrf(3, 4, 3, seed=1)
        exact = _exact_marginal_probs(*inst.raw(), ve_marginals)
        return inst, exact

    @pytest.mark.parametrize("sched", list_schedulers())
    def test_marginals_match_ve(self, sched, small_stereo):
        inst, exact = small_stereo
        engine = BPEngine(BPConfig(scheduler=sched, eps=1e-6,
                                   max_rounds=4000, history=False))
        res = engine.run(inst.pgm, jax.random.key(0))
        assert bool(res.converged), f"{sched} did not converge"
        n = inst.height * inst.width
        b = np.asarray(beliefs(inst.pgm, res.logm))[:n, : inst.n_disp]
        b = np.exp(b - b.max(axis=1, keepdims=True))
        b /= b.sum(axis=1, keepdims=True)
        err = max(float(np.abs(b[i] - exact[i]).max()) for i in range(n))
        assert err < 2e-2, f"{sched}: max marginal error {err:.3e}"


class TestStereoDecode:
    """Max-product decoding of a 12x16 stereo grid with 8 disparities, per
    registered scheduler: the decoded labelling's energy is at most the
    ground truth's (MAP is what it optimizes), and its +-1 accuracy is at
    least the rounded observation's."""

    H, W, D = 12, 16, 8

    @pytest.fixture(scope="class")
    def inst(self):
        return stereo_mrf(self.H, self.W, self.D, seed=0)

    @pytest.mark.parametrize("sched", list_schedulers())
    def test_map_energy_and_accuracy_beat_truth_and_observation(self, sched,
                                                                inst):
        engine = BPEngine(BPConfig(scheduler=sched, backend="maxprod",
                                   eps=1e-4, max_rounds=2000, history=False))
        res = engine.run(inst.pgm, jax.random.key(0))
        assert bool(res.converged), f"{sched} did not converge"
        labels = np.asarray(map_assignment(inst.pgm, res.logm))
        labels = labels[: self.H * self.W]
        obs = np.clip(np.round(inst.obs), 0, self.D - 1).astype(int)
        assert inst.energy(labels) <= inst.energy(inst.truth)
        assert inst.accuracy(labels) >= inst.accuracy(obs)


class TestHeterogeneousStream:
    """The tentpole regression: the mixed zoo stream served online is
    bitwise identical per request to solo runs on identically padded
    graphs -- under every admission policy and every routing policy."""

    N = 9

    @pytest.fixture(scope="class")
    def zoo(self):
        stream = [p for _, p in zoo_stream(self.N, seed=0)]
        rng = jax.random.key(0)
        engine = BPEngine(BPConfig(scheduler="lbp", backend="maxprod",
                                   eps=1e-3, max_rounds=256, history=False))
        want = {}
        for rid, pgm in enumerate(stream):
            # Solo reference on the online pipeline's exact padded shape:
            # bucket_shape ceilings with static n_real_* overrides, and
            # the pipeline's fold_in(rng, rid) key.
            e, v, s, re_, rv = bucket_shape(pgm, 2.0)
            padded = pad_pgm(pgm, n_edges=e, n_vertices=v, n_states=s,
                             n_real_edges=re_, n_real_vertices=rv)
            want[rid] = engine.run(padded, jax.random.fold_in(rng, rid))
        return stream, rng, engine, want

    def _check(self, records, want):
        assert sorted(r.rid for r in records) == sorted(want)
        for rec in records:
            w = want[rec.rid]
            assert int(rec.result.rounds) == int(w.rounds)
            assert int(rec.result.updates) == int(w.updates)
            np.testing.assert_array_equal(np.asarray(rec.result.logm),
                                          np.asarray(w.logm))

    @pytest.mark.parametrize("policy", list_admission_policies())
    def test_each_admission_policy_bitwise_vs_solo(self, policy, zoo):
        stream, rng, engine, want = zoo
        rep = serve_async(engine, iter(stream), rng, admission=policy,
                          max_batch=3, chunk_rounds=32, prefetch=4, slots=2)
        self._check(rep.records, want)

    @pytest.mark.parametrize("routing", list_routing_policies())
    @pytest.mark.parametrize("steal", [False, True])
    def test_each_routing_policy_bitwise_vs_solo(self, routing, steal, zoo):
        stream, rng, engine, want = zoo
        engines = [BPEngine(engine.config) for _ in range(2)]
        rep = serve_routed(engines, iter(stream), rng, routing=routing,
                           steal=steal, max_batch=3, chunk_rounds=32,
                           prefetch=4, slots=2)
        self._check(rep.records, want)
