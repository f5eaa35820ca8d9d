"""Cross-backend differential conformance: every registered update backend
x every registered scheduler, pinned against the reference semantics.

The registries are enumerated dynamically (``list_backends()`` x
``list_schedulers()``), so a newly registered backend or scheduler is
conformance-tested by existence -- forgetting to test it is impossible.
Per-pair runs rotate through a mixed corpus (ising grid, chain, LDPC
decoder graph, stereo MRF) chosen so every scheduler converges on every
graph; across the matrix every graph kind meets every backend.

Oracles and tolerances are per-backend:

- ``ref`` IS the sum-product reference -- conformance is bitwise.
- ``pallas`` / ``triton`` (interpret mode) reassociate reductions inside
  the fused kernel, so beliefs match to ~1e-4 and round counts to a small
  drift (residual-threshold crossings can shift by ulps).
- ``sharded`` adds a cross-device edge split on top -- 5e-3.
- ``maxprod`` IS the max-product reference (pure jnp); ``pallas_max``, the
  TPU kernel's max body, is compared against it. Max reductions are
  order-exact, so agreement is near-bitwise. A backend's semiring is read
  from ``SEMIRING`` alone.

Also here: the chunked-resume bitwise contract per backend (N rounds via
``step`` == N rounds in one ``run``), serving-stack parity for the triton
backend, and hypothesis fuzz of the kernel pair over degenerate shapes
(S=2, non-power-of-two S, E=1, E below one block, all-masked edges).
``hypothesis`` is an optional extra: without it the fuzz class skips and
the explicit degenerate-shape tests still run.
"""

import jax
import jax.numpy as jnp
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
        integers = floats = booleans = staticmethod(lambda *a, **k: None)

from repro.core import BPConfig, BPEngine
from repro.core import messages as M
from repro.core.graph import NEG_INF
from repro.core.schedulers import list_schedulers
from repro.kernels.message_update import fused_update_t
from repro.kernels.ops import list_backends
from repro.kernels.ref import fused_update_e_ref, fused_update_t_ref
from repro.kernels.triton_update import fused_update_e
from repro.pgm import chain_graph, ising_grid, ldpc_graph, stereo_graph

EPS, MAX_ROUNDS = 1e-3, 2000

#: graph kind -> factory; all six schedulers converge on each (pinned by
#: test_corpus_converges_everywhere below).
CORPUS = {
    "ising": lambda: ising_grid(5, 1.5, seed=0),
    "chain": lambda: chain_graph(30, seed=1),
    "ldpc": lambda: ldpc_graph(seed=0, n=24, dv=3, dc=6, snr_db=3.0),
    "stereo": lambda: stereo_graph(seed=0, height=4, width=5, n_disp=4),
}

#: backend -> (belief atol, rounds must match exactly). The "trajectory"
#: claim: exact backends reproduce the reference round-for-round; kernel
#: backends may shift threshold crossings by reassociation ulps.
TOLERANCE = {
    "ref": (0.0, True),
    "maxprod": (1e-6, True),
    "pallas_max": (1e-6, True),
    "pallas": (1e-4, False),
    "triton": (1e-4, False),
    "sharded": (5e-3, False),
}

#: The max-product backends; every other backend is sum-product.
SEMIRING = {"maxprod": "max", "pallas_max": "max"}


def semiring_of(backend: str) -> str:
    return SEMIRING.get(backend, "sum")


BACKENDS = list_backends()
SCHEDULERS = list_schedulers()

_pgm_cache = {}
_oracle_cache = {}


def corpus_pgm(gname):
    if gname not in _pgm_cache:
        _pgm_cache[gname] = CORPUS[gname]()
    return _pgm_cache[gname]


def _run(backend, scheduler, gname):
    eng = BPEngine(BPConfig(scheduler=scheduler, eps=EPS,
                            max_rounds=MAX_ROUNDS, history=False,
                            backend=backend))
    return eng.run(corpus_pgm(gname), jax.random.key(0))


def oracle_result(scheduler, gname, semiring):
    """Reference trajectory for (scheduler, graph): the pure-jnp update of
    the matching semiring. Cached -- many matrix cells share an oracle."""
    key = (scheduler, gname, semiring)
    if key not in _oracle_cache:
        backend = "ref" if semiring == "sum" else "maxprod"
        _oracle_cache[key] = _run(backend, scheduler, gname)
    return _oracle_cache[key]


class TestBackendSchedulerMatrix:
    """Every (backend, scheduler) pair runs a corpus graph (rotating, so
    all four graph kinds are exercised against every backend) and must
    reproduce the matching-semiring reference beliefs and trajectory."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("scheduler", SCHEDULERS)
    def test_backend_matches_reference(self, backend, scheduler):
        semiring = semiring_of(backend)
        # Max-product oscillates forever on the loopy ising grid (ties in
        # the max make the fixed point unstable) -- a semiring property,
        # not a backend bug -- so the max rotation skips that graph.
        gnames = [g for g in CORPUS if g != "ising"] \
            if semiring == "max" else list(CORPUS)
        gname = gnames[(BACKENDS.index(backend)
                        + SCHEDULERS.index(scheduler)) % len(gnames)]
        res = _run(backend, scheduler, gname)
        ref = oracle_result(scheduler, gname, semiring)
        atol, exact_rounds = TOLERANCE[backend]
        assert bool(res.converged) and bool(ref.converged)
        if exact_rounds:
            assert int(res.rounds) == int(ref.rounds)
        else:
            drift = max(10, int(ref.rounds) // 5)
            assert abs(int(res.rounds) - int(ref.rounds)) <= drift
        if atol == 0.0:
            np.testing.assert_array_equal(np.asarray(res.logm),
                                          np.asarray(ref.logm))
            np.testing.assert_array_equal(np.asarray(res.beliefs),
                                          np.asarray(ref.beliefs))
        else:
            np.testing.assert_allclose(np.asarray(res.beliefs),
                                       np.asarray(ref.beliefs), atol=atol)

    def test_matrix_is_complete(self):
        """The enumeration really covers the live registries (a regression
        here means a backend/scheduler was registered but not conformed)."""
        assert set(BACKENDS) >= {"ref", "maxprod", "pallas", "pallas_max",
                                 "triton", "sharded"}
        assert set(SCHEDULERS) >= {"lbp", "rbp", "rlx", "rlxtree", "rnbp",
                                   "rs"}
        assert set(TOLERANCE) >= set(BACKENDS)

    def test_corpus_converges_everywhere(self):
        """Corpus admission gate: all schedulers converge on all graphs
        under the reference backend (a corpus graph that stops converging
        would silently weaken every matrix cell)."""
        for gname in CORPUS:
            for scheduler in SCHEDULERS:
                res = oracle_result(scheduler, gname, "sum")
                assert bool(res.converged), (gname, scheduler)


class TestChunkedResumePerBackend:
    """The engine's resume contract, per backend: N rounds via repeated
    7-round ``step`` chunks are bit-identical to N rounds in one ``run``."""

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_chunked_equals_monolithic(self, backend):
        gname = "ldpc" if semiring_of(backend) == "max" else "ising"
        pgm = corpus_pgm(gname)
        eng = BPEngine(BPConfig(scheduler="rs", eps=EPS,
                                max_rounds=MAX_ROUNDS, backend=backend))
        mono = eng.run(pgm, jax.random.key(7))
        state = eng.init(pgm, jax.random.key(7))
        while not eng.finished(state):
            state = eng.step(state, chunk_rounds=7)
        chunked = eng.result(state)
        np.testing.assert_array_equal(np.asarray(mono.logm),
                                      np.asarray(chunked.logm))
        np.testing.assert_array_equal(np.asarray(mono.beliefs),
                                      np.asarray(chunked.beliefs))
        assert int(mono.rounds) == int(chunked.rounds)
        assert int(mono.updates) == int(chunked.updates)


class TestTritonServingStack:
    """``BPConfig(backend="triton")`` through the serving layers."""

    def _stream(self):
        return [ising_grid(5, 1.5, seed=s) for s in range(4)]

    def test_serve_matches_ref_backend(self):
        rng = jax.random.key(3)
        outs = {}
        for backend in ("ref", "triton"):
            eng = BPEngine(BPConfig(scheduler="rbp", eps=EPS,
                                    max_rounds=MAX_ROUNDS, history=False,
                                    backend=backend))
            outs[backend] = eng.serve(self._stream(), rng).results
        for r_ref, r_tri in zip(outs["ref"], outs["triton"]):
            assert bool(r_ref.converged) and bool(r_tri.converged)
            np.testing.assert_allclose(np.asarray(r_tri.beliefs),
                                       np.asarray(r_ref.beliefs), atol=1e-4)


def _edge_major_operands(rng, e, s, *, all_masked_frac=0.0):
    logpsi = rng.standard_normal((e, s, s)).astype(np.float32)
    pre = rng.standard_normal((e, s)).astype(np.float32)
    nvalid = rng.integers(1, s + 1, size=e)
    dmask = (np.arange(s)[None, :] < nvalid[:, None])
    if all_masked_frac:
        dmask[rng.random(e) < all_masked_frac] = False
    logm = np.where(dmask, rng.standard_normal((e, s)), NEG_INF)
    return (jnp.asarray(logpsi), jnp.asarray(pre),
            jnp.asarray(logm.astype(np.float32)), jnp.asarray(dmask))


class TestDegenerateShapes:
    """Explicit (always-run) pins on the shapes the padding logic must get
    right: single edge, sub-block edge counts, non-power-of-two states."""

    @pytest.mark.parametrize("e,s", [(1, 2), (3, 2), (17, 5), (100, 17),
                                     (128, 2), (130, 4)])
    @pytest.mark.parametrize("semiring", ["sum", "max"])
    def test_gpu_kernel_vs_oracle(self, e, s, semiring):
        rng = np.random.default_rng(e * 100 + s)
        ops = _edge_major_operands(rng, e, s)
        new_k, r_k = fused_update_e(*ops, semiring=semiring, interpret=True)
        new_r, r_r = fused_update_e_ref(*ops, semiring=semiring)
        assert new_k.shape == (e, s)
        dmask = np.asarray(ops[3])
        np.testing.assert_allclose(
            np.where(dmask, np.asarray(new_k), 0.0),
            np.where(dmask, np.asarray(new_r), 0.0), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("semiring", ["sum", "max"])
    def test_all_masked_edges_inert(self, semiring):
        """Fully masked edges (the padded-lane contract): NEG_INF messages
        and exactly zero residual, both semirings."""
        rng = np.random.default_rng(0)
        ops = _edge_major_operands(rng, 40, 4, all_masked_frac=0.5)
        dead = ~np.asarray(ops[3]).any(axis=1)
        assert dead.any()          # the fraction actually produced some
        new, r = fused_update_e(*ops, semiring=semiring, interpret=True)
        new, r = np.asarray(new), np.asarray(r)
        assert np.all(new[dead] == np.float32(NEG_INF))
        assert np.all(r[dead] == 0.0)

    def test_gpu_vs_tpu_kernel_differential(self):
        """The two kernels are layout transposes of the same math: same
        operands (transposed) must give the same messages and residuals."""
        rng = np.random.default_rng(42)
        e, s = 200, 7
        logpsi, pre, logm, dmask = _edge_major_operands(rng, e, s)
        new_e, r_e = fused_update_e(logpsi, pre, logm, dmask, interpret=True)
        new_t, r_t = fused_update_t(
            jnp.transpose(logpsi, (1, 2, 0)), pre.T, logm.T, dmask.T,
            interpret=True)
        np.testing.assert_allclose(np.asarray(new_e),
                                   np.asarray(new_t).T, atol=1e-6)
        np.testing.assert_allclose(np.asarray(r_e), np.asarray(r_t),
                                   atol=1e-6)


class TestKernelFuzz:
    """Hypothesis sweep of the (shape, seed) space for both kernels and
    both semirings against the pure-jnp oracles."""

    @pytest.fixture(autouse=True, scope="class")
    def _require_hypothesis(self):
        pytest.importorskip("hypothesis")

    @settings(max_examples=30, deadline=None)
    @given(s=st.integers(2, 17), e=st.integers(1, 200),
           seed=st.integers(0, 2**16), maxprod=st.booleans())
    def test_gpu_kernel_fuzz(self, s, e, seed, maxprod):
        rng = np.random.default_rng(seed)
        semiring = "max" if maxprod else "sum"
        ops = _edge_major_operands(rng, e, s, all_masked_frac=0.1)
        new_k, r_k = fused_update_e(*ops, semiring=semiring, interpret=True)
        new_r, r_r = fused_update_e_ref(*ops, semiring=semiring)
        dmask = np.asarray(ops[3])
        np.testing.assert_allclose(
            np.where(dmask, np.asarray(new_k), 0.0),
            np.where(dmask, np.asarray(new_r), 0.0), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                                   atol=1e-5, rtol=1e-5)
        assert np.all(np.asarray(new_k)[~dmask] == np.float32(NEG_INF))

    @settings(max_examples=20, deadline=None)
    @given(s=st.integers(2, 17), e=st.integers(1, 200),
           seed=st.integers(0, 2**16))
    def test_tpu_kernel_fuzz(self, s, e, seed):
        rng = np.random.default_rng(seed)
        logpsi, pre, logm, dmask = _edge_major_operands(rng, e, s)
        ops_t = (jnp.transpose(logpsi, (1, 2, 0)), pre.T, logm.T, dmask.T)
        new_k, r_k = fused_update_t(*ops_t, interpret=True)
        new_r, r_r = fused_update_t_ref(*ops_t)
        dm = np.asarray(dmask).T
        np.testing.assert_allclose(
            np.where(dm, np.asarray(new_k), 0.0),
            np.where(dm, np.asarray(new_r), 0.0), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                                   atol=1e-5, rtol=1e-5)
