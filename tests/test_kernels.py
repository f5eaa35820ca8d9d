"""Pallas kernel validation: interpret-mode vs pure-jnp oracle across
shape/dtype sweeps + hypothesis property tests on kernel semantics.

``hypothesis`` is an optional test extra: without it the property-test
class skips (via ``pytest.importorskip``) and the oracle tests still run.
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
        integers = floats = staticmethod(lambda *a, **k: None)

from repro.core import messages as M
from repro.core.graph import NEG_INF
from repro.kernels.message_update import fused_update_t, pick_block_edges
from repro.kernels import explog, ops
from repro.kernels.ops import get_update_fn, make_pallas_update, pallas_update
from repro.kernels.ref import fused_update_t_ref
from repro.pgm import ising_grid, protein_like_graph


def _rand_operands(rng, s, e, dtype=jnp.float32):
    logpsi = rng.standard_normal((s, s, e)).astype(np.float32)
    pre = rng.standard_normal((s, e)).astype(np.float32)
    # valid-state masks: at least 1 valid state per edge
    nvalid = rng.integers(1, s + 1, size=e)
    dmask = (np.arange(s)[:, None] < nvalid[None, :])
    logm = np.where(dmask, rng.standard_normal((s, e)), NEG_INF)
    return (jnp.asarray(logpsi, dtype), jnp.asarray(pre, dtype),
            jnp.asarray(logm, dtype), jnp.asarray(dmask))


SHAPES = [(2, 128), (2, 256), (3, 128), (8, 384), (17, 128), (51, 256),
          (81, 128), (96, 128)]


class TestKernelVsOracle:
    @pytest.mark.parametrize("s,e", SHAPES)
    def test_allclose_f32(self, s, e):
        rng = np.random.default_rng(s * 1000 + e)
        ops = _rand_operands(rng, s, e)
        new_k, r_k = fused_update_t(*ops, interpret=True)
        new_r, r_r = fused_update_t_ref(*ops)
        dmask = np.asarray(ops[3])
        np.testing.assert_allclose(
            np.where(dmask, np.asarray(new_k), 0.0),
            np.where(dmask, np.asarray(new_r), 0.0), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("s,e", [(2, 128), (8, 256)])
    def test_allclose_bf16_operands(self, s, e):
        """bf16 messages (serving-precision BP) still match the oracle."""
        rng = np.random.default_rng(7)
        ops = _rand_operands(rng, s, e, dtype=jnp.bfloat16)
        new_k, r_k = fused_update_t(*ops, interpret=True)
        new_r, r_r = fused_update_t_ref(*ops)
        dmask = np.asarray(ops[3])
        np.testing.assert_allclose(
            np.where(dmask, np.asarray(new_k, np.float32), 0.0),
            np.where(dmask, np.asarray(new_r, np.float32), 0.0),
            atol=3e-2, rtol=3e-2)

    def test_unpadded_edge_count(self):
        """E not a multiple of the block: internal padding must be inert."""
        rng = np.random.default_rng(11)
        ops = _rand_operands(rng, 4, 130)  # 130 not a lane multiple
        new_k, r_k = fused_update_t(*ops, interpret=True)
        new_r, r_r = fused_update_t_ref(*ops)
        assert new_k.shape == (4, 130)
        np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                                   atol=1e-5)

    def test_block_picker_vmem_budget(self):
        for s in [2, 8, 32, 81, 96]:
            blk = pick_block_edges(s)
            assert blk % 128 == 0 and blk >= 128
            ws = (s * s + 4 * s + 2) * blk * 4
            assert ws <= 4 * 1024 * 1024 * 2  # within 2x of budget


class TestKernelInBP:
    def test_pallas_update_equals_ref_update(self):
        for make in [lambda: ising_grid(12, 2.5, seed=2),
                     lambda: protein_like_graph(50, seed=2)]:
            pgm = make()
            logm = M.init_messages(pgm)
            for _ in range(2):
                cand, _ = M.ref_update(pgm, logm)
                logm = M.apply_frontier(logm, cand, pgm.edge_mask)
            c_r, r_r = M.ref_update(pgm, logm)
            c_k, r_k = pallas_update(pgm, logm, interpret=True)
            mask = np.asarray(pgm.state_mask[pgm.edge_dst])
            np.testing.assert_allclose(
                np.where(mask, np.asarray(c_k), 0.0),
                np.where(mask, np.asarray(c_r), 0.0), atol=1e-5)
            np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r),
                                       atol=1e-5)

    def test_e2e_run_bp_with_kernel(self):
        """Kernel-backed BP reaches the reference fixed point. Trajectories
        may differ by a few rounds: the fused kernel's normalize/residual
        reassociates reductions, and at eps=1e-5 ulp-level differences can
        shift residual-threshold crossings (pre-existing; masked while this
        module failed at collection)."""
        from repro.core import RnBP, run_bp
        pgm = ising_grid(10, 2.5, seed=3)
        r_ref = run_bp(pgm, RnBP(low_p=0.7), jax.random.key(0), eps=1e-5)
        r_k = run_bp(pgm, RnBP(low_p=0.7), jax.random.key(0), eps=1e-5,
                     update_fn=make_pallas_update(True))
        assert bool(r_ref.converged) and bool(r_k.converged)
        assert abs(int(r_ref.rounds) - int(r_k.rounds)) \
            <= max(10, int(r_ref.rounds) // 10)
        # both stop when every residual < eps; beliefs sum ~degree messages,
        # so the fixed points agree to ~degree * eps
        np.testing.assert_allclose(np.asarray(r_ref.beliefs),
                                   np.asarray(r_k.beliefs), atol=1e-4)


class TestInterpretRule:
    """Where each fused kernel interprets: on the CPU both do (the tests'
    path); on an accelerator only that platform's own kernel runs, and
    asking for the other one raises an error naming the right backend."""

    @pytest.mark.parametrize("backend", ["pallas", "triton"])
    def test_cpu_interprets(self, backend):
        assert jax.default_backend() == "cpu"
        pgm = ising_grid(4, 1.5, seed=0)
        logm = M.init_messages(pgm)
        cand, resid = get_update_fn(backend)(pgm, logm)
        c_r, r_r = M.ref_update(pgm, logm)
        mask = np.asarray(pgm.state_mask[pgm.edge_dst])
        np.testing.assert_allclose(np.where(mask, np.asarray(cand), 0.0),
                                   np.where(mask, np.asarray(c_r), 0.0),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(resid), np.asarray(r_r),
                                   atol=1e-5)

    @pytest.mark.parametrize("platform,own,other",
                             [("tpu", "pallas", "triton"),
                              ("gpu", "triton", "pallas")])
    def test_accelerator_runs_only_its_kernel(self, monkeypatch, platform,
                                              own, other):
        monkeypatch.setattr(ops, "_platform", lambda: platform)
        assert ops._resolve_interpret(own, None) is False
        with pytest.raises(RuntimeError, match=repr(own)):
            get_update_fn(other)
        # an explicit interpret= still wins
        assert callable(get_update_fn(other, interpret=True))


class TestExpLog:
    """``repro.kernels.explog`` against float64 numpy: within about an ulp
    on the ranges BP feeds it (the TPU's own f32 exp/log are not)."""

    @pytest.mark.parametrize("fn,ref,lo,hi,log_scale", [
        (explog.exp, np.exp, -87.0, 0.0, False),  # LSE shifts: x - max <= 0
        (explog.exp, np.exp, 0.0, 88.0, False),
        (explog.log, np.log, 1.0, 128.0, False),  # sums of <= S terms >= 1
        (explog.log, np.log, -37.0, 0.0, True),   # 1e-37 .. 1
    ])
    def test_within_an_ulp(self, rng, fn, ref, lo, hi, log_scale):
        x = rng.uniform(lo, hi, size=1 << 16)
        x = (10.0 ** x if log_scale else x).astype(np.float32)
        got = np.asarray(jax.jit(fn)(x), np.float64)
        want = ref(x.astype(np.float64))
        ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
        assert np.max(np.abs(got - want) / ulp) <= 1.5

    def test_domain_edges(self):
        e = np.asarray(explog.exp(jnp.array([NEG_INF, -88.0, 0.0],
                                            jnp.float32)))
        assert e[0] == 0.0 and e[1] == 0.0 and e[2] == 1.0
        lg = np.asarray(explog.log(jnp.array([0.0, 1e-40, 1.0], jnp.float32)))
        floor = np.log(np.float32(np.finfo(np.float32).tiny))
        np.testing.assert_allclose(lg[:2], floor, rtol=1e-6)
        assert lg[2] == 0.0

    def test_keeps_dtype(self):
        x = jnp.linspace(-4.0, 0.0, 64, dtype=jnp.bfloat16)
        assert explog.exp(x).dtype == jnp.bfloat16
        assert explog.log(-x + 1).dtype == jnp.bfloat16


class TestKernelProperties:
    """Hypothesis property tests on the fused-update contract."""

    # class-scoped: a function-scoped autouse fixture would trip
    # Hypothesis's function_scoped_fixture health check when it IS installed
    @pytest.fixture(autouse=True, scope="class")
    def _require_hypothesis(self):
        pytest.importorskip("hypothesis")

    @settings(max_examples=25, deadline=None)
    @given(s=st.integers(2, 12), seed=st.integers(0, 2**16),
           scale=st.floats(0.1, 20.0))
    def test_output_normalized_and_residual_nonneg(self, s, seed, scale):
        rng = np.random.default_rng(seed)
        e = 128
        logpsi = (scale * rng.standard_normal((s, s, e))).astype(np.float32)
        pre = (scale * rng.standard_normal((s, e))).astype(np.float32)
        nvalid = rng.integers(1, s + 1, size=e)
        dmask = (np.arange(s)[:, None] < nvalid[None, :])
        logm = np.where(dmask, rng.standard_normal((s, e)), NEG_INF)
        new, r = fused_update_t(jnp.asarray(logpsi), jnp.asarray(pre),
                                jnp.asarray(logm.astype(np.float32)),
                                jnp.asarray(dmask), interpret=True)
        new = np.asarray(new, np.float64)
        # (1) normalized over valid states (f32 LSE at scale 20 -> ~1e-3)
        z = np.sum(np.where(dmask, np.exp(new), 0.0), axis=0)
        np.testing.assert_allclose(z, 1.0, atol=2e-3)
        # (2) invalid states carry the log(0) sentinel (f32-rounded)
        assert np.all(new[~dmask] == np.float64(np.float32(NEG_INF)))
        # (3) residuals non-negative and finite
        r = np.asarray(r)
        assert np.all(r >= 0) and np.all(np.isfinite(r))

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def test_idempotent_at_fixed_point(self, seed):
        """Feeding back the kernel's own output as messages yields residual
        == 0 only if inputs unchanged -- here: residual of (new vs new) = 0."""
        rng = np.random.default_rng(seed)
        s, e = 4, 128
        ops = _rand_operands(rng, s, e)
        new, _ = fused_update_t(*ops, interpret=True)
        _, r2 = fused_update_t(ops[0], ops[1], new, ops[3], interpret=True)
        r_self = np.asarray(fused_update_t(ops[0], ops[1], new, ops[3],
                                           interpret=True)[0])
        np.testing.assert_allclose(np.asarray(r2),
                                   np.max(np.where(np.asarray(ops[3]),
                                                   np.abs(r_self
                                                          - np.asarray(new)),
                                                   0.0), axis=0), atol=1e-5)
