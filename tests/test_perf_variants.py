"""Optimized-path equivalence: the SSPerf variants must compute the same
math as the baselines they replace."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import build_model
from repro.models.layers.moe import init_moe, moe


class TestMoEDispatchParity:
    def _setup(self, e=8, d=32, ff=16, t=64, k=2, seed=0):
        key = jax.random.key(seed)
        p = init_moe(key, d, e, ff)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, t // 2, d),
                              jnp.float32)
        return p, x, e, k

    def test_dense_equals_ragged(self):
        p, x, e, k = self._setup()
        out_r, aux_r = moe(p, x, n_experts=e, top_k=k, dispatch="ragged")
        out_d, aux_d = moe(p, x, n_experts=e, top_k=k, dispatch="dense")
        np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_d),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(float(aux_r[0]), float(aux_d[0]),
                                   rtol=1e-5)

    def test_sharded_equals_ragged_subprocess(self, run_on_cpu_devices):
        """sharded dispatch on 4 fake devices == ragged on one."""
        code = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.models.layers.moe import init_moe, moe, set_shard_mesh

mesh = jax.make_mesh((2, 2), ("data", "model"))
set_shard_mesh(mesh)
key = jax.random.key(0)
e, d, ff, t, k = 8, 32, 16, 64, 2
p = init_moe(key, d, e, ff)
x = jax.random.normal(jax.random.fold_in(key, 1), (2, t // 2, d),
                      jnp.float32)
out_r, _ = moe(p, x, n_experts=e, top_k=k, dispatch="ragged")
with mesh:
    out_s, _ = jax.jit(lambda p, x: moe(p, x, n_experts=e, top_k=k,
                                        dispatch="sharded"))(p, x)
np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_s),
                           atol=1e-4, rtol=1e-4)
print("OK")
"""
        run_on_cpu_devices(code, n_devices=4, timeout=600)


class TestBandedBP:
    def test_banded_matches_reference_subprocess(self, run_on_cpu_devices):
        code = r"""
import jax, numpy as np
from repro.core import LBP, run_bp
from repro.pgm import ising_grid_fast, chain_graph
from repro.dist import make_bp_mesh
from repro.dist.bp_banded import partition_banded, run_bp_banded

mesh = make_bp_mesh(8)
for pgm in [ising_grid_fast(24, 2.5, seed=0), chain_graph(2000, seed=0)]:
    ref = run_bp(pgm, LBP(), jax.random.key(0), eps=1e-5, max_rounds=6000)
    part = partition_banded(pgm, 8)
    logm, rounds, done = run_bp_banded(part, LBP(), mesh,
                                       jax.random.key(0), eps=1e-5,
                                       max_rounds=6000)
    assert bool(done), "banded LBP did not converge"
    # LBP is deterministic: identical round count == identical trajectory
    assert int(rounds) == int(ref.rounds), (int(rounds), int(ref.rounds))
print("OK")
"""
        run_on_cpu_devices(code, timeout=600)

    def test_partition_rejects_unbanded(self):
        from repro.dist.bp_banded import partition_banded
        from repro.pgm import protein_like_graph
        pgm = protein_like_graph(60, seed=0)  # irregular spatial graph
        with pytest.raises(AssertionError):
            partition_banded(pgm, 32)

    def test_banded_relaxed_converges_subprocess(self, run_on_cpu_devices):
        # rlx/rlxtree are first-class on the banded path: shard-local
        # per-queue top-k, no global sort. Same 8-fake-device subprocess
        # pattern as the LBP parity test above.
        code = r"""
import jax
from repro.core import RLX, RLXTree
from repro.pgm import ising_grid_fast
from repro.dist import make_bp_mesh
from repro.dist.bp_banded import partition_banded, run_bp_banded

mesh = make_bp_mesh(8)
pgm = ising_grid_fast(24, 2.5, seed=0)
part = partition_banded(pgm, 8)
for sched in [RLX(), RLXTree()]:
    logm, rounds, done = run_bp_banded(part, sched, mesh, jax.random.key(0),
                                       eps=1e-4, max_rounds=10000)
    assert bool(done), f"banded {type(sched).__name__} did not converge"
print("OK")
"""
        run_on_cpu_devices(code, timeout=600)

    def test_banded_unsupported_scheduler_error_lists_rlx(self):
        # exact sort-based schedulers are rejected with the uniform
        # registry-style message that names the supported subset
        from repro.core import RBP, RS
        from repro.dist import make_bp_mesh
        from repro.dist.bp_banded import partition_banded, run_bp_banded
        from repro.pgm import ising_grid_fast
        mesh = make_bp_mesh(1)
        part = partition_banded(ising_grid_fast(6, 1.0, seed=0), 1)
        for sched in (RBP(), RS()):
            with pytest.raises(NotImplementedError) as ei:
                run_bp_banded(part, sched, mesh, jax.random.key(0))
            msg = str(ei.value)
            assert "unknown banded scheduler" in msg
            assert "'rlx'" in msg and "'rlxtree'" in msg
            assert "'lbp'" in msg and "'rnbp'" in msg


class TestFSDPShardings:
    def test_fsdp_param_rules(self):
        from jax.sharding import PartitionSpec as P
        from repro.launch.sharding import _fsdp_pspec

        class E:
            def __init__(self, k):
                self.key = k
        leaf = jax.ShapeDtypeStruct((12288, 28672), jnp.float32)
        spec = _fsdp_pspec((E("w_in"),), leaf, ("data", "model"), 256, False)
        assert spec == P(None, ("data", "model"))   # output dim sharded
        small = jax.ShapeDtypeStruct((12288,), jnp.float32)
        assert _fsdp_pspec((E("ln1"),), small, ("data", "model"), 256,
                           False) == P(None)        # small leaf replicated
