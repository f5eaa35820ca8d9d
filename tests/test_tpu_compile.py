"""Compile the fused message-update kernel for a TPU v5e that is described,
not attached. Nothing runs: each test lowers and compiles with the TPU
compiler and checks that the kernel is in the compiled program
(``tpu_custom_call``) rather than an interpreter loop. The shapes are the
main path's real widths: Ising 200x200 (S=2), the Tsukuba-size stereo MRF
(S=16), LDPC check vertices (S=32), protein-like graphs (S=47), and a
4-graph bucket through the batch fold.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
every test module. The persistent compilation cache is off while these
compiles run: a TPU executable written to it could not be read back here.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.pgm import stereo_graph  # first: it initializes repro.core
from repro.kernels.message_update import fused_update_t
from repro.kernels.ops import pallas_update_batch

#: (S, E): states and edges of the kernel call at real sizes.
KERNEL_SHAPES = {
    "ising200": (2, 159_232),
    "stereo_tsukuba": (16, 441_088),
    "ldpc_checks": (32, 4_096),
    "protein": (47, 20_000),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("name", sorted(KERNEL_SHAPES))
def test_fused_update_compiles_for_v5e(one_chip, name):
    s, e = KERNEL_SHAPES[name]
    f32 = jnp.float32
    args = (_spec((s, s, e), f32, one_chip), _spec((s, e), f32, one_chip),
            _spec((s, e), f32, one_chip), _spec((s, e), jnp.bool_, one_chip))
    compiled = fused_update_t.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bucket_fold_compiles_for_v5e(one_chip):
    """A bucket of 4 stereo graphs folds into one kernel launch."""
    pgm = stereo_graph(seed=0, height=12, width=16, n_disp=8)
    bpgm = jax.tree.map(
        lambda x: _spec((4,) + x.shape, x.dtype, one_chip), pgm)
    logm = _spec((4, pgm.n_edges, pgm.n_states_max), jnp.float32, one_chip)
    compiled = pallas_update_batch.lower(bpgm, logm,
                                         interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
