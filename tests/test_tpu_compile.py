"""Compile the fused message-update kernel for a TPU v5e that is described,
not attached. Nothing runs: each test lowers and compiles with the TPU
compiler and checks that the kernel is in the compiled program
(``tpu_custom_call``) rather than an interpreter loop. The shapes are the
main path's real widths: Ising 200x200 (S=2), the Tsukuba-size stereo MRF
(S=16), LDPC check vertices (S=32), protein-like graphs (S=47), and a
4-graph bucket through the batch fold; the max-product body at the
Tsukuba-size stereo pair's and the Ising grid's widths; and the engine's
whole round loop on the Ising 200x200 grid, whose compiled ``while`` body
must not gather the source-state mask.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the test workers all import
every test module. The persistent compilation cache is off while these
compiles run: a TPU executable written to it could not be read back here.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.pgm import ising_grid_fast, stereo_graph  # first: repro.core
from repro.core import BPConfig, BPEngine, BatchedPGM
from repro.core import engine as E
from repro.kernels.message_update import fused_update_t
from repro.kernels.ops import make_pallas_update

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


#: (S, E) of the max-product body's calls at real sizes.
MAX_SHAPES = {
    "stereo_tsukuba": (16, 441_088),
    "ising200": (2, 159_232),
}


@pytest.mark.parametrize("name", sorted(MAX_SHAPES))
def test_max_body_compiles_for_v5e(one_chip, name):
    s, e = MAX_SHAPES[name]
    f32 = jnp.float32
    args = (_spec((s, s, e), f32, one_chip), _spec((s, e), f32, one_chip),
            _spec((s, e), f32, one_chip), _spec((s, e), jnp.bool_, one_chip))
    compiled = fused_update_t.lower(*args, interpret=False,
                                    semiring="max").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bucket_fold_compiles_for_v5e(one_chip):
    """A bucket of 4 stereo graphs folds into one kernel launch: the
    engine's disjoint-union fold through the single-graph update."""
    pgm = stereo_graph(seed=0, height=12, width=16, n_disp=8)
    bpgm = jax.tree.map(
        lambda x: _spec((4,) + x.shape, x.dtype, one_chip), pgm)
    logm = _spec((4 * pgm.n_edges, pgm.n_states_max), jnp.float32, one_chip)
    update = make_pallas_update(interpret=False)
    fold = jax.jit(lambda p, m: update(BatchedPGM(p).folded(), m))
    compiled = fold.lower(bpgm, logm).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _computations(text):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            name, out[head.group(1)] = head.group(1), []
        elif line.startswith("}"):
            name = None
        elif name:
            out[name].append(line)
    return out


def test_ising_round_loop_has_no_source_mask_gather(one_chip):
    """The v5e-compiled Ising 200x200 round loop: its ``while`` body holds
    the three f32 (E, 2) gathers of ``pre`` (``log_psi_v`` and ``vsum``
    through ``edge_src``, ``logm[edge_rev]``) and no pred (E, 2) gather: the
    source-state mask is gathered before the loop."""
    pgm = ising_grid_fast(200, 2.0, seed=0)
    eng = BPEngine(BPConfig(scheduler="rnbp", eps=1e-3, max_rounds=2000,
                            backend=make_pallas_update(interpret=False)))
    state = eng.init(pgm, jax.random.key(0))
    spec = lambda x: _spec(x.shape, x.dtype, one_chip)  # noqa: E731
    cfg = eng.config
    text = E._chunk_single.lower(
        jax.tree.map(spec, pgm), jax.tree.map(spec, E._carry_of(state)),
        spec(state.rounds), cfg.eps, scheduler=eng.scheduler,
        damping=cfg.damping, update_fn=eng.update_fn,
        track_history=cfg.history).compile().as_text()
    assert "tpu_custom_call" in text
    comps = _computations(text)
    body = re.search(r" while\(.*body=%([^,\s]+)", text).group(1)
    fused = [c for line in comps[body]
             for c in re.findall(r"calls=%([^,\s]+)", line)]
    rows = f"[{pgm.n_edges},{pgm.n_states_max}]"
    gathers = [re.match(r"\s*%\S+ = (\w+)\[", line).group(1)
               for c in [body] + fused for line in comps[c]
               if f"{rows}{{" in line and " gather(" in line]
    assert sorted(gathers) == ["f32", "f32", "f32"], gathers
