"""Multi-device BP: shard the folded edge axis over a JAX mesh.

The paper saturates one device by exposing more parallelism per BP round;
this subsystem takes the next axis -- *multiple* devices -- by sharding the
directed-edge dimension (the ``(E,)`` axis of ``logm``/residuals, or the
folded ``(B*E)`` axis of a bucket) over a 1-D mesh:

- every shard owns a contiguous, equal slice of the edge axis and runs the
  unmodified per-edge message math (``repro.core.messages``) on its slice,
- the one cross-edge coupling -- the per-vertex incoming-message sum -- is a
  local ``segment_sum`` into the (small, replicated) vertex axis followed by
  one ``psum``. Vertices whose incoming edges span shards get their partial
  sums combined in shard order rather than edge order, so results match
  single-device up to float reassociation (~1e-6 in beliefs; the banded
  path below is the bitwise-exact alternative for graphs that support it),
- reverse-message lookups (``logm[edge_rev]``) stay shard-local because the
  builders emit directed pairs at adjacent even-aligned indices ``(2k,
  2k+1)`` and shard boundaries are kept even (see ``make_sharded_update``).

The sharded update is an ordinary ``(pgm, logm) -> (cand, resid)`` backend
registered as ``"sharded"`` in ``repro.kernels.ops.UPDATE_BACKENDS``, so the
whole engine stack -- chunked ``BPEngine.step`` resume, evacuating ``serve``,
the batched disjoint-union fold -- runs unmodified on a mesh:

    engine = BPEngine(BPConfig(scheduler="rnbp", backend="sharded"))

Relaxed/partitioned schedulers keep converging under exactly this kind of
distribution (Aksenov et al., 2020); ``repro.dist.bp_banded`` adds the
stricter halo-exchange path for banded graphs where neighbor-only
communication suffices and LBP trajectories are reproduced round-exactly.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import messages as M
from repro.core.engine import BPConfig, BPEngine, BPResult, BPState
from repro.core.graph import NEG_INF, PGM, pad_pgm
from repro.core.schedulers.base import Scheduler

from repro.dist.bp_banded import (BandedPartition, partition_banded,
                                  run_bp_banded)

#: Default mesh axis name for the sharded edge dimension.
BP_AXIS = "bp"


def cpu_child_env(n_devices: int = 8) -> dict:
    """Environment for a child process that fakes ``n_devices`` devices on
    the host CPU. It pins ``JAX_PLATFORMS=cpu``: the parent may already
    hold the accelerator, and a chip serves one process at a time, so such
    a child's numbers are CPU numbers wherever it runs."""
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           f"{n_devices}"))


def make_bp_mesh(n_devices: int | None = None, *,
                 axis: str = BP_AXIS) -> Mesh:
    """1-D device mesh over the BP edge axis.

    Returns a ``jax.sharding.Mesh`` of shape ``(n_devices,)`` with one axis
    named ``axis`` (default ``"bp"``), using the first ``n_devices`` of
    ``jax.devices()`` (all of them when ``None``). Works with any device
    count, including ``--xla_force_host_platform_device_count`` CPU meshes.

    The axis is ``AxisType.Auto``: the engine's gathers and scatters over
    sharded edge arrays (``init_messages``, the banded scatter-back) leave
    their output layout to the compiler, which explicit axes refuse.
    """
    n = n_devices or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def _check_edge_layout(pgm: PGM, n_shards: int) -> None:
    """Host-side validation of the sharding contract on a concrete PGM:
    equal even-sized shards, and every reverse edge co-resident with its
    partner (true by construction for all builders in ``repro.core.graph``
    and for ``BatchedPGM.folded()``)."""
    e = pgm.n_edges
    if e % n_shards:
        raise ValueError(
            f"padded edge count {e} not divisible by {n_shards} shards")
    size = e // n_shards
    if size % 2:
        raise ValueError(
            f"shard size {size} is odd: directed pairs (2k, 2k+1) would "
            "split across shards")
    rev = np.asarray(pgm.edge_rev)
    shard_of = np.arange(e) // size
    if not np.all(shard_of == shard_of[rev]):
        raise ValueError(
            "edge_rev crosses a shard boundary; re-pad with "
            "build_pgm/pad_pgm")


def shard_pgm(pgm: PGM, mesh: Mesh, *, axis: str = BP_AXIS) -> PGM:
    """Place a PGM's arrays on ``mesh``: edge-axis leaves sharded over
    ``axis``, vertex-axis leaves (``log_psi_v``/``state_mask``/``n_states``,
    all small) replicated. Shapes/dtypes are unchanged; only device layout
    moves. The padded edge count must divide the mesh size into even shards
    (see ``run_bp_sharded``, which re-pads automatically). The in-edge
    table is dropped (``in_edges=None``)."""
    _check_edge_layout(pgm, mesh.shape[axis])
    edge = NamedSharding(mesh, P(axis))
    edge3 = NamedSharding(mesh, P(axis, None, None))
    rep = NamedSharding(mesh, P())
    rep2 = NamedSharding(mesh, P(None, None))
    import dataclasses
    return dataclasses.replace(
        pgm,
        edge_src=jax.device_put(pgm.edge_src, edge),
        edge_dst=jax.device_put(pgm.edge_dst, edge),
        edge_rev=jax.device_put(pgm.edge_rev, edge),
        edge_mask=jax.device_put(pgm.edge_mask, edge),
        log_psi_e=jax.device_put(pgm.log_psi_e, edge3),
        log_psi_v=jax.device_put(pgm.log_psi_v, rep2),
        state_mask=jax.device_put(pgm.state_mask, rep2),
        n_states=jax.device_put(pgm.n_states, NamedSharding(mesh, P(None))),
        edge_count=(None if pgm.edge_count is None
                    else jax.device_put(pgm.edge_count, rep)),
        vertex_count=(None if pgm.vertex_count is None
                      else jax.device_put(pgm.vertex_count, rep)),
        # The sharded update sums by its own segment_sum; a single-device
        # in-edge table must not ride into the mesh-sharded jit.
        in_edges=None)


def make_sharded_update(mesh: Mesh | None = None, *, axis: str = BP_AXIS):
    """Build the mesh-sharded message-update backend.

    Returns an ``update_fn(pgm, logm) -> (cand (E, S) f32, resid (E,) f32)``
    with the exact signature/semantics of ``repro.core.messages.ref_update``
    (equal up to float reassociation in the per-vertex reduction for
    vertices whose incoming edges span shards), implemented as a
    ``shard_map`` over ``mesh``'s ``axis``: per-edge work is 1/n per
    device; the only collective is one ``psum`` of the (V, S) incoming-sum
    table per call. With ``mesh=None`` a mesh over all devices
    is built at factory time -- this is what the registry entry
    ``UPDATE_BACKENDS["sharded"]`` uses, so ``BPConfig(backend="sharded")``
    stays a plain serializable string (and the engine's batch fold can read
    ``update_fn.mesh`` before the first call).

    Contract on ``pgm``: the padded edge count must split into even-sized
    shards (``E % n == 0`` and ``E/n`` even) with reverse pairs
    co-resident. The builders' even-pair layout handles co-residency for
    any even split; divisibility is the caller's: ``run_bp_sharded``
    re-pads single graphs automatically, while the batched fold does not --
    a bucket's folded ``B*E`` axis (always a multiple of ``EDGE_PAD=128``)
    must divide the mesh, so keep mesh sizes at powers of two <= 64 or
    re-pad the bucket yourself.
    """
    if mesh is None:
        mesh = make_bp_mesh(axis=axis)
    m = mesh

    def update_fn(pgm: PGM, logm: jax.Array):
        n = m.shape[axis]
        e = logm.shape[0]
        v = pgm.log_psi_v.shape[0]
        if e % n or (e // n) % 2:
            raise ValueError(
                f"edge axis {e} does not split into even shards over "
                f"{n} devices; pad with pad_pgm (run_bp_sharded does this)")

        def body(src, dst, rev, emask, psi_e, psi_v, smask, logm_sh):
            # Local reverse lookup: pairs are co-resident by contract.
            off = jax.lax.axis_index(axis) * (e // n)
            contrib = jnp.where(emask[:, None], logm_sh, 0.0)
            part = jax.ops.segment_sum(contrib, dst, num_segments=v)
            vsum = jax.lax.psum(part, axis)           # exact: others add 0.0
            pre = psi_v[src] + vsum[src] - logm_sh[rev - off]
            pre = jnp.where(smask[src], pre, NEG_INF)
            cand = M.propagate_ref(psi_e, pre)
            return M.normalize_and_residual(cand, logm_sh, smask[dst], emask)

        es, es2 = P(axis), P(axis, None)
        return jax.shard_map(
            body, mesh=m,
            in_specs=(es, es, es, es, P(axis, None, None),
                      P(None, None), P(None, None), es2),
            out_specs=(es2, es),
            check_vma=False)(
            pgm.edge_src, pgm.edge_dst, pgm.edge_rev, pgm.edge_mask,
            pgm.log_psi_e, pgm.log_psi_v, pgm.state_mask, logm)

    update_fn.mesh = m             # engine/batch fold reads this seam
    update_fn.axis = axis
    return update_fn


def make_sharded_engine(scheduler: Scheduler | str, mesh: Mesh | None = None,
                        *, axis: str = BP_AXIS, **config) -> BPEngine:
    """A ``BPEngine`` whose message update runs sharded over ``mesh``.

    ``scheduler`` is a ``Scheduler`` instance or registry spec string;
    ``config`` holds the remaining ``BPConfig`` fields (eps, max_rounds,
    damping, chunk_rounds, history, ...). Scheduler selection, convergence
    voting and frontier commits stay in the engine's jitted chunk and are
    partitioned by XLA around the shard_map'd update, so ``init``/``step``
    resume and ``serve`` evacuation work unchanged under sharding.
    """
    return BPEngine(BPConfig(scheduler=scheduler,
                             backend=make_sharded_update(mesh, axis=axis),
                             **config))


def run_bp_sharded(pgm: PGM, scheduler: Scheduler | str, mesh: Mesh,
                   rng: jax.Array, *, eps: float = 1e-3,
                   max_rounds: int = 2000, damping: float = 0.0,
                   chunk_rounds: int | None = None, history: bool = True,
                   axis: str = BP_AXIS) -> BPResult:
    """One-shot sharded BP: beliefs for ``pgm`` computed over ``mesh``.

    Shapes/dtypes match the single-device engine exactly: returns a
    ``BPResult`` with ``beliefs (V, S) f32`` log-marginals, ``logm (E', S)``
    final messages (``E'`` = edge count re-padded to split evenly over the
    mesh; real-edge prefix identical layout), int32 ``rounds``, bool
    ``converged``. Convergence semantics are the engine's: ``converged`` is
    True iff every real edge's residual fell below ``eps`` within
    ``max_rounds`` sweeps.

    Deterministic schedulers (LBP) follow the single-device trajectory up to
    float reassociation in the per-vertex reduction (beliefs typically agree
    to ~1e-6); stochastic schedulers (RnBP/RBP) draw the *same* per-edge
    randomness as single-device runs -- the RNG stream lives in the engine
    loop, outside the shard_map -- so trajectories match to the same
    tolerance. Graphs whose padded edge count does not divide the mesh are
    re-padded with inert edges (contents unchanged).
    """
    n = mesh.shape[axis]
    e = pgm.n_edges
    quantum = 2 * n
    need = ((e + quantum - 1) // quantum) * quantum
    if need != e:
        pgm = pad_pgm(pgm, n_edges=need, n_vertices=pgm.n_vertices,
                      n_states=pgm.n_states_max)
    engine = make_sharded_engine(scheduler, mesh, axis=axis, eps=eps,
                                 max_rounds=max_rounds, damping=damping,
                                 chunk_rounds=chunk_rounds, history=history)
    return engine.run(shard_pgm(pgm, mesh, axis=axis), rng)


__all__ = [
    "BP_AXIS", "cpu_child_env", "make_bp_mesh", "shard_pgm",
    "make_sharded_update", "make_sharded_engine", "run_bp_sharded",
    "BandedPartition", "partition_banded", "run_bp_banded",
]
