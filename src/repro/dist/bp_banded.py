"""Banded BP: contiguous edge partitions + neighbor-only halo exchange.

``repro.dist`` (the general sharded path) pays one all-reduce of the (V, S)
vertex table per round. For *banded* graphs -- chains, grids, any MRF whose
adjacency matrix has small bandwidth under its natural vertex order -- that
collective is overkill: a contiguous vertex block only ever needs messages
from the blocks directly beside it. This module exploits that:

- ``partition_banded(pgm, n)`` reorders the real directed edges into global
  *stable destination order* and cuts them into ``n`` contiguous bands at
  vertex-block boundaries (blocks balanced by in-degree). The banded
  contract -- **every edge connects vertices in the same or adjacent
  blocks** -- is asserted; irregular graphs (random geometric / protein-like
  contact maps) are rejected with ``AssertionError``.
- ``run_bp_banded(part, sched, mesh, rng)`` runs the frontier loop with each
  band resident on one device. Per round each shard exchanges its message
  band with its two neighbors only (``lax.ppermute`` halo exchange, no
  all-reduce of message data), rebuilds the incoming-sum table for exactly
  the vertices its band touches, and commits its own band's frontier. The
  only global collective is the scalar unconverged-edge count (an exact
  integer psum shared by the convergence vote and RnBP's controller).

Round-exactness: a vertex's incoming edges all live in its own band, and the
stable sort preserves their original relative order, so the per-vertex sums
add the same values in the same order as the single-device reference --
banded LBP reproduces the reference trajectory (and therefore the round
count) exactly. Stochastic schedulers (RnBP) use *per-shard* RNG streams
(``fold_in(rng, shard)``); they converge to the same quality but not the
same trajectory.

Priority scheduling: *exact* sort-based schedulers (RBP/RS) need a global
top-k per round, which defeats neighbor-only communication -- they raise
the registry-style unsupported error below; use ``run_bp_sharded`` for
them. The *relaxed* priority family (RLX/RLXTree) is supported natively:
band slots are already in stable destination order, so contiguous
band-local queues are simultaneously storage-contiguous (rlx's partition)
and destination-ordered (rlxtree's structural partition) -- the two
coincide here, and per-queue top-k selection stays entirely shard-local
(per-shard RNG streams like RnBP, each shard force-including its own
max-residual queue), preserving the banded invariant that the only global
collective is the scalar unconverged count. ``BANDED_SCHEDULERS`` names
the supported subset; unsupported schedulers raise ``NotImplementedError``
with the uniform registry message format ("unknown banded scheduler ...;
registered: [...]").
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import messages as M
from repro.core.graph import NEG_INF, PGM
from repro.core.registry import Registry
from repro.core.schedulers import LBP, RLX, RLXTree, RnBP, get_scheduler
from repro.core.schedulers.rlx import queue_count, relaxed_frontier
from repro.kernels.explog import log

#: The scheduler subset the banded runner supports (see module docstring:
#: exact sort-based priorities need a global top-k and are excluded).
#: Same Registry class as ``SCHEDULERS`` so the unsupported-scheduler
#: error carries the uniform "unknown X ...; registered: [...]" format.
BANDED_SCHEDULERS = Registry("banded scheduler", {
    "lbp": LBP,
    "rlx": RLX,
    "rlxtree": RLXTree,
    "rnbp": RnBP,
})


@dataclasses.dataclass(frozen=True)
class BandedPartition:
    """``n`` contiguous edge bands of a banded PGM, padded to equal length.

    Slot layout: band ``s`` occupies flattened slot coordinates
    ``[s*band_len, (s+1)*band_len)``; real slots are the band's edges in
    global stable-dst order, trailing slots are inert (mask False, pointing
    at the dummy vertex). All per-slot arrays are shaped ``(n, band_len)``
    (``log_psi_e``: ``(n, band_len, S, S)`` f32); ``edge_rev`` holds
    *flattened slot* coordinates of the reverse edge (always in the same or
    an adjacent band -- the banded contract). ``slot_edge`` maps slots back
    to original edge indices (-1 for inert slots); ``v_lo`` gives the
    contiguous vertex blocks ``[v_lo[s], v_lo[s+1])``.
    """

    pgm: PGM                    # original graph (vertex tables, beliefs)
    n: int                      # number of bands == mesh size to run on
    band_len: int               # padded slots per band
    v_lo: np.ndarray            # (n+1,) int64 vertex block boundaries
    edge_src: jax.Array         # (n, L) int32
    edge_dst: jax.Array         # (n, L) int32
    edge_rev: jax.Array         # (n, L) int32, flattened slot coords
    edge_mask: jax.Array        # (n, L) bool
    log_psi_e: jax.Array        # (n, L, S, S) f32
    slot_edge: np.ndarray       # (n, L) int64, original edge id or -1


def partition_banded(pgm: PGM, n: int) -> BandedPartition:
    """Cut ``pgm`` into ``n`` contiguous edge bands for halo-exchange BP.

    Vertices are split into ``n`` contiguous blocks balanced by in-degree;
    each band is the (stable dst-sorted) slice of directed edges pointing
    into one block. Asserts the **banded contract**: every real edge must
    connect vertices in the same or adjacent blocks, so one band of halo on
    each side covers all remote reads. Chains and row-major grids pass for
    any reasonable ``n``; irregular spatial graphs (e.g.
    ``protein_like_graph``) fail the assert and must use the general
    ``run_bp_sharded`` path instead.
    """
    # Contract violations raise AssertionError explicitly (not via the
    # `assert` statement): rejection is API behavior -- silently accepting
    # a non-banded graph under `python -O` would compute wrong beliefs.
    if n < 1:
        raise AssertionError(f"need n >= 1 bands, got {n}")
    src = np.asarray(pgm.edge_src)
    dst = np.asarray(pgm.edge_dst)
    rev = np.asarray(pgm.edge_rev)
    mask = np.asarray(pgm.edge_mask)
    nv = pgm.n_real_vertices
    real = np.flatnonzero(mask)
    if real.size == 0:
        raise AssertionError("empty graph")
    # Global stable destination order: every vertex's incoming edges stay in
    # their original relative order (the round-exactness invariant).
    order = real[np.argsort(dst[real], kind="stable")]
    e_real = order.size

    # Vertex blocks [v_lo[s], v_lo[s+1]) balanced by in-degree.
    indeg = np.bincount(dst[order], minlength=nv)
    cum0 = np.concatenate([[0], np.cumsum(indeg)])          # (nv+1,)
    targets = np.arange(1, n) * (e_real / n)
    cuts = np.searchsorted(cum0[1:], targets, side="left") + 1
    v_lo = np.concatenate([[0], np.clip(cuts, 0, nv), [nv]])
    v_lo = np.maximum.accumulate(v_lo)
    block = np.searchsorted(v_lo, np.arange(nv), side="right") - 1  # (nv,)

    # The banded contract: edges never skip over a block.
    span = np.abs(block[src[order]] - block[dst[order]])
    if int(span.max(initial=0)) > 1:
        raise AssertionError(
            f"graph is not banded for n={n}: an edge spans "
            f"{int(span.max())} vertex blocks (> 1); re-order vertices or "
            "use run_bp_sharded")

    # Band s = sorted positions [p_lo[s], p_lo[s+1]).
    p_lo = cum0[v_lo]                                       # (n+1,)
    band_len = max(int(np.max(p_lo[1:] - p_lo[:-1])), 1)

    # Slot of each sorted position: band s, offset p - p_lo[s].
    pos_band = np.searchsorted(p_lo, np.arange(e_real), side="right") - 1
    pos_slot = pos_band * band_len + (np.arange(e_real) - p_lo[pos_band])
    slot_of = np.full(pgm.n_edges, -1, dtype=np.int64)
    slot_of[order] = pos_slot

    dummy = nv
    total = n * band_len
    b_src = np.full(total, dummy, dtype=np.int32)
    b_dst = np.full(total, dummy, dtype=np.int32)
    b_rev = np.arange(total, dtype=np.int32)                # inert: self
    b_mask = np.zeros(total, dtype=bool)
    s_pad = pgm.n_states_max
    b_psi = np.zeros((total, s_pad, s_pad), dtype=np.float32)
    slot_edge = np.full(total, -1, dtype=np.int64)

    b_src[pos_slot] = src[order]
    b_dst[pos_slot] = dst[order]
    b_rev[pos_slot] = slot_of[rev[order]]
    b_mask[pos_slot] = True
    b_psi[pos_slot] = np.asarray(pgm.log_psi_e)[order]
    slot_edge[pos_slot] = order

    # Reverse edges stay within one band of halo (implied by the contract;
    # kept as a hard invariant because the runner indexes the halo window).
    rev_band = b_rev[pos_slot] // band_len
    if int(np.abs(rev_band - pos_band).max(initial=0)) > 1:
        raise AssertionError("reverse edge escaped the one-band halo")

    shape = (n, band_len)
    return BandedPartition(
        pgm=pgm, n=n, band_len=band_len, v_lo=v_lo,
        edge_src=jnp.asarray(b_src.reshape(shape)),
        edge_dst=jnp.asarray(b_dst.reshape(shape)),
        edge_rev=jnp.asarray(b_rev.reshape(shape)),
        edge_mask=jnp.asarray(b_mask.reshape(shape)),
        log_psi_e=jnp.asarray(b_psi.reshape(shape + (s_pad, s_pad))),
        slot_edge=slot_edge.reshape(shape))


def _halo_ext(x: jax.Array, axis: str, n: int) -> jax.Array:
    """Concatenate [left band | own band | right band] along axis 0 via two
    neighbor ppermutes. Boundary shards see zeros in the missing side --
    always masked inert by the ext edge metadata."""
    left = jax.lax.ppermute(x, axis, [(i, i + 1) for i in range(n - 1)])
    right = jax.lax.ppermute(x, axis, [(i + 1, i) for i in range(n - 1)])
    return jnp.concatenate([left, x, right], axis=0)


# Compiled-loop cache: the shard_map'd while_loop is rebuilt per
# (partition, mesh, scheduler, eps, max_rounds, damping) tuple; caching by
# partition identity (strong ref keeps ids stable) lets repeated calls --
# serving, benchmarking -- reuse the jit cache instead of retracing. FIFO-
# bounded so a long-lived process churning partitions cannot hoard edge
# tables and executables without limit.
_RUNNER_CACHE: "dict" = {}
_RUNNER_CACHE_MAX = 16


def run_bp_banded(part: BandedPartition, scheduler, mesh: Mesh,
                  rng: jax.Array, *, eps: float = 1e-3,
                  max_rounds: int = 2000, damping: float = 0.0):
    """Frontier BP over ``mesh`` with one band per device and neighbor-only
    halo exchange; returns ``(logm, rounds, done)``.

    ``logm`` is ``(E, S) f32`` final messages in the *original* pgm edge
    layout (inert padded edges keep their init values, exactly like the
    single-device loop); ``rounds`` is the () int32 count of committed
    sweeps and ``done`` the () bool convergence flag -- True iff every real
    edge's residual fell below ``eps`` within ``max_rounds``. ``scheduler``
    may be ``LBP()`` (round-exact vs the single-device reference, see module
    docstring), ``RnBP(...)`` / ``RLX(...)`` / ``RLXTree(...)`` (per-shard
    RNG streams), or a registry spec string for any of them; exact
    sort-based schedulers raise ``NotImplementedError`` carrying the
    uniform registry message that names the supported subset
    (``BANDED_SCHEDULERS``).
    """
    if isinstance(scheduler, str):
        scheduler = get_scheduler(scheduler)
    if not isinstance(scheduler, tuple(BANDED_SCHEDULERS.values())):
        raise NotImplementedError(
            f"{type(scheduler).__name__} needs a global sort per round "
            "(use run_bp_sharded); "
            + BANDED_SCHEDULERS.unknown(type(scheduler).__name__.lower()))
    if scheduler.inner_sweeps != 1:
        raise NotImplementedError(
            f"inner_sweeps={scheduler.inner_sweeps}: the banded loop runs "
            "one sweep per round; !=1 would break round parity with the "
            "engine")
    key = (id(part), mesh, scheduler, eps, max_rounds, damping)
    if key in _RUNNER_CACHE:
        _, runner = _RUNNER_CACHE[key]
        return runner(rng)
    n, L = part.n, part.band_len
    axis = mesh.axis_names[0]
    if mesh.shape[axis] != n:
        raise AssertionError(
            f"partition has {n} bands but mesh axis {axis!r} has "
            f"{mesh.shape[axis]} devices")
    pgm = part.pgm
    nvert = pgm.n_vertices
    e_real = int(np.asarray(part.edge_mask).sum())

    # Static halo-extended edge metadata: band s sees [s-1 | s | s+1].
    def ext3(a: np.ndarray, fill) -> np.ndarray:
        pad = np.full((1,) + a.shape[1:], fill, a.dtype)
        return np.concatenate(
            [np.concatenate([pad, a[:-1]]), a,
             np.concatenate([a[1:], pad])], axis=1)

    dst_np = np.asarray(part.edge_dst)
    mask_np = np.asarray(part.edge_mask)
    ext_dst = jnp.asarray(ext3(dst_np, pgm.n_real_vertices))   # (n, 3L)
    ext_mask = jnp.asarray(ext3(mask_np, False))               # (n, 3L)

    rnbp = isinstance(scheduler, RnBP)
    relaxed = isinstance(scheduler, (RLX, RLXTree))
    if relaxed:
        # Band slots are already in stable destination order, so contiguous
        # band-local queues realize both rlx (storage-contiguous) and
        # rlxtree (dst-ordered) partitions at once. `queues` is the global
        # relaxation degree: each of the n shards hosts its share, and the
        # per-queue k divides the global frontier budget p*|E| over all
        # queues. Selection is entirely shard-local.
        q_band = queue_count(L, max(1, scheduler.queues // n))
        k_band = min(max(1, int(round(
            scheduler.p * e_real / (q_band * n)))), L // q_band)

    def body_shard(src, dst, rev, emask, psi_e, xdst, xmask, psi_v, smask,
                   key_data):
        (src, dst, rev, emask, xdst, xmask) = (
            a.reshape(a.shape[1:]) for a in (src, dst, rev, emask, xdst,
                                             xmask))
        psi_e = psi_e.reshape(psi_e.shape[1:])
        idx = jax.lax.axis_index(axis)
        base = (idx - 1) * L            # flattened coord of ext slot 0
        shard_key = jax.random.fold_in(
            jax.random.wrap_key_data(key_data), idx)
        logm0 = jnp.where(smask[dst], -log(
            pgm.n_states[dst].astype(jnp.float32))[:, None], NEG_INF)

        def cond(c):
            logm, rounds, done, old_count, k = c
            return (~done) & (rounds < max_rounds)

        def bp_round(c):
            logm, rounds, done, old_count, k = c
            k, sel_key = jax.random.split(k)
            ext_logm = _halo_ext(logm, axis, n)               # (3L, S)
            # Incoming sums for every vertex the band touches: the ext
            # window holds ALL incoming edges of any src/dst of an owned
            # edge (banded contract), in global stable order.
            contrib = jnp.where(xmask[:, None], ext_logm, 0.0)
            vsum = jax.ops.segment_sum(contrib, xdst, num_segments=nvert)
            pre = psi_v[src] + vsum[src] - ext_logm[rev - base]
            pre = jnp.where(smask[src], pre, NEG_INF)
            cand = M.propagate_ref(psi_e, pre)
            cand, resid = M.normalize_and_residual(cand, logm, smask[dst],
                                                   emask)
            unconverged = jax.lax.psum(
                jnp.sum((resid >= eps) & emask).astype(jnp.int32), axis)
            if rnbp:
                new_count = unconverged.astype(jnp.float32)
                ratio = new_count / jnp.maximum(old_count, 1.0)
                p = jnp.where(ratio > scheduler.ratio_threshold,
                              scheduler.low_p, scheduler.high_p)
                keep = jax.random.uniform(sel_key, resid.shape) < p
                frontier = (resid >= eps) & emask & keep
                old_count = new_count
            elif relaxed:
                # Per-queue top-k of a sampled queue subset, shard-local;
                # each shard force-includes its own max-residual queue
                # (relaxed_frontier), so the shard holding the global max
                # always commits it -- no livelock, no cross-shard sort.
                res2 = jnp.where(emask, resid, 0.0).reshape(
                    q_band, L // q_band)
                frontier = relaxed_frontier(
                    res2, k_band, scheduler.sample, sel_key).reshape(L)
            else:
                frontier = emask
            newly_done = unconverged == 0
            frontier = frontier & ~newly_done
            if damping > 0.0:
                cand = (1.0 - damping) * cand + damping * logm
            logm = jnp.where(frontier[:, None], cand, logm)
            rounds = rounds + jnp.where(newly_done, 0, 1)
            return (logm, rounds, newly_done, old_count, k)

        init = (logm0, jnp.int32(0), jnp.asarray(False),
                jnp.float32(e_real), shard_key)
        logm, rounds, done, _, _ = jax.lax.while_loop(cond, bp_round, init)
        return logm, rounds, done

    sharded = jax.jit(jax.shard_map(
        body_shard, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(axis), P(axis), P(None, None), P(None, None), P()),
        out_specs=(P(axis, None), P(), P()),
        check_vma=False))
    slots = part.slot_edge.reshape(-1)
    live = np.flatnonzero(slots >= 0)

    def runner(rng):
        logm_bands, rounds, done = sharded(
            part.edge_src, part.edge_dst, part.edge_rev, part.edge_mask,
            part.log_psi_e, ext_dst, ext_mask, pgm.log_psi_v,
            pgm.state_mask, jax.random.key_data(rng))
        # Scatter band slots back to the original edge layout; untouched
        # padded edges keep their init values, like the single-device loop.
        flat = logm_bands.reshape(n * L, -1)
        logm = M.init_messages(pgm).at[slots[live]].set(flat[live])
        return logm, rounds, done

    if len(_RUNNER_CACHE) >= _RUNNER_CACHE_MAX:
        _RUNNER_CACHE.pop(next(iter(_RUNNER_CACHE)))   # FIFO eviction
    _RUNNER_CACHE[key] = (part, runner)   # strong ref pins id(part)
    return runner(rng)


__all__ = ["BANDED_SCHEDULERS", "BandedPartition", "partition_banded",
           "run_bp_banded"]
