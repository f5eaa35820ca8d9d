"""Log-space sum-product message math (pure jnp reference path).

The per-round compute is exactly the paper's Eq. (2), vectorized over *all*
directed edges (static shapes; the scheduler masks which results commit):

    m_{i->j}(x_j) oc sum_{x_i} psi_ij(x_i, x_j) psi_i(x_i)
                     prod_{k in G(i)\\j} m_{k->i}(x_i)

In log space with a per-vertex "incoming sum" cache:

    vsum[i]   = sum over incoming edges e'=(k->i) of logm[e']
                (gathers over the builder's in-edge table, else segment_sum)
    pre[e]    = log_psi_v[src] + vsum[src] - logm[rev(e)]            (exclude j->i)
    cand[e,j] = LSE_{x_i}( log_psi_e[e, x_i, x_j] + pre[e, x_i] )    (hot spot)

``cand`` is then normalized (LSE over valid dst states == 0). The LSE hot spot
is what the Pallas kernel in ``repro.kernels.message_update`` implements; this
module is the oracle (``ref.py`` re-exports from here) and the CPU path.

Residual (paper Eq. 4): r(m) = || f_BP(m) - m ||_inf over valid states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.graph import NEG_INF, PGM
from repro.kernels.explog import exp, log


def masked_logsumexp(x: jax.Array, mask: jax.Array, axis: int) -> jax.Array:
    """LSE over ``axis`` counting only ``mask`` entries; NEG_INF-safe."""
    x = jnp.where(mask, x, NEG_INF)
    m = jnp.max(x, axis=axis, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # all-masked rows stay finite
    s = jnp.sum(jnp.where(mask, exp(x - m), 0.0), axis=axis)
    return jnp.squeeze(m, axis) + log(s)


def init_messages(pgm: PGM, dtype=jnp.float32) -> jax.Array:
    """Uniform messages over the *destination* vertex's valid states."""
    dst_mask = pgm.state_mask[pgm.edge_dst]                     # (E, S)
    n_dst = pgm.n_states[pgm.edge_dst].astype(dtype)            # (E,)
    logm = jnp.where(dst_mask, -log(n_dst)[:, None], NEG_INF)
    return logm.astype(dtype)


def vertex_logprod(pgm: PGM, logm: jax.Array,
                   onto: jax.Array | None = None) -> jax.Array:
    """(V, S) sum of incoming log-messages per vertex (the paper's per-vertex
    message product, in log space), added onto ``onto`` (V, S) where given.
    Padded edges target the dummy vertex so they never pollute real sums;
    invalid states carry NEG_INF garbage which downstream masking discards.

    With the builder's in-edge table (``pgm.in_edges``) the sum is D gathers
    of V rows added in slot order (ascending edge id) after ``onto``: the
    order in which XLA's scatter-add accumulates on the CPU, where it also
    folds an added ``onto`` in as the scatter's initial value. Unused slots
    read 0. Without the table (every re-padded, stacked, folded or sharded
    PGM) it is a ``segment_sum``."""
    if pgm.in_edges is not None:
        total = onto
        for k in range(pgm.in_edges.shape[1]):
            part = logm.at[pgm.in_edges[:, k]].get(mode="fill",
                                                   fill_value=0.0)
            total = part if total is None else total + part
        return total
    contrib = jnp.where(pgm.edge_mask[:, None], logm, 0.0)
    total = jax.ops.segment_sum(contrib, pgm.edge_dst,
                                num_segments=pgm.n_vertices)
    return total if onto is None else onto + total


def edge_prelude(pgm: PGM, logm: jax.Array,
                 vsum: jax.Array | None = None) -> jax.Array:
    """(E, S) per-edge source-side belief excluding the reverse message.
    Its ops carry the ``bp.prelude`` scope in traces."""
    with jax.named_scope("bp.prelude"):
        if vsum is None:
            vsum = vertex_logprod(pgm, logm)
        pre = (pgm.log_psi_v[pgm.edge_src]
               + vsum[pgm.edge_src]
               - logm[pgm.edge_rev])
        src_mask = pgm.state_mask[pgm.edge_src]
        return jnp.where(src_mask, pre, NEG_INF)


def propagate_ref(log_psi_e: jax.Array, pre: jax.Array) -> jax.Array:
    """The LSE hot spot: cand[e, xj] = LSE_xi(log_psi_e[e, xi, xj] + pre[e, xi]).

    Pure-jnp oracle for the Pallas kernel. Not normalized, not masked on dst.
    """
    scores = log_psi_e + pre[:, :, None]          # (E, S, S)
    m = jnp.max(scores, axis=1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)
    s = jnp.sum(exp(scores - m), axis=1)
    return jnp.squeeze(m, 1) + log(s)


def normalize_messages(pgm: PGM, cand: jax.Array) -> jax.Array:
    """Normalize (LSE over valid dst states -> 0) and mask invalid states."""
    dst_mask = pgm.state_mask[pgm.edge_dst]
    z = masked_logsumexp(cand, dst_mask, axis=1)
    out = cand - z[:, None]
    return jnp.where(dst_mask, out, NEG_INF)


def compute_candidates(pgm: PGM, logm: jax.Array,
                       propagate=propagate_ref) -> jax.Array:
    """One full candidate-message pass f_BP(m) for every directed edge."""
    pre = edge_prelude(pgm, logm)
    cand = propagate(pgm.log_psi_e, pre)
    return normalize_messages(pgm, cand)


def normalize_and_residual(cand: jax.Array, logm: jax.Array,
                           dst_mask: jax.Array, edge_mask: jax.Array):
    """Shared tail of the jnp update paths (``ref_update`` and both
    ``repro.dist`` backends): normalize raw candidates (LSE over valid
    destination states -> 0, invalid states NEG_INF) and compute the (E,)
    L-inf residual vs the current messages (0 on padded edges). Takes
    explicit masks instead of a PGM so shard-local edge slices run the
    exact single-device math."""
    z = masked_logsumexp(cand, dst_mask, axis=1)
    cand = jnp.where(dst_mask, cand - z[:, None], NEG_INF)
    d = jnp.where(dst_mask, jnp.abs(cand - logm), 0.0)
    resid = jnp.where(edge_mask, jnp.max(d, axis=1), 0.0)
    return cand, resid


def residuals(pgm: PGM, logm: jax.Array, cand: jax.Array) -> jax.Array:
    """(E,) L-inf residual per directed edge; 0 on padded edges."""
    dst_mask = pgm.state_mask[pgm.edge_dst]
    d = jnp.where(dst_mask, jnp.abs(cand - logm), 0.0)
    r = jnp.max(d, axis=1)
    return jnp.where(pgm.edge_mask, r, 0.0)


def beliefs(pgm: PGM, logm: jax.Array) -> jax.Array:
    """(V, S) normalized log-marginals (paper Eq. 3)."""
    b = vertex_logprod(pgm, logm, onto=pgm.log_psi_v)
    z = masked_logsumexp(b, pgm.state_mask, axis=1)
    b = b - z[:, None]
    return jnp.where(pgm.state_mask, b, NEG_INF)


def ref_update(pgm: PGM, logm: jax.Array):
    """One fused BP step: (candidate messages, residuals). Pure-jnp reference;
    the Pallas path (repro.kernels.ops.pallas_update) matches this signature."""
    pre = edge_prelude(pgm, logm)
    with jax.named_scope("bp.update"):
        cand = propagate_ref(pgm.log_psi_e, pre)
        return normalize_and_residual(
            cand, logm, pgm.state_mask[pgm.edge_dst], pgm.edge_mask)


# ------------------------------------------------------ max-product (MAP) --

def propagate_max(log_psi_e: jax.Array, pre: jax.Array) -> jax.Array:
    """Max-product semiring: cand[e, xj] = max_xi(log_psi + pre). The paper
    (SSV) notes RnBP applies to other BP variants; scheduling is semiring-
    agnostic, so max-product reuses the whole frontier machinery."""
    return jnp.max(log_psi_e + pre[:, :, None], axis=1)


def max_product_update(pgm: PGM, logm: jax.Array):
    """ref_update for MAP inference (max-product). Messages renormalized to
    max 0 over valid states (the standard max-product normalization). The
    Pallas kernel's max body (``pallas_update(..., semiring="max")``)
    matches it bitwise."""
    pre = edge_prelude(pgm, logm)
    with jax.named_scope("bp.update"):
        cand = propagate_max(pgm.log_psi_e, pre)
        dst_mask = pgm.state_mask[pgm.edge_dst]
        cand = jnp.where(dst_mask, cand, NEG_INF)
        z = jnp.max(jnp.where(dst_mask, cand, NEG_INF), axis=1)
        cand = jnp.where(dst_mask, cand - z[:, None], NEG_INF)
        return cand, residuals(pgm, logm, cand)


def map_assignment(pgm: PGM, logm: jax.Array) -> jax.Array:
    """(V,) argmax decoding of max-product beliefs."""
    b = vertex_logprod(pgm, logm, onto=pgm.log_psi_v)
    b = jnp.where(pgm.state_mask, b, NEG_INF)
    return jnp.argmax(b, axis=1)


def apply_frontier(logm: jax.Array, cand: jax.Array,
                   frontier: jax.Array, damping: float = 0.0) -> jax.Array:
    """Commit candidate messages on frontier edges (static-shape analogue of
    the paper's compacted update). Optional damping (beyond-paper knob):
    new = (1-d)*cand + d*old, in log space (geometric damping)."""
    if damping > 0.0:
        cand = (1.0 - damping) * cand + damping * logm
    return jnp.where(frontier[:, None], cand, logm)
