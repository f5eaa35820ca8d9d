"""Pallas TPU kernel: fused BP message update + normalize + residual.

This is the paper's per-round compute hot spot (SS III-B "Update" kernel).
The CUDA version assigns one thread per edge; the TPU-native rethink is:

  * **edges on the 128-wide lane axis** -- state counts are 2..96, far below
    the lane width, so an (E, S) row-major layout would waste >90% of every
    vector register. All kernel operands are stored transposed, (S, E) /
    (S, S, E), with E tiled by ``BlockSpec`` along the grid.
  * the whole per-edge pipeline after the vertex gather is **fused into one
    VMEM-resident pass**: LSE-propagate through the pairwise table,
    destination-state renormalize, and L-inf residual, so candidate messages
    are produced in a single HBM round-trip (3 reads, 2 writes per edge
    block) instead of the 3 separate XLA fusions the reference path emits.
  * the LSE over source states runs on sublanes (VPU reduction), with the
    max-shift trick for stability; padded states carry NEG_INF and padded
    edges point at a 1-state dummy vertex, so no divergent control flow is
    needed -- masks are data, exactly as on the GPU.
  * two semirings share the skeleton (static ``semiring``): ``"sum"``
    (sum-product, the LSE above) and ``"max"`` (max-product for MAP: a max
    over source states, renormalized to max 0 over valid destination
    states, with no ``exp`` or ``log`` at all). The max body reads no padded
    edges: its grid covers E with a ragged last block, whose lanes past E
    are never written back, so the (S, S, E) table is not copied into a
    padded one every round.

VMEM budget: the (S, S, BLK_E) pairwise block dominates at
S^2 * BLK_E * 4 B; ``pick_block_edges`` sizes BLK_E so the working set stays
under ~4 MiB (one core's VMEM is 16 MiB on v5e; we leave room for
double-buffering of in/out streams).

The kernel is batch-agnostic by construction: edges are an opaque 1-D grid
axis, so a *bucket* of B same-shape graphs is served by folding the batch
axis into the edge axis (E -> B*E, see ``repro.kernels.ops.
pallas_update_batch``) -- one launch, full lane occupancy across graph
boundaries, no per-graph block-remainder waste.

Validated in ``interpret=True`` mode on CPU against ``ref.py`` (pure jnp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.explog import exp, log

NEG_INF = -1.0e30
_LANE = 128
_VMEM_BUDGET_BYTES = 4 * 1024 * 1024


def pick_block_edges(n_states: int, dtype_bytes: int = 4) -> int:
    """Largest lane-multiple edge block whose working set fits the budget.

    Working set per block ~ (S^2 + 4*S + 2) * BLK_E * dtype_bytes
    (pairwise table + pre/old/new/dst-mask rows + residual row). Both
    semirings keep it: each loops over source states one (S, BLK_E) slice
    at a time, so neither holds an (S, S, BLK_E) temporary.
    """
    per_edge = (n_states * n_states + 4 * n_states + 2) * dtype_bytes
    blk = _VMEM_BUDGET_BYTES // max(per_edge, 1)
    blk = max(_LANE, (blk // _LANE) * _LANE)
    return int(min(blk, 4096))


def _fused_kernel(logpsi_ref, pre_ref, logm_ref, dmask_ref,
                  out_ref, resid_ref):
    """Blocks: logpsi (S,S,Eb) [xi,xj,e]; pre/logm/dmask/out (S,Eb); resid (1,Eb).

    The LSE over source states xi runs as two loops over xi (max, then the
    shifted exp-sum), each on one (S, Eb) slice of the pairwise block: the
    f32 ``exp`` of ``repro.kernels.explog`` keeps several temporaries per
    element, which at (S, S, Eb) would not fit in VMEM."""
    n_src = logpsi_ref.shape[0]

    def scores(xi):                                          # (S,Eb) [xj,e]
        return logpsi_ref[xi] + pre_ref[pl.ds(xi, 1), :]

    m = jax.lax.fori_loop(1, n_src, lambda xi, m: jnp.maximum(m, scores(xi)),
                          scores(0))
    m = jnp.maximum(m, NEG_INF)
    s = jax.lax.fori_loop(0, n_src,
                          lambda xi, s: s + exp(scores(xi) - m),
                          jnp.zeros_like(m))
    cand = m + log(s)                                        # (S,Eb) [xj,e]
    dmask = dmask_ref[...] != 0
    cand = jnp.where(dmask, cand, NEG_INF)
    # renormalize over valid destination states (sublane reduction)
    zm = jnp.maximum(jnp.max(cand, axis=0), NEG_INF)         # (Eb,)
    zs = jnp.sum(jnp.where(dmask, exp(cand - zm[None, :]), 0.0), axis=0)
    z = zm + log(zs)
    new = jnp.where(dmask, cand - z[None, :], NEG_INF)
    out_ref[...] = new
    resid_ref[...] = jnp.max(
        jnp.where(dmask, jnp.abs(new - logm_ref[...]), 0.0),
        axis=0)[None, :]


def _max_kernel(logpsi_ref, pre_ref, logm_ref, dmask_ref,
                out_ref, resid_ref):
    """The max-product body, on the blocks of ``_fused_kernel``: the max
    over source states xi (one (S, Eb) slice at a time), masked to valid
    destination states and shifted so that their max is 0, then the L-inf
    residual. The ops are those of ``messages.max_product_update``, so the
    two agree bitwise."""
    n_src = logpsi_ref.shape[0]

    def scores(xi):                                          # (S,Eb) [xj,e]
        return logpsi_ref[xi] + pre_ref[pl.ds(xi, 1), :]

    cand = jax.lax.fori_loop(1, n_src,
                             lambda xi, m: jnp.maximum(m, scores(xi)),
                             scores(0))
    dmask = dmask_ref[...] != 0
    cand = jnp.where(dmask, cand, NEG_INF)
    z = jnp.max(cand, axis=0)                                # (Eb,)
    new = jnp.where(dmask, cand - z[None, :], NEG_INF)
    out_ref[...] = new
    resid_ref[...] = jnp.max(
        jnp.where(dmask, jnp.abs(new - logm_ref[...]), 0.0),
        axis=0)[None, :]


SEMIRINGS = ("sum", "max")


@functools.partial(jax.jit, static_argnames=("interpret", "semiring"))
def fused_update_t(logpsi_t: jax.Array,   # (S, S, E) [x_src, x_dst, e]
                   pre_t: jax.Array,      # (S, E) source-side belief
                   logm_t: jax.Array,     # (S, E) current messages
                   dmask_t: jax.Array,    # (S, E) int8/bool valid dst states
                   *, interpret: bool = False, semiring: str = "sum"):
    """Returns (new_logm_t (S, E), residual (E,)). ``semiring`` is ``"sum"``
    (sum-product) or ``"max"`` (max-product). The sum body pads edges to the
    block size internally (padded lanes carry all-masked states -> inert);
    the max body runs a ragged last block instead. In traces the padding,
    slicing and mask cast carry the ``bp.layout`` scope."""
    if semiring not in SEMIRINGS:
        raise ValueError(f"semiring must be one of {SEMIRINGS}, got "
                         f"{semiring!r}")
    s, e = pre_t.shape
    # Size blocks for the *actual* operand width: bf16 operands halve the
    # per-edge working set, so the VMEM budget admits twice the edges.
    blk = min(pick_block_edges(s, jnp.dtype(pre_t.dtype).itemsize),
              max(_LANE, e))
    if semiring == "max":
        return _fused_max(logpsi_t, pre_t, logm_t, dmask_t, blk=blk,
                          interpret=interpret)
    e_pad = ((e + blk - 1) // blk) * blk
    with jax.named_scope("bp.layout"):
        if e_pad != e:
            pad = [(0, 0)] * (len(logpsi_t.shape) - 1) + [(0, e_pad - e)]
            logpsi_t = jnp.pad(logpsi_t, pad)
            pre_t = jnp.pad(pre_t, ((0, 0), (0, e_pad - e)),
                            constant_values=NEG_INF)
            logm_t = jnp.pad(logm_t, ((0, 0), (0, e_pad - e)),
                             constant_values=NEG_INF)
            dmask_t = jnp.pad(dmask_t, ((0, 0), (0, e_pad - e)))
        dmask_t = dmask_t.astype(jnp.int8)
    grid = (e_pad // blk,)
    new_t, resid = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((s, s, blk), lambda i: (0, 0, i)),
            pl.BlockSpec((s, blk), lambda i: (0, i)),
            pl.BlockSpec((s, blk), lambda i: (0, i)),
            pl.BlockSpec((s, blk), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((s, blk), lambda i: (0, i)),
            pl.BlockSpec((1, blk), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, e_pad), pre_t.dtype),
            jax.ShapeDtypeStruct((1, e_pad), pre_t.dtype),
        ],
        interpret=interpret,
    )(logpsi_t, pre_t, logm_t, dmask_t)
    with jax.named_scope("bp.layout"):
        return new_t[:, :e], resid[0, :e]


def _fused_max(logpsi_t, pre_t, logm_t, dmask_t, *, blk, interpret):
    """The max-product ``pallas_call`` over ceil(E / blk) blocks. The last
    block may run past E: its lanes are independent edges, read as
    unspecified values and dropped on write, so no operand is padded."""
    s, e = pre_t.shape
    with jax.named_scope("bp.layout"):
        dmask_t = dmask_t.astype(jnp.int8)
    row = pl.BlockSpec((s, blk), lambda i: (0, i))
    new_t, resid = pl.pallas_call(
        _max_kernel,
        grid=(pl.cdiv(e, blk),),
        in_specs=[pl.BlockSpec((s, s, blk), lambda i: (0, 0, i)),
                  row, row, row],
        out_specs=[row, pl.BlockSpec((1, blk), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((s, e), pre_t.dtype),
                   jax.ShapeDtypeStruct((1, e), pre_t.dtype)],
        interpret=interpret,
    )(logpsi_t, pre_t, logm_t, dmask_t)
    with jax.named_scope("bp.layout"):
        return new_t, resid[0]
