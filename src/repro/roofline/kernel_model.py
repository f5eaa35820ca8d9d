"""Hand cost model of the fused message-update kernel (the tuning contract).

The fused kernels (``repro.kernels.message_update`` on TPU,
``repro.kernels.triton_update`` on GPU) promise **3 reads + 2 writes per
edge**: pairwise table, prelude and old messages stream in; new messages
and the residual stream out; plus the 1-byte destination-state mask.
Per edge of S (padded) states at ``itemsize`` b:

    bytes = (S^2 + 3*S + 1) * b  +  S          # 3 reads + 2 writes + mask

Flops are hand-counted from the kernel body, one flop per output element
per arithmetic op (the same convention ``repro.roofline.jaxpr_cost``
uses), so the jaxpr walker and this model are directly comparable. Both
kernels take ``exp`` and ``log`` from ``repro.kernels.explog``: 31 and 41
ops per element.

    GPU sum-product:  scores add S^2, src max-reduce S^2, shift-sub S^2,
                      exp 31*S^2, sum-reduce S^2             -> 35*S^2
                      + tail with S exps, S + 1 logs          ~ 93*S + 45
    GPU max-product:  scores add S^2, src max-reduce S^2      -> 2*S^2
                      + normalize/residual/mask tail          ~ 14*S + 1
    TPU sum-product (``kernel="tpu"``), the LSE as two loops over source
    states:
        loop 1: scores add, max                               -> 2*S^2
        loop 2: scores add, shift-sub, exp (31), sum          -> 34*S^2
        + tail with S exps, S + 1 logs                        ~ 91*S + 48

The exp/log ops run on the vector units, so the ridge point that decides
whether a kernel is memory-bound is the vector f32 rate over memory
bandwidth, not a matrix unit's peak; which side either kernel sits on is
not measured.

The O(S) tail constants are fitted once against the traced kernel (exact
at time of writing); ``tests/test_roofline.py`` pins model-vs-jaxpr
agreement so neither the kernel body nor the walker can drift silently.
"""

from __future__ import annotations

from repro.roofline.jaxpr_cost import Cost

__all__ = ["fused_update_cost", "predicted_intensity", "gpu_padded_shape",
           "round_cost"]

_FLOPS_PER_EDGE = {
    # (kernel, semiring) -> (S^2 coefficient, S coefficient, constant)
    ("gpu", "sum"): (35.0, 93.0, 45.0),
    ("gpu", "max"): (2.0, 14.0, 1.0),
    ("tpu", "sum"): (36.0, 91.0, 48.0),
}


def _next_pow2(n: int) -> int:
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def gpu_padded_shape(n_edges: int, n_states: int, dtype_bytes: int = 4, *,
                     blk_e: int | None = None):
    """The shapes the GPU kernel actually launches with: states padded to
    the next power of two (>= 2, Triton tile constraint), edges to a
    multiple of the picked block. Returns ``(e_pad, s_pad, blk)``."""
    from repro.kernels.triton_update import (_MIN_BLK, next_pow2,
                                             pick_block_edges_gpu)
    s_pad = max(2, next_pow2(n_states))
    blk = blk_e or pick_block_edges_gpu(s_pad, dtype_bytes)
    blk = max(_MIN_BLK, min(blk, next_pow2(n_edges)))
    e_pad = ((n_edges + blk - 1) // blk) * blk
    return e_pad, s_pad, blk


def fused_update_cost(n_edges: int, n_states: int, *, dtype_bytes: int = 4,
                      semiring: str = "sum", padded: bool = False,
                      kernel: str = "gpu") -> Cost:
    """3-read/2-write model cost of one fused update over ``n_edges`` edges
    of ``n_states`` states by ``kernel`` ("gpu" | "tpu"; the byte model is
    the same, the flops are not). With ``padded=True`` the GPU kernel's
    internal padding (power-of-two states, block-multiple edges) is applied
    first, predicting the *launched* cost rather than the logical one."""
    if (kernel, semiring) not in _FLOPS_PER_EDGE:
        raise ValueError(f"unknown kernel/semiring {(kernel, semiring)!r}; "
                         f"expected one of {sorted(_FLOPS_PER_EDGE)}")
    e, s = int(n_edges), int(n_states)
    if padded:
        e, s, _ = gpu_padded_shape(e, s, dtype_bytes)
    a, b, c = _FLOPS_PER_EDGE[kernel, semiring]
    flops = e * (a * s * s + b * s + c)
    byts = e * ((s * s + 3 * s + 1) * dtype_bytes + s)
    return Cost(float(flops), float(byts))


def predicted_intensity(n_states: int, *, dtype_bytes: int = 4,
                        semiring: str = "sum", padded: bool = False) -> float:
    """Model arithmetic intensity (flops/byte) of the fused update; edge
    count cancels, so this is a pure function of the state count and width.
    Against a matrix unit's ridge (~240 f/B on a v5e) every BP state count
    is memory-bound; against the vector units, which run this kernel's
    exp/log, that is not measured (see the module docstring)."""
    c = fused_update_cost(1 if not padded else 64, n_states,
                          dtype_bytes=dtype_bytes, semiring=semiring,
                          padded=padded)
    return c.flops / c.bytes


def round_cost(pgm, scheduler, update_fn, *, eps: float = 1e-3,
               rng=None) -> Cost:
    """Jaxpr-walk cost of ONE full engine round -- fused update + residual
    gate + scheduler frontier selection + commit -- for a given scheduler
    instance and update backend: the update kernel's intensity diluted by
    whatever selection machinery the scheduler adds (top-k, per-queue
    bisection, RNG)."""
    import jax
    import jax.numpy as jnp

    from repro.core import messages as M
    from repro.roofline.jaxpr_cost import trace_cost

    logm = M.init_messages(pgm)
    sstate = scheduler.init(pgm)
    key = jax.random.key(0) if rng is None else rng

    def one_round(logm, sstate, key):
        cand, r = update_fn(pgm, logm)
        unconverged = jnp.sum((r >= eps) & pgm.edge_mask).astype(jnp.int32)
        frontier, sstate = scheduler.select(pgm, r, eps, key, sstate,
                                            unconverged)
        return M.apply_frontier(logm, cand, frontier), sstate

    return trace_cost(one_round, logm, sstate, key)
