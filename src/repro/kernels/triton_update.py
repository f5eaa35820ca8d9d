"""Pallas GPU kernel ("triton" backend): fused BP message update, edge-major.

This is the paper's actual target -- many-core GPUs, one worker per edge --
lowered through Pallas's Triton path instead of hand CUDA. The layout
rethink is the *transpose* of the TPU kernel (``message_update.py``):

  * **edges on the grid axis, states in registers** -- a GPU has thousands
    of independent lanes, not one 128-wide vector unit, so the natural
    tiling is one Triton program per ``BLK_E``-edge tile with the (S,) and
    (S, S) state axes held entirely in registers/shared memory. Operands
    therefore stay in the engine's native edge-major layout, (E, S) /
    (E, S, S): the GPU path needs *zero* transposes at the boundary (the
    TPU path pays two per round to reach its (S, E) lane layout).
  * the whole per-edge pipeline after the vertex gather is **fused into one
    pass**: LSE- (or max-) propagate through the pairwise table, valid-state
    renormalize, and L-inf residual, so one HBM round-trip covers what the
    reference path does in three XLA fusions. The traffic contract is
    **3 reads + 2 writes per edge** (pairwise table, prelude, old messages
    in; new messages, residual out; plus the 1-byte dst-state mask), the
    model ``repro.roofline.kernel_model`` predicts from and
    ``tests/test_roofline.py`` pins.
  * **both semirings** ship in the same kernel skeleton: ``semiring="sum"``
    is sum-product (logsumexp propagate, LSE-normalize), ``semiring="max"``
    is max-product (max propagate, max-normalize) -- bit-compatible with
    ``repro.core.messages.max_product_update``, so the LDPC MAP workload
    runs the fused path too. Scheduling is semiring-agnostic (paper SSV).
  * padded state lanes carry ``dmask=0`` and contribute nothing; padded
    edges are all-masked and produce (NEG_INF messages, 0 residual) --
    masks are data, no divergent control flow. State counts are padded to
    the next power of two because Triton tiles (``tl.arange``) must be
    power-of-two sized; the pad lanes are dead weight the block picker
    accounts for.

Occupancy/tile budget: the (BLK_E, S, S) pairwise tile dominates the
working set at ``S^2 * BLK_E * itemsize`` bytes. ``pick_block_edges_gpu``
sizes BLK_E so one program's streamed working set stays under
``_GPU_WORKSET_BYTES`` (64 KiB -- two ``num_stages`` of that fit L1/SMEM on
any modern part), clamped to power-of-two [8, 1024]; at S >= 32 the
pairwise tile forces small blocks and low occupancy, exactly as the TPU
VMEM budget does.

On the CPU the kernel runs in ``interpret=True`` mode (CPU CI exercises the
same program through the Pallas interpreter), so ``BPConfig(
backend="triton")`` is differentially tested against the reference path;
on a TPU it refuses to run and points to ``"pallas"``
(``repro.kernels.ops``). On a CUDA device the identical program lowers
through Triton with ``plgpu.CompilerParams`` (num_warps scaled to the
tile, ``num_stages=2`` for double-buffered HBM streaming).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.explog import exp, log

try:  # GPU lowering knobs; absent/renamed on CPU-only or old installs.
    from jax.experimental.pallas import triton as plgpu
    _TRITON_PARAMS = getattr(plgpu, "TritonCompilerParams",
                             getattr(plgpu, "CompilerParams", None))
except Exception:  # pragma: no cover - environment-dependent
    plgpu = None
    _TRITON_PARAMS = None

NEG_INF = -1.0e30
_GPU_WORKSET_BYTES = 64 * 1024
_MIN_BLK = 8
_MAX_BLK = 1024

__all__ = ["fused_update_e", "pick_block_edges_gpu", "next_pow2"]


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n`` (>= 1) -- Triton tile sizes and the
    state-padding width must be power-of-two."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def pick_block_edges_gpu(n_states: int, dtype_bytes: int = 4, *,
                         budget: int = _GPU_WORKSET_BYTES) -> int:
    """Largest power-of-two edge block whose streamed working set fits the
    per-program budget.

    Working set per edge ~ (S^2 + 4S + 2) * itemsize -- the 3-read/2-write
    fusion model (pairwise table + prelude/old/new message rows + mask +
    residual), same accounting as the TPU picker but against a GPU
    SMEM/L1-scale budget and power-of-two blocks (Triton tile constraint).
    Result is clamped to [8, 1024]: >=8 keeps tiles warp-friendly, <=1024
    keeps a single program's register demand sane.
    """
    per_edge = (n_states * n_states + 4 * n_states + 2) * max(dtype_bytes, 1)
    blk = max(int(budget) // per_edge, 1)
    blk = 1 << (blk.bit_length() - 1)          # floor to power of two
    return int(min(max(blk, _MIN_BLK), _MAX_BLK))


def _sum_kernel(logpsi_ref, pre_ref, logm_ref, dmask_ref, out_ref, resid_ref):
    """Blocks: logpsi (Eb,S,S) [e,xi,xj]; pre/logm/dmask/out (Eb,S); resid (Eb,).

    Sum-product: LSE over source states (max-shift for stability), then
    LSE-renormalize over valid destination states, then L-inf residual.
    Mirrors ``message_update._fused_kernel`` with every axis transposed.
    """
    scores = logpsi_ref[...] + pre_ref[...][:, :, None]      # (Eb,S,S)
    m = jnp.maximum(jnp.max(scores, axis=1), NEG_INF)        # (Eb,S) over xi
    s = jnp.sum(exp(scores - m[:, None, :]), axis=1)
    cand = m + log(s)                                        # (Eb,S) [e,xj]
    dmask = dmask_ref[...] != 0
    cand = jnp.where(dmask, cand, NEG_INF)
    zm = jnp.maximum(jnp.max(cand, axis=1), NEG_INF)         # (Eb,)
    zs = jnp.sum(jnp.where(dmask, exp(cand - zm[:, None]), 0.0), axis=1)
    z = zm + log(zs)
    new = jnp.where(dmask, cand - z[:, None], NEG_INF)
    out_ref[...] = new
    resid_ref[...] = jnp.max(
        jnp.where(dmask, jnp.abs(new - logm_ref[...]), 0.0), axis=1)


def _max_kernel(logpsi_ref, pre_ref, logm_ref, dmask_ref, out_ref, resid_ref):
    """Max-product semiring: max-propagate + max-normalize (peak at 0 over
    valid states), matching ``repro.core.messages.max_product_update``
    exactly -- max reductions are order-exact, so parity is bitwise."""
    scores = logpsi_ref[...] + pre_ref[...][:, :, None]      # (Eb,S,S)
    cand = jnp.max(scores, axis=1)                           # (Eb,S) over xi
    dmask = dmask_ref[...] != 0
    cand = jnp.where(dmask, cand, NEG_INF)
    z = jnp.max(cand, axis=1)                                # (Eb,)
    new = jnp.where(dmask, cand - z[:, None], NEG_INF)
    out_ref[...] = new
    resid_ref[...] = jnp.max(
        jnp.where(dmask, jnp.abs(new - logm_ref[...]), 0.0), axis=1)


_KERNELS = {"sum": _sum_kernel, "max": _max_kernel}


def _compiler_params(blk: int, s_pad: int):
    """Triton launch knobs for the non-interpret (real GPU) path: warps
    scaled to the (BLK_E, S) tile, 2 stages for double-buffered streaming."""
    if _TRITON_PARAMS is None:  # pragma: no cover - environment-dependent
        return None
    warps = next_pow2(min(8, max(1, (blk * s_pad) // 2048)))
    return _TRITON_PARAMS(num_warps=int(warps), num_stages=2)


@functools.partial(jax.jit,
                   static_argnames=("semiring", "interpret", "blk_e"))
def fused_update_e(logpsi: jax.Array,   # (E, S, S) [e, x_src, x_dst]
                   pre: jax.Array,      # (E, S) source-side belief
                   logm: jax.Array,     # (E, S) current messages
                   dmask: jax.Array,    # (E, S) bool-ish valid dst states
                   *, semiring: str = "sum", interpret: bool = False,
                   blk_e: int | None = None):
    """Fused gather->propagate->normalize->residual update, edge-major.

    Returns ``(new_logm (E, S), residual (E,))``. States are padded to the
    next power of two and edges to a multiple of ``BLK_E`` internally; pad
    lanes are all-masked and inert (NEG_INF messages, zero residual).
    ``semiring`` is ``"sum"`` (sum-product) or ``"max"`` (max-product);
    ``blk_e`` overrides the roofline-model block picker (autotuning hook).
    """
    if semiring not in _KERNELS:
        raise ValueError(f"unknown semiring {semiring!r}; "
                         f"expected one of {sorted(_KERNELS)}")
    e, s = pre.shape
    dtype_bytes = jnp.dtype(pre.dtype).itemsize
    s_pad = max(2, next_pow2(s))
    if s_pad != s:
        d = s_pad - s
        logpsi = jnp.pad(logpsi, ((0, 0), (0, d), (0, d)))
        pre = jnp.pad(pre, ((0, 0), (0, d)), constant_values=NEG_INF)
        logm = jnp.pad(logm, ((0, 0), (0, d)), constant_values=NEG_INF)
        dmask = jnp.pad(dmask, ((0, 0), (0, d)))
    blk = blk_e or pick_block_edges_gpu(s_pad, dtype_bytes)
    blk = max(_MIN_BLK, min(blk, next_pow2(e)))
    e_pad = ((e + blk - 1) // blk) * blk
    if e_pad != e:
        d = e_pad - e
        logpsi = jnp.pad(logpsi, ((0, d), (0, 0), (0, 0)))
        pre = jnp.pad(pre, ((0, d), (0, 0)), constant_values=NEG_INF)
        logm = jnp.pad(logm, ((0, d), (0, 0)), constant_values=NEG_INF)
        dmask = jnp.pad(dmask, ((0, d), (0, 0)))
    grid = (e_pad // blk,)
    kwargs = {}
    if not interpret:  # pragma: no cover - requires a CUDA device
        params = _compiler_params(blk, s_pad)
        if params is not None:
            kwargs["compiler_params"] = params
    new, resid = pl.pallas_call(
        _KERNELS[semiring],
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, s_pad, s_pad), lambda i: (i, 0, 0)),
            pl.BlockSpec((blk, s_pad), lambda i: (i, 0)),
            pl.BlockSpec((blk, s_pad), lambda i: (i, 0)),
            pl.BlockSpec((blk, s_pad), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((blk, s_pad), lambda i: (i, 0)),
            pl.BlockSpec((blk,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, s_pad), pre.dtype),
            jax.ShapeDtypeStruct((e_pad,), pre.dtype),
        ],
        interpret=interpret,
        **kwargs,
    )(logpsi, pre, logm, dmask.astype(jnp.int8))
    return new[:e, :s], resid[:e]

