"""Relaxed multi-queue residual BP (Aksenov, Alistarh, Korhonen 2020).

RBP's exact top-k is the round's dominant cost *and* its last global sync:
``lax.top_k`` over all E residuals is a device-wide sort, and under the
sharded backend a cross-shard gather. The relaxed-scheduling result
(arxiv 2002.11505) is that BP does not need the exact top-k: pick
*approximately* the highest-residual messages -- a MultiQueue -- and the
trajectory converges like exact residual BP while the selection becomes
embarrassingly parallel.

The bulk-parallel realization here: the edge axis is cut into ``Q``
equal contiguous queues (a static ``reshape``; contiguous blocks align with
how the sharded backend slices the edge axis, so every queue lives on one
shard when ``Q`` is a multiple of the mesh size). Each round:

1. sample a Bernoulli(``sample``) subset of queues (one tiny ``(Q,)`` draw;
   the queue holding the current max residual is always included so a
   round can never select nothing while unconverged),
2. inside each sampled queue admit the local top ``k = p * |E| / Q``
   residuals (threshold semantics like RBP), with the per-queue k-th value
   found by **bisection on the threshold** (count >= k), not by
   ``lax.top_k``: top_k lowers to a sort/TopK custom call that GSPMD
   cannot partition -- the compiler responds by all-gathering the full
   residual array, silently reintroducing the global gather this family
   exists to remove. Bisection uses only elementwise compares and
   trailing-axis count reductions, which shard cleanly along the queue
   axis.

Net: the only cross-shard traffic left in a sharded round is the update's
(V, S) psum plus O(Q)-scalar reductions -- no collective ever touches an
edge-sized array (audited from the compiled HLO by
``tests/test_schedulers.py``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.graph import PGM


def queue_count(n_edges: int, queues: int) -> int:
    """Effective queue count: the largest ``q <= queues`` dividing the
    (static, padded) edge count, so the queue partition is an exact
    ``reshape``. Padded edge counts are multiples of ``EDGE_PAD = 128``, so
    any power-of-two ``queues <= 128`` is returned unchanged for
    builder-made graphs; odd hand-made shapes degrade gracefully (worst
    case ``q = 1`` == exact RBP semantics)."""
    q = max(1, min(int(queues), int(n_edges)))
    while n_edges % q:
        q -= 1
    return q


def queue_threshold(res2: jax.Array, k, iters: int = 30) -> jax.Array:
    """Per-queue k-th-largest threshold by bisection: the largest ``t``
    (per queue, up to float resolution) with ``count(res >= t) >= k``.

    Sort-free on purpose (see module docstring): each iteration is one
    elementwise compare plus a trailing-axis count, so a queue axis sharded
    over devices stays shard-local -- GSPMD has no sort/TopK to gather
    for. ``iters=30`` resolves the threshold to ``max_residual * 2**-30``,
    far below the eps scales BP runs at; threshold selection admits ties
    exactly like RBP's ``>= topk[k-1]`` rule. Invariant: ``lo`` always
    satisfies the count, ``hi`` never does.
    """
    hi = jnp.max(res2, axis=1) * (1.0 + 1e-6) + 1e-30       # count(>=hi) == 0
    lo = jnp.zeros_like(hi)                                 # count(>=0) == L

    def body(_, c):
        lo, hi = c
        mid = 0.5 * (lo + hi)
        ok = jnp.sum(res2 >= mid[:, None], axis=1) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return lo


def relaxed_frontier(res2: jax.Array, k, sample: float,
                     rng: jax.Array) -> jax.Array:
    """Shared relaxed selection core: per-queue top-k over sampled queues.

    ``res2`` is the ``(Q, L)`` queue view of the masked residuals (zeros on
    non-real edges); ``k`` the (possibly traced) per-queue frontier size.
    Returns the ``(Q, L)`` bool frontier: edges at or above their queue's
    k-th residual (bisection threshold, ties admitted), in queues kept by
    the Bernoulli(``sample``) draw -- the queue holding the global max
    residual is always kept, so the frontier is non-empty whenever any
    residual is. All per-queue work runs on the trailing axis only; the
    sole cross-queue reductions are the ``(Q,)`` argmax of the per-queue
    maxima and the threshold counts -- O(Q) scalars, never edge-sized data.
    """
    maxq = jnp.max(res2, axis=1)                      # (Q,) per-queue maxima
    thresh = queue_threshold(res2, k)
    keep = jax.random.uniform(rng, (res2.shape[0],)) < sample
    keep = keep.at[jnp.argmax(maxq)].set(True)        # max queue always in
    # >= max(thresh, tiny): never thrash zero-residual (converged/padding)
    # edges on the last stretch -- RBP's guard, per queue.
    return (res2 >= jnp.maximum(thresh, 1e-30)[:, None]) & keep[:, None]


@dataclasses.dataclass(frozen=True)
class RLX:
    """Relaxed multi-queue residual BP: per-queue top-k of a sampled queue
    subset -- approximate prioritization without a global sort.

    ``select`` cuts the edge axis into ``queues`` contiguous equal blocks
    (static reshape), keeps a Bernoulli(``sample``) subset of queues (the
    queue holding the max residual always included), and admits each kept
    queue's local top ``k = p * |E| / Q`` residuals (threshold semantics,
    like RBP). Stochastic: consumes one tiny ``(Q,)`` uniform draw per
    round; no carried state. Under ``backend="sharded"`` the per-queue
    sorts stay shard-local, removing RBP's cross-shard top-k gather -- the
    sharded path's last global sync. Registry spec ``"rlx"``.
    """

    queues: int = 8          # Q: relaxation degree (queues to cut edges into)
    sample: float = 0.5      # fraction of queues admitted per round
    p: float = 1.0 / 256.0   # frontier multiplier: k_per_queue = p * |E| / Q
    inner_sweeps: int = 1

    def __post_init__(self):
        if self.queues < 1:
            raise ValueError(f"queues must be >= 1, got {self.queues}")
        if not 0.0 < self.sample <= 1.0:
            raise ValueError(f"sample must be in (0, 1], got {self.sample}")
        if not self.p > 0.0:
            raise ValueError(f"p must be > 0, got {self.p}")

    def init(self, pgm: PGM):
        return ()

    def select(self, pgm: PGM, residuals: jax.Array, eps: float,
               rng: jax.Array, state, unconverged: jax.Array):
        e = residuals.shape[0]
        q = queue_count(e, self.queues)
        # Traced per-graph k (batch-safe: one trace serves every graph of a
        # vmapped bucket; the bisection threshold takes k as data).
        k = jnp.clip(jnp.round(self.p * pgm.traced_edge_count()
                               .astype(jnp.float32) / q).astype(jnp.int32),
                     1, e // q)
        res2 = jnp.where(pgm.edge_mask, residuals, 0.0).reshape(q, e // q)
        frontier = relaxed_frontier(res2, k, self.sample, rng)
        return frontier.reshape(e) & pgm.edge_mask, state
