"""Randomized BP -- the paper's contribution (SS IV).

Frontier = two filters over all directed edges:
  1. *eps filter*: drop messages whose next update moves them < eps
     (they are already locally converged; after Yang et al.),
  2. *random filter*: keep a Bernoulli(p) subset of the survivors
     (cuRAND per-thread on the GPU; threefry here -- pure elementwise,
     no sort, which is the entire point).

Dynamic p (SS IV-A): track EdgeRatio = NewEdgeCount / OldEdgeCount of
unconverged edges between consecutive rounds. EdgeRatio > 0.9 means the run
is stalling -> use LowP (sequentialism, convergence mode); otherwise HighP
(parallelism, speed mode). The paper locks HighP = 1.0 for its synthetic
graphs and sweeps LowP in {0.7, 0.4, 0.1}; protein runs use (0.9, 0.4).

Carried state: previous round's unconverged-edge count.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.graph import PGM


@dataclasses.dataclass(frozen=True)
class RnBP:
    """Randomized BP (the paper's contribution): eps-filter + Bernoulli(p)
    keep, with a two-mode dynamic p.

    ``select`` keeps each unconverged real edge (residual >= eps) with
    probability ``p`` -- pure elementwise work, no sort. The carried state
    is the previous round's unconverged count (() f32): when the ratio
    new/old exceeds ``ratio_threshold`` the run is stalling and ``low_p``
    (convergence mode) is used, otherwise ``high_p`` (speed mode).
    Stochastic: consumes one (E,)-shaped uniform draw per round from the
    engine's RNG stream. Registry spec ``"rnbp"``.
    """

    low_p: float = 0.7
    high_p: float = 1.0
    ratio_threshold: float = 0.9
    inner_sweeps: int = 1

    def init(self, pgm: PGM):
        # OldEdgeCount starts at "everything unconverged". Traced count so a
        # vmapped bucket carries each graph's own controller state.
        return pgm.traced_edge_count().astype(jnp.float32)

    def select(self, pgm: PGM, residuals: jax.Array, eps: float,
               rng: jax.Array, state, unconverged: jax.Array):
        old_count = state
        new_count = unconverged.astype(jnp.float32)
        edge_ratio = new_count / jnp.maximum(old_count, 1.0)
        p = jnp.where(edge_ratio > self.ratio_threshold,
                      self.low_p, self.high_p)
        # Filter 1: eps-prune.
        candidates = (residuals >= eps) & pgm.edge_mask
        # Filter 2: randomized keep. One uniform per edge -- O(E) elementwise,
        # the low-overhead replacement for sort-and-select.
        keep = jax.random.uniform(rng, residuals.shape) < p
        frontier = candidates & keep
        return frontier, new_count
