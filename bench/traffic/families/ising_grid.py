"""Ising grids of the source paper (arXiv:1909.11469, section III-C).

N x N binary variables on a 4-neighbour grid. Unaries are drawn per state
from U[1e-3, 1]; each edge draws lambda ~ U[-0.5, 0.5] and has the table
exp(lambda C) where the two spins agree and exp(-lambda C) where they
differ. The distribution is the one of ``ising_grid_fast`` in the
program's dataset module, copied here so that a change there cannot move
the benchmark's inputs.

Config keys: ``n`` (grid side), ``C`` (coupling).
"""

from __future__ import annotations

import numpy as np

from bench.reference import Graph


def _grid_edges(n: int) -> np.ndarray:
    idx = np.arange(n * n).reshape(n, n)
    horiz = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    vert = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    return np.concatenate([horiz, vert], axis=0)


def make(cfg: dict, rng: np.random.Generator) -> Graph:
    """One grid drawn from ``rng``."""
    n, c = int(cfg["n"]), float(cfg["C"])
    edges = _grid_edges(n)
    unary = rng.uniform(1e-3, 1.0, size=(n * n, 2))
    lam = rng.uniform(-0.5, 0.5, size=len(edges))
    pair = np.empty((len(edges), 2, 2))
    pair[:, 0, 0] = pair[:, 1, 1] = lam * c
    pair[:, 0, 1] = pair[:, 1, 0] = -lam * c
    return Graph(n_states=np.full(n * n, 2), edges=edges,
                 log_unary=np.log(unary), log_pair=pair)
