"""Plain sum-product BP on a pairwise MRF, in float64 numpy.

This is the yardstick that decides ``correct``. It imports nothing of the
system under test. A graph is a ``Graph``: vertex state counts, undirected
edges, and log potentials, made by the benchmark's own generators from the
seed. The system's answer is its messages and beliefs; the reference
checks that the messages are a fixed point of one exact BP update within
the stated tolerance, and that the beliefs are the ones those messages
give. Any fixed point is a valid answer of loopy BP, whatever schedule
reached it, so the check does not depend on the schedule's randomness.

Messages live on directed edges: edge ``2k`` runs ``edges[k, 0] ->
edges[k, 1]`` and edge ``2k + 1`` the reverse. A message is a vector of
log values over the destination's states, normalized so that its
log-sum-exp is 0.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """A pairwise MRF as the benchmark generates it (float64, log space).

    ``log_unary`` is (V, S) and ``log_pair`` is (E, S, S), indexed
    ``[x_u, x_v]`` for undirected edge ``(u, v)``; entries past a vertex's
    state count are -inf. Graphs of one code may share ``log_pair``."""

    n_states: np.ndarray        # (V,) int
    edges: np.ndarray           # (E, 2) int
    log_unary: np.ndarray       # (V, S) float64
    log_pair: np.ndarray        # (E, S, S) float64

    @property
    def n_vertices(self) -> int:
        return len(self.n_states)

    @property
    def n_directed(self) -> int:
        return 2 * len(self.edges)


def _lse(x: np.ndarray, axis: int) -> np.ndarray:
    m = np.max(x, axis=axis, keepdims=True)
    m = np.where(np.isfinite(m), m, 0.0)
    with np.errstate(divide="ignore"):
        return np.squeeze(m, axis) + np.log(np.sum(np.exp(x - m), axis=axis))


def directed(g: Graph):
    """(src, dst, rev) of the directed edges, in the module's order."""
    u, v = g.edges[:, 0], g.edges[:, 1]
    e = len(u)
    src = np.empty(2 * e, np.int64)
    dst = np.empty(2 * e, np.int64)
    src[0::2], src[1::2] = u, v
    dst[0::2], dst[1::2] = v, u
    rev = np.arange(2 * e) ^ 1
    return src, dst, rev


def _incoming(g: Graph, dst: np.ndarray, logm: np.ndarray) -> np.ndarray:
    """(V, S) sum of incoming log-messages (-inf states left as -inf)."""
    finite = np.where(np.isfinite(logm), logm, 0.0)
    out = np.zeros(g.log_unary.shape)
    np.add.at(out, dst, finite)
    return out


def update(g: Graph, logm: np.ndarray) -> np.ndarray:
    """One exact BP update of every directed edge: (2E, S) new messages."""
    src, dst, rev = directed(g)
    vsum = _incoming(g, dst, logm)
    finite_m = np.where(np.isfinite(logm), logm, 0.0)
    pre = g.log_unary[src] + vsum[src] - finite_m[rev]           # (2E, S)
    pre = np.where(np.isfinite(g.log_unary[src]), pre, -np.inf)
    out = np.empty_like(logm)
    for parity, table in ((0, g.log_pair),
                          (1, np.swapaxes(g.log_pair, 1, 2))):
        sl = slice(parity, None, 2)
        out[sl] = _lse(table + pre[sl][:, :, None], axis=1)
    valid = np.isfinite(g.log_unary[dst])
    out = np.where(valid, out, -np.inf)
    return np.where(valid, out - _lse(out, axis=1)[:, None], -np.inf)


def beliefs(g: Graph, logm: np.ndarray) -> np.ndarray:
    """(V, S) normalized log-marginals from messages ``logm``."""
    _, dst, _ = directed(g)
    b = g.log_unary + _incoming(g, dst, logm)
    b = np.where(np.isfinite(g.log_unary), b, -np.inf)
    return np.where(np.isfinite(b), b - _lse(b, axis=1)[:, None], -np.inf)


def residual(g: Graph, logm: np.ndarray) -> float:
    """Largest change one exact update makes to any message, over the valid
    states of every directed edge (the paper's L-inf residual)."""
    new = update(g, logm)
    _, dst, _ = directed(g)
    valid = np.isfinite(g.log_unary[dst])
    with np.errstate(invalid="ignore"):
        return float(np.max(np.where(valid, np.abs(new - logm), 0.0)))


def belief_gap(g: Graph, logm: np.ndarray, got: np.ndarray) -> float:
    """Largest gap between beliefs ``got`` and those ``logm`` gives, over
    valid states."""
    want = beliefs(g, logm)
    valid = np.isfinite(g.log_unary)
    with np.errstate(invalid="ignore"):
        return float(np.max(np.where(valid, np.abs(got - want), 0.0)))


def solve(g: Graph, *, eps: float = 1e-9, max_rounds: int = 5000,
          dtype=np.float64) -> np.ndarray:
    """Synchronous BP from uniform messages until no message moves by
    ``eps``. ``dtype`` rounds the messages after every update: a lower
    precision than float64 stands in for a lower-precision system."""
    _, dst, _ = directed(g)
    valid = np.isfinite(g.log_unary[dst])
    logm = np.where(valid, -np.log(g.n_states[dst])[:, None], -np.inf)
    for _ in range(max_rounds):
        new = update(g, logm).astype(dtype).astype(np.float64)
        with np.errstate(invalid="ignore"):
            moved = np.max(np.where(valid, np.abs(new - logm), 0.0))
        logm = new
        if moved < eps:
            break
    return logm
