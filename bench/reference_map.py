"""Plain max-product BP on a pairwise MRF, in float64 numpy.

The yardstick of the MAP cells: it decides ``correct`` as
``bench.reference`` does for sum-product, and imports nothing of the system
under test. Messages live on ``bench.reference``'s directed edges (``2k``
runs ``edges[k, 0] -> edges[k, 1]``, ``2k + 1`` the reverse) and are log
values over the destination's states, normalized so that their largest
valid entry is 0. One exact update is

    m'[u -> v](x_v) = max_{x_u} (log_pair[x_u, x_v] + log_unary[u](x_u)
                                 + sum of the messages into u but v's)

renormalized to max 0. Any fixed point is a valid answer, whatever the
schedule that reached it. Edges are taken in blocks, so that the (B, S, S)
candidates fit the host's memory at image sizes. The beliefs that messages
give are the sum-product module's (the program's normalization of its
beliefs, a log-sum-exp to 0, is the same in both semirings).
"""

from __future__ import annotations

import numpy as np

from bench.reference import Graph, belief_gap, directed  # noqa: F401

#: Undirected edges per block of the update.
BLOCK = 16384


def _incoming(g: Graph, dst: np.ndarray, logm: np.ndarray) -> np.ndarray:
    finite = np.where(np.isfinite(logm), logm, 0.0)
    out = np.zeros(g.log_unary.shape)
    np.add.at(out, dst, finite)
    return out


def _max_normalized(m: np.ndarray, valid: np.ndarray) -> np.ndarray:
    m = np.where(valid, m, -np.inf)
    top = np.max(m, axis=1, keepdims=True)
    top = np.where(np.isfinite(top), top, 0.0)
    return np.where(valid, m - top, -np.inf)


def update(g: Graph, logm: np.ndarray) -> np.ndarray:
    """One exact max-product update of every directed edge: (2E, S)."""
    src, dst, rev = directed(g)
    finite_m = np.where(np.isfinite(logm), logm, 0.0)
    pre = g.log_unary[src] + _incoming(g, dst, logm)[src] - finite_m[rev]
    pre = np.where(np.isfinite(g.log_unary[src]), pre, -np.inf)
    out = np.empty_like(logm)
    for a in range(0, len(g.edges), BLOCK):
        b = min(a + BLOCK, len(g.edges))
        table = g.log_pair[a:b]
        out[2 * a:2 * b:2] = np.max(
            table + pre[2 * a:2 * b:2][:, :, None], axis=1)
        out[2 * a + 1:2 * b:2] = np.max(
            np.swapaxes(table, 1, 2) + pre[2 * a + 1:2 * b:2][:, :, None],
            axis=1)
    return _max_normalized(out, np.isfinite(g.log_unary[dst]))


def residual(g: Graph, logm: np.ndarray) -> float:
    """Largest change one exact update makes to any message, both
    normalized to max 0, over the valid states of every directed edge."""
    _, dst, _ = directed(g)
    valid = np.isfinite(g.log_unary[dst])
    logm = _max_normalized(logm, valid)
    new = update(g, logm)
    with np.errstate(invalid="ignore"):
        return float(np.max(np.where(valid, np.abs(new - logm), 0.0)))


def decode(g: Graph, logm: np.ndarray) -> np.ndarray:
    """(V,) each vertex's best state under the messages ``logm``."""
    _, dst, _ = directed(g)
    b = g.log_unary + _incoming(g, dst, logm)
    return np.argmax(np.where(np.isfinite(g.log_unary), b, -np.inf), axis=1)


def energy(g: Graph, labels: np.ndarray) -> float:
    """Negative log-potential of a labeling: the MAP objective."""
    u, v = g.edges[:, 0], g.edges[:, 1]
    e = np.sum(g.log_unary[np.arange(len(labels)), labels])
    e += np.sum(g.log_pair[np.arange(len(u)), labels[u], labels[v]])
    return -float(e)


def map_solve(g: Graph, *, eps: float = 1e-10,
              max_rounds: int = 5000) -> np.ndarray:
    """Synchronous max-product from zero messages until no message moves by
    ``eps``; exact on a tree."""
    _, dst, _ = directed(g)
    valid = np.isfinite(g.log_unary[dst])
    logm = np.where(valid, 0.0, -np.inf)
    for _ in range(max_rounds):
        new = update(g, logm)
        with np.errstate(invalid="ignore"):
            moved = np.max(np.where(valid, np.abs(new - logm), 0.0))
        logm = new
        if moved < eps:
            break
    return logm
