"""The max-product update's work, counted from the algorithm.

``bench.work`` counts the sum-product update. The max-product update of a
real directed edge ``u -> v`` at ``a = n_states[u]``, ``b = n_states[v]``
moves the same bytes (the edge's table, the source's a-vector, the old and
new b-vector messages and one residual, float32), and does, per
destination state, a max over the a source states (add the table, max:
2a), then masks, shifts to max 0 over the b states and takes the residual
(5 per state): ``2ab + 5b`` operations. The roofline against the chip's
peaks is ``bench.work``'s.
"""

from __future__ import annotations

import numpy as np

from bench import work
from bench.reference import Graph, directed


def per_round(g: Graph) -> tuple[float, float]:
    """(operations, bytes) of one round of max-product updates over all of
    ``g``'s directed edges."""
    src, dst, _ = directed(g)
    a = g.n_states[src].astype(np.float64)
    b = g.n_states[dst].astype(np.float64)
    return float(np.sum(2 * a * b + 5 * b)), work.per_round(g)[1]
