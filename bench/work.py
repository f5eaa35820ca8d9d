"""The message update's work, counted from the algorithm, and its roofline.

Per round, BP computes a candidate message for every real directed edge
``u -> v``. At the real state counts ``a = n_states[u]`` and ``b =
n_states[v]`` that update

- reads the edge's table (a*b values), the source's a-vector input and the
  old b-vector message (the residual needs it), and writes the new
  b-vector message and one residual, all float32:
  ``4 * (a*b + a + 2b + 1)`` bytes;
- does, per destination state, one log-sum-exp over the a source states
  (add the table, max, shift, exp, sum: 5a, a transcendental counted as one
  operation), then normalizes over the b states (5 per state) and takes
  the residual (3 per state): ``5ab + 8b`` operations.

No padding, block layout or count taken from the program enters here, so
a change that removes padding or fuses work moves the share honestly and
the count cannot read above 100%. It counts every edge in every round; a
program that computes fewer candidates than all edges per round would make
it stale.
"""

from __future__ import annotations

import json
import os

import numpy as np

from bench.reference import Graph, directed

#: Device ops of the fused Pallas update, by the name the trace gives them.
UPDATE_KERNEL = r"^%fused_update_t[.\d]* = "

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def per_round(g: Graph) -> tuple[float, float]:
    """(operations, bytes) of one round of updates over all of ``g``'s
    directed edges."""
    src, dst, _ = directed(g)
    a = g.n_states[src].astype(np.float64)
    b = g.n_states[dst].astype(np.float64)
    ops = float(np.sum(5 * a * b + 8 * b))
    nbytes = float(np.sum(4 * (a * b + a + 2 * b + 1)))
    return ops, nbytes


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; add them with their source")
    return table[device_kind]


def roofline_share(ops: float, nbytes: float, kernel_s: float,
                   pk: dict) -> tuple[float, str]:
    """(share in %, the bound that sets it): the least time the chip could
    take for the work over the time the kernel took."""
    t_ops = ops / pk["flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "flops"
    return 100.0 * max(t_ops, t_bytes) / kernel_s, bound


def update_roofline(o) -> float | None:
    """Share of the update's roofline over the traced window of outcome
    ``o``; None without chip peaks, work or kernel events."""
    if o.trace is None or o.traced_work is None or o.peaks is None:
        return None
    kernel_s = o.trace.op_seconds(UPDATE_KERNEL)
    if kernel_s <= 0:
        return None
    return roofline_share(*o.traced_work, kernel_s, o.peaks)[0]
