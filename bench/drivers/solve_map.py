"""Closed loop of MAP solves: ``solve.py``'s loop, checked by max-product.

Traffic keys, the loop, its keys per cycle, the copy of each answer to the
host and every ``Outcome`` field are those of ``bench.drivers.solve``: its
``run`` runs unchanged, bound to the max-product yardstick in place of the
sum-product one. The check (``bench.reference_map``) asks of every answer
of the window that its messages be a fixed point of one exact max-product
update within the configuration's limit and that its beliefs be the ones
those messages give; the traced work is the max-product count
(``bench.work_map``).

Each answer's labeling (the argmax of its beliefs) is also scored, as
information and not as a check: its +-1 accuracy against the truth and its
energy against the truth's, one ``map answer`` line each, with the check's
host time per answer.
"""

from __future__ import annotations

import time
import types

import numpy as np

from bench import reference_map, work_map
from bench.drivers import solve


class _Check:
    """``solve.run``'s ``reference``: the max-product residual and belief
    gap, which also scores each answer's labeling."""

    def __init__(self):
        self.scored = []
        self.seconds = 0.0

    def residual(self, g, logm):
        t0 = time.perf_counter()
        r = reference_map.residual(g, logm)
        self.seconds += time.perf_counter() - t0
        return r

    def belief_gap(self, g, logm, got):
        t0 = time.perf_counter()
        gap = reference_map.belief_gap(g, logm, got)
        self.seconds += time.perf_counter() - t0
        labels = np.argmax(np.where(np.isfinite(g.log_unary), got, -np.inf),
                           axis=1)
        self.scored.append((float(np.mean(np.abs(labels - g.truth) <= 1)),
                            reference_map.energy(g, labels),
                            reference_map.energy(g, g.truth)))
        return gap


#: The module globals of ``solve.run`` that this driver rebinds.
REBOUND = ("reference", "work")


def run(ctx):
    missing = set(REBOUND) - set(solve.run.__code__.co_names)
    if missing:
        raise RuntimeError(f"bench.drivers.solve.run no longer reads the "
                           f"globals {sorted(missing)}: the max-product "
                           f"check and work count would not be used")
    check = _Check()
    loop = types.FunctionType(
        solve.run.__code__,
        dict(solve.run.__globals__, reference=check, work=work_map))
    out = loop(ctx)
    for k, (acc, e, e_truth) in enumerate(check.scored):
        print(f"map answer {k}: acc+-1 {acc:.4f} energy {e:.2f} "
              f"truth energy {e_truth:.2f}", flush=True)
    if check.scored:
        print(f"map check: {check.seconds / len(check.scored):.3f} s of "
              f"host time per answer", flush=True)
    return out
