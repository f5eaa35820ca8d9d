"""Quickstart: Randomized Belief Propagation on an Ising grid.

Reproduces the paper's core result in miniature: on a hard Ising grid,
synchronous (Loopy) BP stalls while RnBP's randomized frontier converges,
at the same per-round cost and with no sort-and-select overhead.

Everything routes through the unified engine: one serializable ``BPConfig``
(scheduler spec string + kwargs) drives ``BPEngine.run``.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import BPConfig, BPEngine

from repro.pgm import ising_grid


def main():
    use_compile_cache()
    # C controls difficulty (paper SSIII-C); this instance is in the regime
    # where synchronous LBP oscillates forever but randomized scheduling
    # converges (paper Fig 4b)
    pgm = ising_grid(40, C=2.5, seed=2)
    print(f"Ising 40x40, C=2.5: {pgm.n_real_vertices} vertices, "
          f"{pgm.n_real_edges} directed edges")

    base = BPConfig(eps=1e-3, max_rounds=8000)
    for name, spec, kwargs in [
        ("LBP  (all messages)      ", "lbp", {}),
        ("RBP  (top-k, p=1/128)    ", "rbp", {"p": 1 / 128}),
        ("RnBP (random, LowP=0.4)  ", "rnbp", {"low_p": 0.4}),
        ("RnBP (random, LowP=0.1)  ", "rnbp", {"low_p": 0.1}),
    ]:
        engine = BPEngine(base, scheduler=spec, scheduler_kwargs=kwargs)
        t0 = time.perf_counter()
        res = engine.run(pgm, jax.random.key(0))
        jax.block_until_ready(res.logm)
        dt = time.perf_counter() - t0
        status = "converged" if bool(res.converged) else "STALLED  "
        print(f"{name} {status} rounds={int(res.rounds):5d} "
              f"committed-updates={int(res.updates):10d} "
              f"wall={dt:6.2f}s")

    engine = BPEngine(base, scheduler="rnbp", scheduler_kwargs={"low_p": 0.4})
    res = engine.run(pgm, jax.random.key(0))
    beliefs = np.exp(np.asarray(res.beliefs))[:pgm.n_real_vertices]
    print("\nfirst 5 marginals P(x_i = 1):", np.round(beliefs[:5, 1], 4))


if __name__ == "__main__":
    main()
