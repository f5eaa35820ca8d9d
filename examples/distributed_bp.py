"""Multi-device BP via shard_map (run with forced host devices on CPU).

Demonstrates both pod-scale paths in ``repro.dist``:

- **sharded**: edges split over a 1-D mesh, per-vertex sums combined with
  one exact psum per round; works for any graph and any scheduler.
- **banded**: contiguous edge bands + neighbor-only halo exchange; only for
  banded graphs (grids/chains) but round-exact vs the single-device loop.

Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/distributed_bp.py [--size N]
"""

import argparse
import os

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.core import BPConfig, BPEngine, LBP, RnBP
from repro.dist import make_bp_mesh, run_bp_sharded
from repro.dist.bp_banded import partition_banded, run_bp_banded
from repro.pgm import ising_grid_fast


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=32,
                    help="Ising grid side (default 32; paper-ish scale 48+)")
    args = ap.parse_args()

    print(f"devices: {len(jax.devices())}")
    mesh = make_bp_mesh()
    pgm = ising_grid_fast(args.size, 2.5, seed=0)
    print(f"Ising {args.size}x{args.size}: {pgm.n_real_edges} directed "
          f"edges over {mesh.devices.size} shards")

    engine = BPEngine(BPConfig(scheduler="rnbp",
                               scheduler_kwargs={"low_p": 0.7},
                               eps=1e-3, max_rounds=6000))
    ref = engine.run(pgm, jax.random.key(0))
    print(f"single-device RnBP: rounds={int(ref.rounds)} "
          f"converged={bool(ref.converged)}")

    for sched in [LBP(), RnBP(low_p=0.7)]:
        t0 = time.perf_counter()
        res = run_bp_sharded(pgm, sched, mesh, jax.random.key(0),
                             eps=1e-3, max_rounds=6000)
        jax.block_until_ready(res.beliefs)
        diff = float(jnp.max(jnp.abs(jnp.where(
            pgm.state_mask, res.beliefs - ref.beliefs, 0.0))))
        print(f"sharded {type(sched).__name__:5s}: "
              f"rounds={int(res.rounds):5d} "
              f"converged={bool(res.converged)} "
              f"max-belief-diff-vs-ref={diff:.2e} "
              f"wall={time.perf_counter() - t0:.2f}s")

    # Banded halo-exchange path: round-exact LBP on the same grid.
    lbp_ref = BPEngine(BPConfig(scheduler="lbp", eps=1e-3,
                                max_rounds=6000)).run(pgm, jax.random.key(0))
    part = partition_banded(pgm, mesh.devices.size)
    t0 = time.perf_counter()
    _, rounds, done = run_bp_banded(part, LBP(), mesh, jax.random.key(0),
                                    eps=1e-3, max_rounds=6000)
    print(f"banded  LBP  : rounds={int(rounds):5d} converged={bool(done)} "
          f"round-parity-vs-ref={int(rounds) == int(lbp_ref.rounds)} "
          f"wall={time.perf_counter() - t0:.2f}s")


if __name__ == "__main__":
    main()
