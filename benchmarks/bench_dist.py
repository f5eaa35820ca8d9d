"""Distributed BP: single-device vs 8-forced-host-device sweep throughput.

The device count is locked at first jax use, so the measurements run in a
child process with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the same trick the dist tests use); the parent relays the CSV rows. The
child pins ``JAX_PLATFORMS=cpu`` (``repro.dist.cpu_child_env``), so
every number here is a CPU number on any host, as the JSON's ``backend``
field says. Three paths over the same graphs, all LBP (deterministic, so
sweeps/sec is the clean unit):

- **single**: the engine's reference backend on one device,
- **sharded**: ``repro.dist`` shard_map backend, edge axis over 8 shards
  (one (V, S) psum per round),
- **banded**: ``repro.dist.bp_banded`` halo-exchange path, 8 contiguous
  bands (neighbor-only ppermute per round) -- plus its round-count parity
  vs the reference, the correctness invariant the speed numbers ride on.

On a 1-2 core CI host the 8 "devices" share the same silicon, so sharding
adds collective overhead without adding FLOPs -- expect <= 1x, like the
warm-batch numbers in BENCH_batch.json. The numbers are recorded anyway
(``benchmarks/out/BENCH_dist.json``, uploaded as a CI artifact) so the
trajectory is honest and a real multi-chip run slots into the same file.
"""

from __future__ import annotations

import json
import platform
import subprocess
import sys
import time


def _child(full: bool) -> None:
    import jax
    from benchmarks.common import emit, out_path
    from repro.core import BPConfig, BPEngine, LBP
    from repro.dist import make_bp_mesh, make_sharded_engine, shard_pgm
    from repro.dist.bp_banded import partition_banded, run_bp_banded
    from repro.pgm import chain_graph, ising_grid_fast

    grid_n = 48 if full else 32
    chain_n = 20000 if full else 4000
    budget = 512 if full else 192        # sweep budget per measurement
    eps = 1e-12                          # unreachable: pin the round count
    mesh = make_bp_mesh()
    n_dev = int(mesh.devices.size)

    record = {
        "suite": "dist", "devices": n_dev,
        "backend": jax.default_backend(), "platform": platform.machine(),
        "unix_time": time.time(), "graphs": {},
    }

    def timed(fn):
        out = fn()                       # warm-up/compile
        jax.block_until_ready(out[0])
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out[0])
        return out, time.perf_counter() - t0

    for gname, pgm in [(f"ising{grid_n}", ising_grid_fast(grid_n, 2.5,
                                                          seed=0)),
                       (f"chain{chain_n}", chain_graph(chain_n, seed=0))]:
        single = BPEngine(BPConfig(scheduler="lbp", eps=eps,
                                   max_rounds=budget, history=False))
        (res, wall_1) = timed(lambda: (single.run(pgm, jax.random.key(0))
                                       .rounds,))
        rounds_1 = int(res[0])   # == budget unless the run hit a fixed point

        shard_eng = make_sharded_engine("lbp", mesh, eps=eps,
                                        max_rounds=budget, history=False)
        spgm = shard_pgm(pgm, mesh)
        (res_s, wall_s) = timed(lambda: (shard_eng.run(
            spgm, jax.random.key(0)).rounds,))
        rounds_s = int(res_s[0])

        part = partition_banded(pgm, n_dev)
        (out_b, wall_b) = timed(lambda: run_bp_banded(
            part, LBP(), mesh, jax.random.key(0), eps=eps,
            max_rounds=budget))
        rounds_b = int(out_b[1])

        # Round-parity spot check at a realistic eps (the invariant
        # TestBandedBP pins; cheap enough to keep in the bench).
        ref = BPEngine(BPConfig(scheduler="lbp", eps=1e-5, max_rounds=6000,
                                history=False)).run(pgm, jax.random.key(0))
        _, rounds_par, done_par = run_bp_banded(
            part, LBP(), mesh, jax.random.key(0), eps=1e-5, max_rounds=6000)
        parity = bool(done_par) and int(rounds_par) == int(ref.rounds)

        sps = {"single": rounds_1 / wall_1, "sharded": rounds_s / wall_s,
               "banded": rounds_b / wall_b}
        for path, v in sps.items():
            emit(f"dist/{gname}/{path}", 1e6 / v,
                 f"sweeps_per_s={v:.1f};speedup_vs_single="
                 f"{v / sps['single']:.2f}")
        emit(f"dist/{gname}/banded_round_parity", 0.0,
             f"match={parity};rounds={int(rounds_par)}")
        record["graphs"][gname] = {
            "edges": pgm.n_real_edges, "sweeps": rounds_1,
            "single_sweeps_per_s": sps["single"],
            "sharded_sweeps_per_s": sps["sharded"],
            "banded_sweeps_per_s": sps["banded"],
            "sharded_speedup": sps["sharded"] / sps["single"],
            "banded_speedup": sps["banded"] / sps["single"],
            "banded_round_parity": parity,
        }

    with open(out_path("BENCH_dist.json"), "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")


def run(full: bool = False, n_graphs: int = 0) -> None:
    """Parent entry (benchmarks.run registry): re-exec in a child with 8
    forced host devices and relay its output."""
    from repro.dist import cpu_child_env
    del n_graphs
    cmd = [sys.executable, "-m", "benchmarks.bench_dist", "--child"]
    if full:
        cmd.append("--full")
    out = subprocess.run(cmd, env=cpu_child_env(8), capture_output=True,
                         text=True, timeout=3600)
    sys.stdout.write(out.stdout)
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-4000:])
        raise RuntimeError("bench_dist child failed")


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child("--full" in sys.argv)
    else:
        run("--full" in sys.argv)
