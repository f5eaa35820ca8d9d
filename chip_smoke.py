"""Chip smoke test: the BP engine and its serving stack, end to end, on TPU.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: sharded and banded BP only

One chip runs the normal entry points (``BPConfig`` -> ``BPEngine`` ->
``serve_async``) with the fused Pallas kernel compiled for the chip
(``backend="pallas"``, no interpreter):

1. exactness: a 12-vertex chain (a tree, where BP is exact) and a 4x4
   Ising grid, ``pallas`` and ``ref`` against brute-force / variable-
   elimination marginals;
2. paper scale: Ising 200x200 (the source paper's largest grid) with rnbp
   at C=2.5 and lbp at C=2.0 (where lbp converges), ``pallas`` against
   ``ref``;
3. real size: the 288x384, 16-label stereo MRF (the Middlebury Tsukuba
   pair's size) through ``engine.run``; its compiled chunk must contain the
   kernel (``tpu_custom_call``), so a kernel that quietly interprets fails;
4. serving: a mixed stream with a Tsukuba-size frame and two LDPC codewords
   (32-state check vertices: the kernel's widest block here) through
   ``serve_async``; every request must complete and converge.

Four chips run ``run_bp_sharded`` (lbp, rnbp) and ``run_bp_banded`` (lbp)
on Ising 200x200 over a mesh of all four devices, against the one-chip
``ref`` engine, within the contracts of the multi-device tests.

Every input is generated here from fixed seeds. A failed check raises, and
the script exits non-zero. Without a TPU it exits non-zero before any work,
and it needs the checkout's ``src/`` next to it. The last line of standard
output is the only JSON line: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# Sizes. ISING_N is the source paper's largest grid; STEREO is the size of
# the Middlebury Tsukuba pair (288x384, 16 disparities); the 504-bit
# (3,6)-regular LDPC code is a classic short code length.
ISING_N = 200
STEREO = (288, 384, 16)
SMALL_STEREO = (72, 96, 16)
LDPC_N = 504
SERVE_ISING_N = 100
CHAIN_N = 10_000


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips; found "
                 f"{len(devices)}")
    return devices


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def max_belief_diff(a, b, state_mask) -> float:
    mask = np.asarray(state_mask)
    return float(np.max(np.abs(np.where(mask, np.asarray(a) - np.asarray(b),
                                        0.0))))


def engine(backend: str, scheduler: str, eps: float, max_rounds: int = 2000):
    from repro.core import BPConfig, BPEngine
    return BPEngine(BPConfig(scheduler=scheduler, eps=eps,
                             max_rounds=max_rounds, history=False,
                             backend=backend))


# --------------------------------------------------------------- phases --

#: pallas against ref: beliefs and round drift, as in
#: tests/test_backend_conformance.py (the kernel reassociates reductions).
PALLAS_ATOL = 1e-4
#: eps for lbp and for the four-chip runs on Ising 200x200: the paper's
#: and the multi-device tests' 1e-5. It needs f32 exp/log good to about an
#: ulp (``repro.kernels.explog``); with the chip's own, lbp at C=2.0 stalled
#: with residuals near 1e-4.
LBP_EPS = 1e-5


def round_drift_ok(a: int, b: int) -> bool:
    return abs(a - b) <= max(10, b // 5)


def phase_exact() -> None:
    """Small graphs against the exact oracles (``repro.core.exact``)."""
    from repro.core.exact import brute_force_marginals, ve_marginals
    from repro.core.graph import build_pgm
    from repro.pgm import small_ising

    # The chain of tests/test_bp_core.py: a tree, so BP is exact (2e-4,
    # its tolerance).
    rng = np.random.default_rng(3)
    unary = [rng.uniform(1e-3, 1.0, size=2) for _ in range(12)]
    lam = rng.uniform(-0.5, 0.5, size=11)
    pair = [np.array([[np.exp(3 * l), np.exp(-3 * l)],
                      [np.exp(-3 * l), np.exp(3 * l)]]) for l in lam]
    edges = np.stack([np.arange(11), np.arange(1, 12)], axis=1)
    chain = build_pgm(12, edges, unary, pair)
    chain_exact = brute_force_marginals(12, edges, unary, pair)
    # A loopy 4x4 grid: BP approximates; 2e-2 is the loopy tolerance of
    # tests/test_zoo.py. Both oracles must agree first.
    grid, nv, g_edges, g_unary, g_pair = small_ising(4, 2.0, seed=0)
    grid_exact = brute_force_marginals(nv, g_edges, g_unary, g_pair)
    grid_ve = ve_marginals(nv, g_edges, g_unary, g_pair)
    check(all(np.allclose(a, b, atol=1e-8)
              for a, b in zip(grid_exact, grid_ve)),
          "brute force and variable elimination disagree on ising4x4")

    for name, pgm, exact, tol in [("chain12", chain, chain_exact, 2e-4),
                                  ("ising4x4", grid, grid_exact, 2e-2)]:
        n = len(exact)
        res = {}
        for backend in ("ref", "pallas"):
            r = engine(backend, "lbp", 1e-5).run(pgm, jax.random.key(0))
            check(bool(r.converged), f"{name}/{backend} did not converge")
            got = np.exp(np.asarray(r.beliefs, np.float64))[:n, :2]
            err = float(np.max(np.abs(got - np.stack(exact))))
            log(f"exact {name} {backend}: rounds={int(r.rounds)} "
                f"max|p-p_exact|={err:.3e} (bound {tol:g})")
            check(err <= tol, f"{name}/{backend} marginals off by {err}")
            res[backend] = r
        d = max_belief_diff(res["pallas"].beliefs, res["ref"].beliefs,
                            pgm.state_mask)
        log(f"exact {name} pallas-vs-ref: max|dbelief|={d:.3e} "
            f"(bound {PALLAS_ATOL:g})")
        check(d <= PALLAS_ATOL, f"{name} pallas vs ref beliefs {d}")
        check(round_drift_ok(int(res["pallas"].rounds),
                             int(res["ref"].rounds)),
              f"{name} pallas vs ref rounds drift")


def phase_paper_scale() -> None:
    """Ising ISING_N^2: rnbp at C=2.5 and lbp at C=2.0, pallas vs ref."""
    from repro.pgm import ising_grid_fast

    # rnbp is stochastic: each converged run sits within eps of the fixed
    # point per message, so with degree <= 4 two runs' log-beliefs differ
    # by at most about 2 * 2 * 4 * eps. lbp's eps is LBP_EPS.
    eps_rnbp = 1e-3
    runs = [("rnbp", 2.5, eps_rnbp, 16 * eps_rnbp),
            ("lbp", 2.0, LBP_EPS, PALLAS_ATOL)]
    for sched, c, eps, bound in runs:
        pgm = ising_grid_fast(ISING_N, c, seed=0)
        res = {}
        for backend in ("ref", "pallas"):
            eng = engine(backend, sched, eps)
            key = jax.random.key(0)
            _, cold = timed(lambda: eng.run(pgm, key))
            r, warm = timed(lambda: eng.run(pgm, key))
            log(f"ising{ISING_N} C={c} {sched} {backend}: "
                f"rounds={int(r.rounds)} converged={bool(r.converged)} "
                f"max_residual={float(r.max_residual):.2e} "
                f"wall_cold={cold:.3f}s wall_warm={warm:.3f}s")
            check(bool(r.converged),
                  f"ising{ISING_N}/{sched}/{backend} did not converge")
            res[backend] = r
        d = max_belief_diff(res["pallas"].beliefs, res["ref"].beliefs,
                            pgm.state_mask)
        log(f"ising{ISING_N} C={c} {sched} pallas-vs-ref: "
            f"max|dbelief|={d:.3e} (bound {bound:g})")
        check(d <= bound,
              f"ising{ISING_N}/{sched} pallas vs ref beliefs {d}")
        if sched == "lbp":
            check(round_drift_ok(int(res["pallas"].rounds),
                                 int(res["ref"].rounds)),
                  f"ising{ISING_N}/lbp pallas vs ref rounds drift")


def chunk_hlo(eng, state) -> str:
    """Compiled text of the engine chunk ``eng.step(state)`` dispatches
    (the same jitted function and static arguments)."""
    import jax.numpy as jnp

    from repro.core.engine import _carry_of, _chunk_single

    cfg = eng.config
    limit = jnp.minimum(state.rounds + cfg.max_rounds, cfg.max_rounds)
    return _chunk_single.lower(
        state.graph, _carry_of(state), limit, cfg.eps,
        scheduler=eng.scheduler, damping=cfg.damping,
        update_fn=eng.update_fn,
        track_history=cfg.history).compile().as_text()


def decoded(beliefs, n: int, s: int):
    """Max-marginal labels of the first ``n`` vertices over ``s`` states."""
    return np.argmax(np.asarray(beliefs)[:n, :s], axis=1)


def phase_stereo() -> None:
    """Tsukuba-size stereo MRF through ``engine.run`` with the kernel."""
    from repro.pgm import stereo_mrf

    h, w, d = STEREO
    inst, build = timed(lambda: stereo_mrf(h, w, d, seed=0))
    log(f"stereo {h}x{w}x{d}: E={inst.pgm.n_real_edges} directed edges, "
        f"tables {inst.pgm.log_psi_e.nbytes / 2**20:.0f} MiB, "
        f"built in {build:.2f}s")
    eng = engine("pallas", "rnbp", 1e-3)
    key = jax.random.key(0)
    t0 = time.perf_counter()
    hlo = chunk_hlo(eng, eng.init(inst.pgm, key))
    log(f"stereo chunk compiled in {time.perf_counter() - t0:.1f}s; "
        f"tpu_custom_call in HLO: {'tpu_custom_call' in hlo}")
    check("tpu_custom_call" in hlo,
          "the pallas chunk's HLO has no tpu_custom_call: the kernel is "
          "not compiled for the chip")
    res, wall = timed(lambda: eng.run(inst.pgm, key))
    labels = decoded(res.beliefs, h * w, d)
    obs = np.clip(np.round(inst.obs), 0, d - 1).astype(int)
    acc_bp, acc_obs = inst.accuracy(labels), inst.accuracy(obs)
    e_bp, e_truth = inst.energy(labels), inst.energy(inst.truth)
    log(f"stereo {h}x{w}x{d} rnbp pallas: rounds={int(res.rounds)} "
        f"converged={bool(res.converged)} wall_cold={wall:.3f}s "
        f"acc_bp={acc_bp:.4f} acc_obs={acc_obs:.4f} energy_bp={e_bp:.2f} "
        f"energy_truth={e_truth:.2f}")
    check(bool(res.converged), "stereo did not converge")
    check(acc_bp >= acc_obs, "stereo BP is less accurate than its input")


def phase_serving() -> None:
    """A mixed stream through ``serve_async`` on the pallas backend."""
    from repro.core import serve_async
    from repro.pgm import chain_graph, ising_grid_fast, ldpc_code, stereo_mrf

    frame = stereo_mrf(*STEREO, seed=1)
    small = stereo_mrf(*SMALL_STEREO, seed=2)
    codes = [ldpc_code(LDPC_N, snr_db=3.0, seed=s) for s in (0, 1)]
    stream = [ising_grid_fast(SERVE_ISING_N, 2.5, seed=1), codes[0].pgm,
              frame.pgm, chain_graph(CHAIN_N, seed=2), codes[1].pgm,
              ising_grid_fast(SERVE_ISING_N, 2.5, seed=2), small.pgm]
    kinds = ["ising", "ldpc", "stereo", "chain", "ldpc", "ising", "stereo"]
    eng = engine("pallas", "rnbp", 1e-3)
    t0 = time.perf_counter()
    rep = serve_async(eng, iter(stream), jax.random.key(0), max_batch=2,
                      chunk_rounds=64)
    wall = time.perf_counter() - t0
    check(len(rep.records) == len(stream), "serving lost requests")
    for rec in sorted(rep.records, key=lambda r: r.rid):
        r = rec.result
        log(f"serve rid={rec.rid} {kinds[rec.rid]}: status={rec.status} "
            f"converged={bool(r.converged)} rounds={int(r.rounds)} "
            f"latency={1e3 * rec.latency_s:.1f}ms "
            f"(queue {1e3 * (rec.t_admit - rec.t_enqueue):.1f}ms)")
        check(rec.status == "completed" and bool(r.converged),
              f"request {rec.rid} ({kinds[rec.rid]}) not served to "
              "convergence")
    results = rep.results
    for rid, code in ((1, codes[0]), (4, codes[1])):
        bits = decoded(results[rid].beliefs, code.n_bits, 2)
        log(f"serve rid={rid} ldpc: coded_errors={code.coded_errors(bits)} "
            f"uncoded_errors={code.uncoded_errors}")
        check(code.coded_errors(bits) <= code.uncoded_errors,
              f"LDPC request {rid} decoded worse than uncoded")
    for rid, inst in ((2, frame), (6, small)):
        n, d = inst.height * inst.width, inst.n_disp
        obs = np.clip(np.round(inst.obs), 0, d - 1).astype(int)
        acc = inst.accuracy(decoded(results[rid].beliefs, n, d))
        log(f"serve rid={rid} stereo: acc_bp={acc:.4f} "
            f"acc_obs={inst.accuracy(obs):.4f}")
        check(acc >= inst.accuracy(obs), f"stereo request {rid} is less "
              "accurate than its input")
    st = rep.stats
    log(f"serving: {len(rep.records)}/{len(stream)} completed in "
        f"{wall:.2f}s (compiles included); chunks={st.chunks} "
        f"evacuated={st.evacuated} useful_sweeps={st.useful_sweeps} "
        f"wasted_sweeps={st.wasted_sweeps}")


def phase_four_chips() -> None:
    """Sharded and banded BP over four chips against the one-chip ref."""
    from repro.core import messages as M
    from repro.dist import (make_bp_mesh, partition_banded, run_bp_banded,
                            run_bp_sharded, shard_pgm)
    from repro.pgm import ising_grid_fast

    check(len(jax.devices()) == 4, f"need 4 devices, have "
          f"{len(jax.devices())}")
    mesh = make_bp_mesh(4)
    log(f"mesh {dict(mesh.shape)}: {[str(d) for d in mesh.devices.flat]}")
    key = jax.random.key(0)
    # The contracts of tests/test_system.py (sharded beliefs within 5e-3)
    # and tests/test_perf_variants.py (banded lbp rounds == single device).
    # lbp runs at C=2.0, where it converges on this grid; rnbp at C=2.5.
    eps, max_rounds = LBP_EPS, 4000
    refs = {}
    for sched, c in (("lbp", 2.0), ("rnbp", 2.5)):
        pgm = ising_grid_fast(ISING_N, c, seed=0)
        ref, ref_wall = timed(lambda: engine("ref", sched, eps, max_rounds)
                              .run(pgm, key))
        check(bool(ref.converged), f"one-chip ref {sched} did not converge "
              f"(max residual {float(ref.max_residual):.2e})")
        refs[sched] = pgm, ref
        for s in shard_pgm(pgm, mesh).log_psi_e.addressable_shards:
            log(f"sharded {sched} edges {s.index[0]} on {s.device}")
        res, wall = timed(lambda: run_bp_sharded(
            pgm, sched, mesh, key, eps=eps, max_rounds=max_rounds))
        d = max_belief_diff(res.beliefs, ref.beliefs, pgm.state_mask)
        log(f"ising{ISING_N} C={c} {sched}: one-chip ref "
            f"rounds={int(ref.rounds)} ({ref_wall:.2f}s); sharded x4 "
            f"rounds={int(res.rounds)} converged={bool(res.converged)} "
            f"({wall:.2f}s) max|dbelief|={d:.3e} (bound 5e-3)")
        check(bool(res.converged), f"sharded {sched} did not converge")
        check(d <= 5e-3, f"sharded {sched} beliefs off by {d}")

    pgm, ref = refs["lbp"]
    part = partition_banded(pgm, 4)
    log(f"banded lbp: {part.n} bands of {part.band_len} slots, vertex "
        f"blocks {part.v_lo.tolist()}")
    (logm, rounds, done), wall = timed(lambda: run_bp_banded(
        part, "lbp", mesh, key, eps=eps, max_rounds=max_rounds))
    d = max_belief_diff(M.beliefs(pgm, logm), ref.beliefs, pgm.state_mask)
    log(f"ising{ISING_N} C=2.0 banded x4 lbp: rounds={int(rounds)} "
        f"(one-chip {int(ref.rounds)}) converged={bool(done)} "
        f"({wall:.2f}s) max|dbelief|={d:.3e} (bound 5e-3)")
    check(bool(done), "banded lbp did not converge")
    check(int(rounds) == int(ref.rounds),
          "banded lbp round count differs from the one-chip run")
    check(d <= 5e-3, f"banded lbp beliefs off by {d}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded/banded phase on 4 chips")
    args = ap.parse_args()
    devices = require_tpu(args.chips)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, SRC)
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    log(f"devices: {[str(d) for d in devices]}")

    phases = ([phase_four_chips] if args.chips == 4 else
              [phase_exact, phase_paper_scale, phase_stereo, phase_serving])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"phase {phase.__name__} ok in {time.perf_counter() - t0:.1f}s")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
