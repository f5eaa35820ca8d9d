"""Paper Fig. 2 + the relaxation axis: scheduling <-> convergence trade-off.

Two sweeps over the same hard instances (high-coupling Ising grids, the
regime where LBP oscillates and scheduling decides convergence):

1. **fig2** (the original reproduction): frontier multiplier ``p`` for
   Residual Splash vs LBP -- lower p => more graphs converge, slower.
2. **relaxation** (arxiv 2002.11505): the rlx family's relaxation degree
   (``queues`` x ``sample`` fraction) against converged-fraction and
   rounds-to-converge, with exact RBP as the quality baseline. The paper's
   claim under test: relaxed multi-queue selection tracks exact residual
   scheduling's convergence (acceptance: rlx converged-fraction within 10%
   of RBP's) while replacing the global top-k with shard-local per-queue
   sorts.

The relaxation section also runs a **collective audit** in an 8-forced-
host-device child pinned to the CPU (same trick as ``bench_dist``; the
audit records ``backend: cpu``): one BP round (sharded update + frontier
select + commit) is jitted and compiled for rbp and rlx under
``backend="sharded"``, and the optimized HLO is scanned for
cross-shard data movement (``all-gather``/``all-to-all``). RBP's exact
global top-k forces the residual vector to be gathered across shards;
rlx's per-queue top-k must not -- ``eliminates_global_topk`` in the JSON
records exactly that, from the compiled artifact rather than from intent.

Everything lands in ``benchmarks/out/BENCH_tradeoff.json`` (uploaded as a
CI artifact). ``--tiny`` runs a minutes-scale smoke sweep (CI); ``--full``
restores paper scale.
"""

from __future__ import annotations

import json
import platform
import subprocess
import sys
import time


# ------------------------------------------------------------- fig2 sweep --

def _fig2(full: bool, n_graphs: int) -> None:
    from repro.core import LBP, RS
    from repro.pgm import chain_graph, ising_grid

    from benchmarks.common import emit, graph_set, summarize, time_bp

    n = 100 if full else 40
    chain_n = 100_000 if full else 10_000
    datasets = [
        (f"ising{n}x{n}_C2.5", lambda s: ising_grid(n, 2.5, seed=s)),
        (f"chain{chain_n}_C10", lambda s: chain_graph(chain_n, seed=s)),
    ]
    max_rounds = 8000 if full else 4000
    for dname, factory in datasets:
        graphs = graph_set(factory, n_graphs)
        for sched_name, sched in [
            ("LBP", LBP()),
            ("RS_p1/16", RS(p=1.0 / 16)),
            ("RS_p1/64", RS(p=1.0 / 64)),
            ("RS_p1/256", RS(p=1.0 / 256)),
        ]:
            stats = [time_bp(g, sched, max_rounds=max_rounds) for g in graphs]
            s = summarize(stats)
            emit(f"fig2/{dname}/{sched_name}", s["mean_wall_s"] * 1e6,
                 f"conv={s['conv_pct']:.0f}%;rounds={s['mean_rounds']:.0f};"
                 f"updates={s['mean_updates']:.0f}")


# ------------------------------------------------------- relaxation sweep --

def _relaxation_sweep(tiny: bool, full: bool) -> dict:
    """(queues x sample) grid for rlx (+ rlxtree spot) vs exact RBP on hard
    Ising instances; returns the BENCH_tradeoff.json section."""
    from repro.core import RBP, RLX, RLXTree
    from repro.pgm import ising_grid

    from benchmarks.common import emit, graph_set, summarize, time_bp

    if tiny:
        n, n_graphs, max_rounds, p = 10, 2, 1500, 1.0 / 64
        grid = [(4, 0.5), (4, 1.0)]
    elif full:
        n, n_graphs, max_rounds, p = 50, 5, 12000, 1.0 / 256
        grid = [(q, s) for q in (4, 8, 16, 32) for s in (0.25, 0.5, 1.0)]
    else:
        n, n_graphs, max_rounds, p = 24, 4, 8000, 1.0 / 256
        grid = [(q, s) for q in (4, 16) for s in (0.25, 0.5, 1.0)]

    dname = f"ising{n}x{n}_C3.0"
    graphs = graph_set(lambda s: ising_grid(n, 3.0, seed=s), n_graphs)
    section: dict = {"dataset": dname, "n_graphs": n_graphs,
                     "max_rounds": max_rounds, "p": p, "schedulers": {}}

    def measure(label, sched, extra=()):
        stats = [time_bp(g, sched, max_rounds=max_rounds) for g in graphs]
        s = summarize(stats)
        s["conv_frac"] = s.pop("conv_pct") / 100.0
        s.update(extra)
        section["schedulers"][label] = s
        emit(f"relax/{dname}/{label}", max(s["mean_wall_s"], 0.0) * 1e6,
             f"conv={100 * s['conv_frac']:.0f}%;"
             f"rounds={s['mean_rounds']:.0f}")
        return s

    rbp = measure("rbp_exact", RBP(p=p))
    for q, smp in grid:
        measure(f"rlx_q{q}_s{smp}", RLX(queues=q, sample=smp, p=p),
                {"queues": q, "sample": smp})
    measure("rlxtree_q8_s0.5", RLXTree(queues=8, sample=0.5, p=p),
            {"queues": 8, "sample": 0.5})

    # Acceptance: best rlx point within 10% of exact RBP's converged
    # fraction. (On these sizes every relaxation point usually matches RBP
    # at 100%; the margin is for the full-scale run.)
    best_rlx = max(v["conv_frac"] for k, v in section["schedulers"].items()
                   if k.startswith("rlx_"))
    section["rbp_conv_frac"] = rbp["conv_frac"]
    section["best_rlx_conv_frac"] = best_rlx
    section["rlx_within_10pct_of_rbp"] = bool(
        best_rlx >= rbp["conv_frac"] - 0.10)
    return section


# ------------------------------------------------------- collective audit --

def _audit_child() -> None:
    """Runs under 8 forced host devices: compile one sharded BP round per
    scheduler and scan the optimized HLO for cross-shard data movement.

    The discriminating metric is **edge-sized gathers**: all-gather /
    all-to-all instructions whose output holds >= one full edge vector
    (RBP's exact top-k forces XLA to gather the whole residual array to
    every device; the relaxed selection must not). O(Q)-scalar collectives
    -- the per-queue argmax, the convergence vote psum -- are the
    architecture's legitimate traffic and are reported separately."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import get_scheduler
    from repro.core import messages as M
    from repro.dist import make_bp_mesh, make_sharded_update, shard_pgm
    from repro.pgm import ising_grid_fast

    mesh = make_bp_mesh()
    update_fn = make_sharded_update(mesh)
    pgm = shard_pgm(ising_grid_fast(16, 2.5, seed=0), mesh)
    n_edges = pgm.n_edges
    report = {"backend": jax.default_backend(),
              "devices": int(mesh.devices.size), "edge_count": n_edges}
    shape_re = re.compile(r"=\s+\w+\[([\d,]*)\]")

    def out_elems(line: str) -> int:
        m = shape_re.search(line)
        if not m:
            return 0
        dims = [int(d) for d in m.group(1).split(",") if d]
        n = 1
        for d in dims:
            n *= d
        return n

    for name in ("rbp", "rlx"):
        sched = get_scheduler(name)
        state = sched.init(pgm)

        def round_fn(logm, rng):
            # One traced BP round, exactly the engine's dataflow: sharded
            # update -> frontier select on the sharded residuals -> commit.
            cand, resid = update_fn(pgm, logm)
            frontier, _ = sched.select(pgm, resid, 1e-3, rng, state,
                                       jnp.int32(1))
            return jnp.where(frontier[:, None], cand, logm)

        logm0 = M.init_messages(pgm)
        txt = (jax.jit(round_fn)
               .lower(logm0, jax.random.key(0)).compile().as_text())
        edge_gathers = small_gathers = 0
        for line in txt.splitlines():
            if " all-gather(" in line or " all-to-all(" in line:
                if out_elems(line) >= n_edges:
                    edge_gathers += 1
                else:
                    small_gathers += 1
        report[name] = {
            "edge_sized_gathers": edge_gathers,
            "small_gathers": small_gathers,
            "sorts": txt.count(" sort("),
            "all-reduce": txt.count("all-reduce"),
        }

    report["eliminates_global_topk"] = bool(
        report["rlx"]["edge_sized_gathers"] == 0
        and report["rlx"]["sorts"] == 0
        and report["rbp"]["edge_sized_gathers"] > 0)
    print("AUDIT_JSON=" + json.dumps(report))


def _run_audit() -> dict:
    from repro.dist import cpu_child_env
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_tradeoff", "--child-audit"],
        env=cpu_child_env(8), capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-4000:])
        raise RuntimeError("bench_tradeoff audit child failed")
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("AUDIT_JSON=")][-1]
    return json.loads(line[len("AUDIT_JSON="):])


# ------------------------------------------------------------------ entry --

def _write_record(relax: dict, audit: dict, mode: str) -> None:
    import jax

    from benchmarks.common import emit, out_path

    record = {
        "suite": "tradeoff", "mode": mode,
        "backend": jax.default_backend(), "platform": platform.machine(),
        "unix_time": time.time(),
        "relaxation_sweep": relax,
        "collective_audit": audit,
    }
    with open(out_path("BENCH_tradeoff.json"), "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    emit("relax/audit/eliminates_global_topk", 0.0,
         f"match={audit['eliminates_global_topk']};"
         f"rbp_edge_gathers={audit['rbp']['edge_sized_gathers']};"
         f"rlx_edge_gathers={audit['rlx']['edge_sized_gathers']}")
    emit("relax/acceptance/rlx_within_10pct_of_rbp", 0.0,
         f"match={relax['rlx_within_10pct_of_rbp']};"
         f"rbp={relax['rbp_conv_frac']:.2f};"
         f"rlx={relax['best_rlx_conv_frac']:.2f}")


def run(full: bool = False, n_graphs: int = 5) -> None:
    """benchmarks.run entry: fig2 sweep + relaxation sweep + audit."""
    _fig2(full, n_graphs)
    relax = _relaxation_sweep(tiny=False, full=full)
    _write_record(relax, _run_audit(), "full" if full else "default")


def run_tiny() -> None:
    """CI smoke: minutes-scale relaxation sweep (incl. rlx) + audit; skips
    the fig2 sweep. Same BENCH_tradeoff.json artifact shape."""
    relax = _relaxation_sweep(tiny=True, full=False)
    _write_record(relax, _run_audit(), "tiny")


if __name__ == "__main__":
    if "--child-audit" in sys.argv:
        _audit_child()
    elif "--tiny" in sys.argv:
        run_tiny()
    else:
        run("--full" in sys.argv)
