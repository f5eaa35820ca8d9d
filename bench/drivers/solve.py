"""Closed loop: one client solves graphs back to back through
``BPEngine.run``.

Traffic keys: ``instances`` (the seeds of the pool's graphs, drawn by the
configuration's family), ``trace_s`` (how long the traced window of a
``--trace 1`` run lasts).

Every seed offers the same work. The window runs whole cycles through
the pool; in cycle ``c`` graph ``i`` is solved with the key
``fold_in(key(instances[i]), c)`` (the schedule's randomness), and the
run's seed only orders the graphs within a cycle. Keys drawn from the
run's seed made the mean round count, and with it ``solve_s``, differ by
2.8% between seeds.

A solve runs from ``BPEngine.init`` to beliefs that are ready. The window
starts cycles until ``--seconds`` have passed and ends when the last solve
is ready; ``solve_s`` is its length over the solves in it. Each answer is
copied to the host while the next solve runs, and its device arrays are
dropped, as a client that takes its answers would: the device holds the
pool and the solves in flight, not every answer of the window. Set-up
draws the pool, builds the program's graphs and runs one solve, which
compiles (or loads from the cache) every program the window runs.
"""

from __future__ import annotations

import time

import numpy as np

from bench import program, reference, work
from bench.drivers import common


def run(ctx) -> common.Outcome:
    import jax

    cfg, mix = ctx.cfg, ctx.mix
    family = common.family(cfg)
    graphs = [family.make(cfg["graph"], np.random.default_rng(s))
              for s in mix["instances"]]
    order = np.random.default_rng(ctx.seed).permutation(len(graphs))
    keys = [jax.random.key(s) for s in mix["instances"]]

    def graph_of(k):
        return int(order[k % len(order)])

    def solve(k, cycle=None):
        i = graph_of(k)
        key = jax.random.fold_in(keys[i], k // len(order)
                                 if cycle is None else cycle)
        res = eng.run(pgms[i], key)
        jax.block_until_ready(res.beliefs)
        return res

    def fetch(res):
        """The answer on the host: (logm, beliefs, rounds, updates,
        converged)."""
        return tuple(np.asarray(a) for a in (
            res.logm, res.beliefs, res.rounds, res.updates, res.converged))

    with common.CompileCounter() as setup:
        dtype = program.table_dtype(cfg, ctx.control)
        pgms = [program.pgm(g, dtype) for g in graphs]
        for p, g in zip(pgms, graphs):
            program.check_layout(p, g)
        eng = program.engine(cfg)
        fetch(solve(0, cycle=2 ** 31 - 1))  # a key the window never uses
    out = common.Outcome(setup_s=time.perf_counter() - ctx.t_start)
    out.setup_programs = (setup.count, setup.cache_hits, setup.cache_misses)

    answers, walls = [], []
    pending = None
    tracer = common.Tracer(ctx.trace, float(mix["trace_s"]))
    with common.CompileCounter() as compiles:
        t0 = now = time.perf_counter()
        tracer.start()
        k = 0
        while True:
            res = solve(k)
            for a in (res.logm, res.beliefs, res.rounds, res.updates,
                      res.converged):
                a.copy_to_host_async()
            if pending is not None:
                answers.append(fetch(pending))
            pending = res
            k += 1
            walls.append(time.perf_counter() - now)
            now = time.perf_counter()
            if tracer.active and now - t0 >= tracer.seconds:
                tracer.stop()
                out.traced_solves = k
            if now - t0 >= ctx.seconds and k % len(order) == 0:
                break
        answers.append(fetch(pending))
        del pending, res
        out.window_s = time.perf_counter() - t0
    if tracer.active:
        tracer.stop()
        out.traced_solves = k
    out.compiles_in_window = compiles.count
    out.memory_peak_bytes = common.memory_peak()

    out.solves = [dict(rounds=int(r), updates=int(u), converged=bool(c),
                       wall_s=w)
                  for (_, _, r, u, c), w in zip(answers, walls)]
    out.attempted = len(answers)
    out.failed = sum(not s["converged"] for s in out.solves)
    out.n_real_edges = graphs[0].n_directed
    out.trace = tracer.result()
    if out.trace is not None:
        traced = out.solves[:out.traced_solves]
        out.traced_rounds = sum(s["rounds"] for s in traced)
        per_round = [work.per_round(g) for g in graphs]
        out.traced_work = tuple(
            sum(s["rounds"] * per_round[graph_of(k)][i]
                for k, s in enumerate(traced)) for i in (0, 1))

    # The check, after the window and the memory reading: every solve's
    # messages must be a fixed point of the exact update, and its beliefs
    # those the messages give.
    del pgms
    res_max, gap_max = 0.0, 0.0
    for k, (logm, b, *_) in enumerate(answers):
        g = graphs[graph_of(k)]
        logm = program.messages(logm, g)
        res_max = max(res_max, reference.residual(g, logm))
        gap_max = max(gap_max, reference.belief_gap(
            g, logm, program.beliefs(b, g)))
    limits = cfg["limits"]
    out.checks = [("unconverged", out.failed, "<=", 0),
                  ("residual_max", res_max, "<=", limits["residual_max"]),
                  ("belief_gap_max", gap_max, "<=", limits["belief_gap_max"])]
    return out
