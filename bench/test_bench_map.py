"""Self-test of the MAP stereo cell, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench

It checks the family against the program's generator (it is a copy), the
max-product reference against brute force, the work count and the
``kernel_ms`` reader, and that a run of the cell drives the loop to
``correct`` true, and to false with the control (the program's bfloat16
tables) or a fault planted under the timed path. No number here is a
device metric: nothing runs on a chip.
"""

from __future__ import annotations

import itertools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import reference, reference_map, run, work, work_map  # noqa: E402
from bench.drivers import common  # noqa: E402
from bench.metrics import kernel_ms  # noqa: E402
from bench.test_bench import (_fault_altered_answer,  # noqa: E402
                              _fault_loosened_eps, _fault_unchanged_state)
from bench.traffic.families import stereo_pair  # noqa: E402

MAP = "stereo_tsukuba_rnbp.map"


def _manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def _tiny():
    """The cell's own files, cut to a size the CPU runs in seconds."""
    cell = {w["name"]: w for w in _manifest()["workloads"]}[MAP]
    cfg = run.load_json(ROOT, "bench", "configs", f"{cell['config']}.json")
    mix = run.load_json(ROOT, "bench", "traffic", f"{cell['traffic']}.json")
    cfg["graph"].update(height=10, width=24)
    mix.update(instances=[0, 1, 2], trace_s=0.5)
    return cell, cfg, mix


def _execute(trace_on=False, control=False, seconds=1.5):
    cell, cfg, mix = _tiny()
    return run.execute(cell, cfg, mix,
                       run.cell_metrics(_manifest(), MAP, trace_on),
                       seed=2 ** 33 + 11, seconds=seconds, trace=trace_on,
                       control=control)


# ------------------------------------------------------------ generator --

def test_family_is_the_programs_generator():
    from repro.pgm import stereo_pair_mrf

    cfg = dict(_tiny()[1]["graph"], height=7, width=11, n_disp=6)
    g = stereo_pair.make(cfg, np.random.default_rng(3))
    inst = stereo_pair_mrf(7, 11, 6, seed=3, lam_data=cfg["lam_data"],
                           trunc_data=cfg["trunc_data"],
                           trunc_smooth=cfg["trunc_smooth"],
                           noise=cfg["noise"])
    p = inst.pgm
    np.testing.assert_array_equal(g.truth, inst.truth.reshape(-1))
    np.testing.assert_array_equal(g.edges, inst.edges)
    np.testing.assert_allclose(np.asarray(p.log_psi_v)[:77], g.log_unary,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(p.log_psi_e)[:g.n_directed:2],
                               g.log_pair, rtol=1e-6, atol=1e-6)


# -------------------------------------------------------------- reference --

def test_reference_map_is_exact_on_a_tree():
    rng = np.random.default_rng(0)
    n, s = 6, 3
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    g = reference.Graph(n_states=np.full(n, s), edges=edges,
                        log_unary=rng.normal(size=(n, s)),
                        log_pair=rng.normal(size=(n - 1, s, s)))
    logm = reference_map.map_solve(g)
    assert reference_map.residual(g, logm) < 1e-12
    best = max(itertools.product(range(s), repeat=n),
               key=lambda x: -reference_map.energy(g, np.array(x)))
    got = reference_map.decode(g, logm)
    np.testing.assert_array_equal(got, best)


def test_reference_map_sees_a_sum_product_fixed_point():
    """Sum-product messages are no max-product fixed point: the check
    tells the two semirings apart."""
    g = stereo_pair.make(dict(_tiny()[1]["graph"], height=5, width=6,
                              n_disp=4), np.random.default_rng(1))
    exact = reference_map.map_solve(g)
    assert reference_map.residual(g, exact) < 1e-8
    assert reference_map.residual(g, reference.solve(g, eps=1e-10)) > 1e-2


# ---------------------------------------------------- metrics and work --

def test_work_map_counts_real_states_only():
    g = reference.Graph(n_states=np.array([2, 32]), edges=np.array([[0, 1]]),
                        log_unary=np.zeros((2, 32)),
                        log_pair=np.zeros((1, 32, 32)))
    ops, nbytes = work_map.per_round(g)
    assert ops == (2 * 64 + 5 * 32) + (2 * 64 + 5 * 2)
    assert nbytes == work.per_round(g)[1]


def test_kernel_ms_reads_nothing_without_a_trace():
    assert kernel_ms.read(common.Outcome(setup_s=1.0)) is None


# ------------------------------------------------------ runs on the CPU --

def test_run_is_correct():
    res = _execute()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in run.cell_metrics(_manifest(), MAP, False)}
    assert names == {"setup_s", "solve_s"}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_traced_run_reads_program_counters():
    res = _execute(trace_on=True)
    assert res["correct"], res["checks"]
    # The CPU trace has no device plane: only counters and spans are read.
    assert res["device"]["busy_s"] is None
    assert set(res["metrics"]) >= {"rounds_per_solve", "commit_pct"}
    assert not any("roofline" in k or "idle" in k or k == "kernel_ms"
                   for k in res["metrics"])


def test_driver_refuses_a_solve_loop_it_cannot_rebind(monkeypatch):
    from bench.drivers import solve, solve_map

    def loop(ctx):
        return ctx
    monkeypatch.setattr(solve, "run", loop)
    with pytest.raises(RuntimeError, match="reference"):
        solve_map.run(None)


def test_control_is_not_correct():
    res = _execute(control=True)
    assert not res["correct"]
    c = res["checks"]
    assert c["belief_gap_max"]["value"] > c["belief_gap_max"]["limit"]


@pytest.mark.parametrize("fault", [_fault_unchanged_state,
                                   _fault_altered_answer,
                                   _fault_loosened_eps])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _execute(seconds=1.0)
    assert not res["correct"], res["checks"]
