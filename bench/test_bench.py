"""Self-test of the benchmark, on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench

It checks the generator against the program's own (it is a copy), the
reference against brute force, the trace reduction against a recorded chip
trace, the metric and work arithmetic, and that a run drives the loop to
``correct`` true, and to false with the control (the program's bfloat16
tables) or a fault planted under the timed path. No number here is a
device metric: nothing runs on a chip.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import reference, run, trace, work  # noqa: E402
from bench.traffic.families import ising_grid  # noqa: E402

#: A traced window of one Ising 200x200 solve on a TPU v5e (a traced run
#: of the solve cell with ``trace_s`` 0.05, its ``.xplane.pb`` gzipped),
#: and what the reduction read from it when it was recorded.
RECORDED = os.path.join(ROOT, "bench", "testdata", "ising_small.xplane.pb.gz")


def _manifest():
    return run.load_json(ROOT, "BENCHMARK.json")


def _metrics(cell_name: str, trace_on: bool) -> list:
    return run.cell_metrics(_manifest(), cell_name, trace_on)


def _tiny(cell_name: str):
    """The cell's own files, cut to a size the CPU runs in seconds."""
    cell = {w["name"]: w for w in _manifest()["workloads"]}[cell_name]
    cfg = run.load_json(ROOT, "bench", "configs", f"{cell['config']}.json")
    mix = run.load_json(ROOT, "bench", "traffic", f"{cell['traffic']}.json")
    cfg["graph"].update(n=12)
    mix.update(instances=[0, 1, 2], trace_s=0.5)
    return cell, cfg, mix


def _execute(cell_name, trace_on=False, control=False, seconds=1.5):
    cell, cfg, mix = _tiny(cell_name)
    return run.execute(cell, cfg, mix, _metrics(cell_name, trace_on),
                       seed=2 ** 33 + 7, seconds=seconds, trace=trace_on,
                       control=control)


SOLVE = "ising200_c20_rnbp.solve"


# ------------------------------------------------------------ generators --

def test_ising_family_is_the_programs_distribution():
    from repro.pgm import ising_grid_fast

    g = ising_grid.make({"n": 7, "C": 2.5}, np.random.default_rng(3))
    p = ising_grid_fast(7, 2.5, seed=3)
    e = g.n_directed
    np.testing.assert_allclose(np.asarray(p.log_psi_v)[:49], g.log_unary,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p.log_psi_e)[:e:2], g.log_pair,
                               rtol=1e-6, atol=1e-6)


# -------------------------------------------------------------- reference --

def test_reference_is_exact_on_a_tree():
    rng = np.random.default_rng(0)
    n, s = 6, 3
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    g = reference.Graph(n_states=np.full(n, s), edges=edges,
                        log_unary=rng.normal(size=(n, s)),
                        log_pair=rng.normal(size=(n - 1, s, s)))
    logm = reference.solve(g, eps=1e-13)
    assert reference.residual(g, logm) < 1e-12
    got = np.exp(reference.beliefs(g, logm))
    joint = np.zeros((s,) * n)
    for x in itertools.product(range(s), repeat=n):
        joint[x] = (sum(g.log_unary[i, x[i]] for i in range(n))
                    + sum(g.log_pair[k, x[u], x[v]]
                          for k, (u, v) in enumerate(edges)))
    joint = np.exp(joint - joint.max())
    joint /= joint.sum()
    for i in range(n):
        axes = tuple(a for a in range(n) if a != i)
        np.testing.assert_allclose(got[i], joint.sum(axis=axes), atol=1e-10)


def test_reference_reads_lower_precision():
    g = ising_grid.make({"n": 8, "C": 2.0}, np.random.default_rng(1))
    exact = reference.solve(g, eps=1e-12)
    low = reference.solve(g, eps=1e-12, max_rounds=300, dtype=np.float16)
    assert reference.residual(g, exact) < 1e-10
    assert reference.residual(g, low) > 1e-4


# ---------------------------------------------------- metrics and work --

def test_work_counts_real_states_only():
    g = reference.Graph(n_states=np.array([2, 32]), edges=np.array([[0, 1]]),
                        log_unary=np.zeros((2, 32)),
                        log_pair=np.zeros((1, 32, 32)))
    ops, nbytes = work.per_round(g)
    # 0 -> 1: a=2, b=32; 1 -> 0: a=32, b=2
    assert ops == (5 * 64 + 8 * 32) + (5 * 64 + 8 * 2)
    assert nbytes == 4 * (64 + 2 + 64 + 1) + 4 * (64 + 32 + 4 + 1)
    pk = work.peaks("TPU v5 lite")
    share, bound = work.roofline_share(ops, nbytes, nbytes / 819e9, pk)
    assert bound == "bytes" and share == pytest.approx(100.0)
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_manifest_names_a_reader_for_every_metric():
    man = _manifest()
    for m in man["end_to_end"] + man["per_layer"]:
        assert hasattr(run.metric_reader(m["name"]), "read"), m["name"]
    for w in man["workloads"]:
        e2e = run.cell_metrics(man, w["name"], False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert run.cell_metrics(man, w["name"], True)


# ------------------------------------------------------------------ trace --

def test_trace_reduction_on_a_recorded_trace():
    with open(RECORDED.replace(".xplane.pb.gz", ".json")) as f:
        want = json.load(f)
    t = trace.load(RECORDED)
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < t.busy_s() <= t.window_s
    assert t.op_seconds(work.UPDATE_KERNEL) == pytest.approx(
        want["update_kernel_s"], rel=1e-9)
    assert 0 < t.op_seconds(work.UPDATE_KERNEL) <= t.busy_s()
    top = t.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1]
    gaps = t.idle_gaps(10)
    assert sum(s for _, s in gaps) <= t.window_s - t.busy_s() + 1e-9


# ------------------------------------------------------ runs on the CPU --

@pytest.mark.parametrize("cell", [SOLVE])
def test_run_is_correct(cell):
    res = _execute(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {m["name"] for m in _metrics(cell, False)}
    assert set(res["metrics"]) == names
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", [SOLVE])
def test_traced_run_reads_program_counters(cell):
    res = _execute(cell, trace_on=True)
    assert res["correct"], res["checks"]
    # The CPU trace has no device plane: only counters and spans are read.
    assert res["device"]["busy_s"] is None
    assert not any("roofline" in k or "idle" in k for k in res["metrics"])
    assert res["metrics"]


@pytest.mark.parametrize("cell", [SOLVE])
def test_control_is_not_correct(cell):
    res = _execute(cell, control=True)
    assert not res["correct"]
    c = res["checks"]
    assert c["belief_gap_max"]["value"] > c["belief_gap_max"]["limit"]


def _fault_unchanged_state(monkeypatch):
    """A step that returns its state unchanged, counters aside."""
    import jax.numpy as jnp

    from repro.core.engine import BPEngine

    def step(self, state, *, chunk_rounds=None):
        return dataclasses.replace(
            state, rounds=jnp.full_like(state.rounds, 1),
            done=jnp.ones_like(state.done))

    monkeypatch.setattr(BPEngine, "step", step)


def _fault_altered_answer(monkeypatch):
    """Beliefs altered where they are produced."""
    from repro.core.engine import BPEngine

    orig = BPEngine.result

    def result(self, state):
        r = orig(self, state)
        return dataclasses.replace(r, beliefs=r.beliefs.at[0, 0].add(0.1))

    monkeypatch.setattr(BPEngine, "result", result)


def _fault_loosened_eps(monkeypatch):
    """Convergence tested against 2.5 times the configuration's eps."""
    from repro.core import engine

    orig = engine._chunk_single

    def chunk(pgm, carry, limit, eps, **kw):
        return orig(pgm, carry, limit, 2.5 * eps, **kw)

    monkeypatch.setattr(engine, "_chunk_single", chunk)


@pytest.mark.parametrize("cell,fault", [
    (SOLVE, _fault_unchanged_state), (SOLVE, _fault_altered_answer),
    (SOLVE, _fault_loosened_eps)])
def test_fault_is_not_correct(monkeypatch, cell, fault):
    fault(monkeypatch)
    res = _execute(cell, seconds=1.0)
    assert not res["correct"], res["checks"]
