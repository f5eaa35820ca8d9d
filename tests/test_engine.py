"""Unified BPEngine API: config/registry round-trips, exact wrapper parity,
bit-identical chunked resume, and converged-graph evacuation.

The contracts under test:
  * ``BPEngine.run`` reproduces ``run_bp``/``run_bp_batch`` trajectories
    exactly (same ``logm``, ``rounds``, ``updates``) for all 4 schedulers;
  * N rounds via repeated ``step`` == N rounds in one ``run``, bitwise
    (the chunked-resume path the old ``_init_logm`` backdoor never tested);
  * ``serve`` with evacuation matches ``run_many`` per-graph results while
    releasing fast graphs early and cutting wasted sweeps vs. the
    run-to-completion baseline.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BPConfig, BPEngine, BatchedPGM, LBP, RBP, RS, RnBP,
                        batch_keys, get_scheduler, list_schedulers, run_bp,
                        run_bp_batch, run_bp_many, run_srbp, scheduler_spec)
from repro.pgm import chain_graph, ising_grid

SCHEDULER_SPECS = [
    ("lbp", {}),
    ("rbp", {"p": 1.0 / 16}),
    ("rs", {"p": 0.05}),
    ("rnbp", {"low_p": 0.4, "high_p": 0.9}),
    ("rlx", {"queues": 8, "sample": 0.5, "p": 1.0 / 32}),
    ("rlxtree", {"queues": 8, "sample": 0.5, "p": 1.0 / 32}),
]
IDS = [s for s, _ in SCHEDULER_SPECS]


def small_batch():
    return BatchedPGM.from_pgms([ising_grid(5, 2.0, seed=3),
                                 chain_graph(30, seed=4),
                                 chain_graph(60, seed=5)])


class TestConfigAndRegistry:
    def test_registry_resolves_specs(self):
        assert isinstance(get_scheduler("lbp"), LBP)
        assert get_scheduler("rnbp", low_p=0.2).low_p == 0.2
        rbp = RBP(p=0.5)
        assert get_scheduler(rbp) is rbp
        with pytest.raises(KeyError):
            get_scheduler("nope")
        with pytest.raises(ValueError):
            get_scheduler(rbp, p=0.1)  # kwargs need a string spec
        with pytest.raises(ValueError):
            get_scheduler("srbp")      # serial baseline, not a scheduler

    def test_scheduler_spec_roundtrip(self):
        name, kw = scheduler_spec(RnBP(low_p=0.3))
        assert name == "rnbp" and kw["low_p"] == 0.3
        assert get_scheduler(name, **kw) == RnBP(low_p=0.3)

    def test_config_serializable_end_to_end(self):
        cfg = BPConfig(scheduler="rnbp", scheduler_kwargs={"low_p": 0.4},
                       eps=1e-4, max_rounds=100, chunk_rounds=10)
        d = cfg.to_dict()
        import json
        assert BPConfig.from_dict(json.loads(json.dumps(d))) == cfg
        # instance schedulers serialize through the reverse registry
        d2 = BPConfig(scheduler=RBP(p=0.25)).to_dict()
        assert d2["scheduler"] == "rbp"
        assert d2["scheduler_kwargs"]["p"] == 0.25

    def test_config_validates(self):
        with pytest.raises(ValueError):
            BPConfig(eps=0.0)
        with pytest.raises(ValueError):
            BPConfig(damping=1.0)
        with pytest.raises(ValueError):
            BPConfig(chunk_rounds=0)

    def test_spec_roundtrip_every_registered_scheduler(self):
        # scheduler_spec(get_scheduler(name, **kw)) is the identity for
        # every registered name, including the relaxed family.
        kw_by_name = dict(SCHEDULER_SPECS)
        for name in list_schedulers():
            kw = kw_by_name.get(name, {})
            sched = get_scheduler(name, **kw)
            got_name, got_kw = scheduler_spec(sched)
            assert got_name == name
            assert get_scheduler(got_name, **got_kw) == sched
            for k, v in kw.items():
                assert got_kw[k] == v

    def test_duplicate_registration_raises(self):
        from repro.core import SCHEDULERS, register_scheduler
        with pytest.raises(ValueError, match="duplicate scheduler"):
            register_scheduler("rlx")(type(get_scheduler("rlx")))
        # deliberate replacement works and restores cleanly
        cls = SCHEDULERS["rlx"]
        assert register_scheduler("rlx", overwrite=True)(cls) is cls

    def test_registries_share_list_and_error_format(self):
        import re
        from repro.core import (get_admission_policy, list_admission_policies,
                                list_backends)
        from repro.kernels.ops import get_update_fn
        from repro.serve import get_routing_policy, list_routing_policies
        assert "rlx" in list_schedulers() and "rlxtree" in list_schedulers()
        assert "sharded" in list_backends()
        assert "pallas" in list_backends()
        assert "fifo" in list_admission_policies()
        assert list_routing_policies() == ["deadline", "kind_affinity",
                                          "least_loaded", "round_robin"]
        fmt = r"unknown [\w ]+ 'nope'; registered: \["
        for fn in (lambda: get_scheduler("nope"),
                   lambda: get_update_fn("nope"),
                   lambda: get_admission_policy("nope"),
                   lambda: get_routing_policy("nope")):
            with pytest.raises(KeyError) as ei:
                fn()
            assert re.search(fmt, str(ei.value)), str(ei.value)

    def test_config_carries_relaxed_kwargs_bitwise(self):
        import json
        kw = {"queues": 16, "sample": 0.3, "p": 1.0 / 3.0}
        for name in ("rlx", "rlxtree"):
            cfg = BPConfig(scheduler=name, scheduler_kwargs=kw)
            rt = BPConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
            assert rt == cfg
            sched = rt.make_scheduler()
            assert sched.queues == 16
            assert sched.sample == 0.3
            assert sched.p == 1.0 / 3.0  # exact float, not approx


class TestWrapperParity:
    """Acceptance: BPEngine.run == run_bp / run_bp_batch exactly."""

    @pytest.mark.parametrize("spec,kw", SCHEDULER_SPECS, ids=IDS)
    def test_single_graph(self, spec, kw):
        pgm = ising_grid(6, 2.5, seed=1)
        engine = BPEngine(BPConfig(scheduler=spec, scheduler_kwargs=kw,
                                   eps=1e-4, max_rounds=300))
        res = engine.run(pgm, jax.random.key(0))
        old = run_bp(pgm, get_scheduler(spec, **kw), jax.random.key(0),
                     eps=1e-4, max_rounds=300)
        assert int(res.rounds) == int(old.rounds)
        assert int(res.updates) == int(old.updates)
        np.testing.assert_array_equal(np.asarray(res.logm),
                                      np.asarray(old.logm))

    @pytest.mark.parametrize("spec,kw", SCHEDULER_SPECS, ids=IDS)
    def test_batched(self, spec, kw):
        batch = small_batch()
        keys = batch_keys(jax.random.key(2), batch)
        engine = BPEngine(BPConfig(scheduler=spec, scheduler_kwargs=kw,
                                   eps=1e-4, max_rounds=300, history=False))
        res = engine.run(batch, keys)
        old = run_bp_batch(batch, get_scheduler(spec, **kw), keys,
                           eps=1e-4, max_rounds=300)
        np.testing.assert_array_equal(np.asarray(res.rounds),
                                      np.asarray(old.rounds))
        np.testing.assert_array_equal(np.asarray(res.updates),
                                      np.asarray(old.updates))
        np.testing.assert_array_equal(np.asarray(res.logm),
                                      np.asarray(old.logm))

    def test_updates_counts_in_integers(self):
        """Satellite: committed-message counter is uint32 (exact), not f32
        (which lost precision past ~16M)."""
        res = BPEngine(BPConfig(max_rounds=50)).run(
            ising_grid(6, 2.0, seed=0), jax.random.key(0))
        assert res.updates.dtype == jnp.uint32

    def test_deprecated_wrappers_warn(self):
        pgm = chain_graph(10, seed=0)
        with pytest.warns(DeprecationWarning, match="BPEngine"):
            run_bp(pgm, LBP(), jax.random.key(0), max_rounds=5)
        with pytest.warns(DeprecationWarning, match="BPEngine"):
            run_bp_batch(BatchedPGM.from_pgms([pgm]), LBP(),
                         jax.random.key(0), max_rounds=5)
        with pytest.warns(DeprecationWarning, match="BPEngine"):
            run_bp_many([pgm], LBP(), jax.random.key(0), max_rounds=5)
        with pytest.warns(DeprecationWarning, match="BPEngine"):
            run_srbp(pgm, eps=1e-2)

    def test_srbp_through_engine(self):
        pgm = ising_grid(5, 2.0, seed=7)
        engine = BPEngine(BPConfig(scheduler="srbp", eps=1e-4,
                                   scheduler_kwargs={"time_limit_s": 30.0}))
        res = engine.run(pgm)
        assert res.converged
        with pytest.raises(NotImplementedError):
            engine.init(pgm, jax.random.key(0))


class TestChunkedResume:
    """Satellite: N rounds in one ``run`` vs the same N via repeated
    ``step`` must be bit-identical (logm, rounds, updates) -- the chunk
    boundary must carry the full trajectory, RNG stream included."""

    @pytest.mark.parametrize("spec,kw", SCHEDULER_SPECS, ids=IDS)
    def test_single_graph_bitwise(self, spec, kw):
        pgm = ising_grid(6, 2.5, seed=1)
        engine = BPEngine(BPConfig(scheduler=spec, scheduler_kwargs=kw,
                                   eps=1e-4, max_rounds=300))
        mono = engine.run(pgm, jax.random.key(0))
        state = engine.init(pgm, jax.random.key(0))
        steps = 0
        while not engine.finished(state):
            state = engine.step(state, chunk_rounds=17)  # odd: RS overshoots
            steps += 1
        assert steps > 1, "graph converged within one chunk; weak test"
        chunked = engine.result(state)
        assert int(chunked.rounds) == int(mono.rounds)
        assert int(chunked.updates) == int(mono.updates)
        np.testing.assert_array_equal(np.asarray(chunked.logm),
                                      np.asarray(mono.logm))
        np.testing.assert_array_equal(
            np.asarray(chunked.unconverged_history),
            np.asarray(mono.unconverged_history))

    @pytest.mark.parametrize("spec,kw", SCHEDULER_SPECS, ids=IDS)
    def test_batched_bitwise(self, spec, kw):
        batch = small_batch()
        keys = batch_keys(jax.random.key(2), batch)
        engine = BPEngine(BPConfig(scheduler=spec, scheduler_kwargs=kw,
                                   eps=1e-4, max_rounds=300, history=False))
        mono = engine.run(batch, keys)
        state = engine.init(batch, keys)
        while not engine.finished(state):
            state = engine.step(state, chunk_rounds=13)
        chunked = engine.result(state)
        np.testing.assert_array_equal(np.asarray(chunked.rounds),
                                      np.asarray(mono.rounds))
        np.testing.assert_array_equal(np.asarray(chunked.updates),
                                      np.asarray(mono.updates))
        np.testing.assert_array_equal(np.asarray(chunked.logm),
                                      np.asarray(mono.logm))

    def test_step_noop_after_convergence(self):
        engine = BPEngine(BPConfig(scheduler="lbp", eps=1e-4,
                                   max_rounds=500))
        state = engine.init(chain_graph(20, seed=1), jax.random.key(0))
        while not engine.finished(state):
            state = engine.step(state)
        again = engine.step(state)
        assert int(again.rounds) == int(state.rounds)
        assert int(again.chunk_iters) == 0
        np.testing.assert_array_equal(np.asarray(again.logm),
                                      np.asarray(state.logm))


class TestServeEvacuation:
    """Satellite: a bucket with one deliberately slow graph must release its
    fast graphs after the first chunk, and wasted sweeps must drop vs. the
    no-evacuation baseline."""

    def _stream(self):
        # LBP deterministic: C=1.5 converges in tens of rounds,
        # ising(8, 3.5, seed=0) stalls to max_rounds. Same shape -> same
        # bucket key -> one backfill pool.
        fast = [ising_grid(8, 1.5, seed=s) for s in range(8)]
        return fast[:4] + [ising_grid(8, 3.5, seed=0)] + fast[4:], 4

    def test_fast_graphs_released_early_and_waste_drops(self):
        stream, slow_i = self._stream()
        engine = BPEngine(BPConfig(scheduler="lbp", eps=1e-5,
                                   max_rounds=320, history=False))
        kw = dict(max_batch=3, chunk_rounds=64)
        evac = engine.serve(stream, jax.random.key(0), evacuate=True, **kw)
        base = engine.serve(stream, jax.random.key(0), evacuate=False, **kw)
        # the slow graph stalls; every fast graph converges
        assert not bool(evac.results[slow_i].converged)
        assert all(bool(r.converged)
                   for i, r in enumerate(evac.results) if i != slow_i)
        # fast graphs sharing the straggler's initial bucket leave at the
        # first chunk boundary instead of waiting for the straggler
        first_chunk = [g for c, g in evac.stats.evacuation_log if c == 1]
        assert len(first_chunk) >= 2
        last_evac = {g: c for c, g in evac.stats.evacuation_log}
        assert last_evac[slow_i] == max(last_evac.values())
        # evacuation + backfill strictly reduce wasted and total sweeps
        assert evac.stats.backfilled > 0
        assert evac.stats.wasted_sweeps < base.stats.wasted_sweeps
        assert evac.stats.device_sweeps < base.stats.device_sweeps
        assert evac.stats.useful_sweeps == base.stats.useful_sweeps

    def test_serve_matches_run_many_exactly(self):
        """Backfilled slots must reproduce solo trajectories: serve() and
        run_many() (same fold_in keys) agree bitwise per graph."""
        stream, _ = self._stream()
        engine = BPEngine(BPConfig(scheduler="rnbp",
                                   scheduler_kwargs={"low_p": 0.4},
                                   eps=1e-4, max_rounds=320, history=False))
        rep = engine.serve(stream, jax.random.key(3), max_batch=3,
                           chunk_rounds=48)
        ref = engine.run_many(stream, jax.random.key(3), max_batch=3)
        assert len(rep.results) == len(stream)
        for got, want in zip(rep.results, ref):
            assert int(got.rounds) == int(want.rounds)
            assert int(got.updates) == int(want.updates)
            np.testing.assert_array_equal(np.asarray(got.logm),
                                          np.asarray(want.logm))

    def test_serve_heterogeneous_stream(self):
        """Mixed shapes split into independent backfill pools; results come
        back in input order, and the evacuating path matches the
        run-to-completion baseline bitwise (both pad to group ceilings, so
        stochastic schedulers see identical padded shapes)."""
        stream = [ising_grid(6, 2.0, seed=1), chain_graph(40, seed=2),
                  ising_grid(7, 2.0, seed=3), chain_graph(50, seed=4),
                  chain_graph(45, seed=5), chain_graph(60, seed=6)]
        engine = BPEngine(BPConfig(scheduler="rnbp",
                                   scheduler_kwargs={"low_p": 0.4},
                                   eps=1e-4, max_rounds=400, history=False))
        kw = dict(max_batch=2, chunk_rounds=32)
        rep = engine.serve(stream, jax.random.key(0), evacuate=True, **kw)
        base = engine.serve(stream, jax.random.key(0), evacuate=False, **kw)
        assert all(r is not None for r in rep.results)
        assert all(bool(r.converged) for r in rep.results)
        for got, want in zip(rep.results, base.results):
            assert int(got.rounds) == int(want.rounds)
            np.testing.assert_array_equal(np.asarray(got.logm),
                                          np.asarray(want.logm))
        assert rep.stats.useful_sweeps == base.stats.useful_sweeps

    def test_resume_via_state_replace(self):
        """BPState is a plain pytree: swapping fields (the checkpoint path)
        resumes exactly."""
        engine = BPEngine(BPConfig(scheduler="rnbp",
                                   scheduler_kwargs={"low_p": 0.7},
                                   eps=1e-4, max_rounds=300))
        pgm = ising_grid(6, 2.5, seed=2)
        state = engine.init(pgm, jax.random.key(1))
        state = engine.step(state, chunk_rounds=20)
        # round-trip through raw host arrays (what a checkpoint does)
        raw = jax.tree.map(np.asarray, dataclasses.replace(
            state, rng=jax.random.key_data(state.rng)))
        revived = dataclasses.replace(
            jax.tree.map(jnp.asarray, raw),
            rng=jax.random.wrap_key_data(jnp.asarray(raw.rng)))
        a = engine.run(pgm, state=state)
        b = engine.run(pgm, state=revived)
        assert int(a.rounds) == int(b.rounds)
        np.testing.assert_array_equal(np.asarray(a.logm), np.asarray(b.logm))
