"""System-level behaviour: distributed BP parity (multi-device subprocess),
checkpoint/restore, data-pipeline determinism, fault-tolerance paths."""

import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_pytree, save_pytree
from repro.configs import get
from repro.configs.base import TRAIN_4K
from repro.core import RnBP, run_bp
from repro.data import SyntheticLM
from repro.ft import ElasticMesh, StragglerMonitor, run_bp_resilient
from repro.pgm import ising_grid


class TestDistributedBP:
    def test_sharded_bp_matches_single_device(self, run_on_cpu_devices):
        """Runs in a subprocess with 8 forced host devices (device count is
        locked at first jax use, so it cannot be set in-process)."""
        code = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import RnBP, LBP, run_bp
from repro.pgm import ising_grid
from repro.dist import make_bp_mesh, run_bp_sharded

pgm = ising_grid(16, 2.5, seed=0)
mesh = make_bp_mesh()
ref = run_bp(pgm, LBP(), jax.random.key(0), eps=1e-6, max_rounds=4000)
assert bool(ref.converged)
for sched in [LBP(), RnBP(low_p=0.7)]:
    res = run_bp_sharded(pgm, sched, mesh, jax.random.key(0), eps=1e-6,
                         max_rounds=4000)
    assert bool(res.converged), type(sched).__name__
    d = float(jnp.max(jnp.abs(jnp.where(pgm.state_mask,
                                        res.beliefs - ref.beliefs, 0.0))))
    assert d < 5e-3, (type(sched).__name__, d)
print("OK")
"""
        run_on_cpu_devices(code)

    def test_sharded_relaxed_scheduler_converges(self, run_on_cpu_devices):
        """rlx/rlxtree under the 8-device sharded backend: converge to the
        single-device beliefs, and chunked resume stays bitwise (the relaxed
        per-queue selection must survive the shard_map backend)."""
        code = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import LBP, run_bp
from repro.pgm import ising_grid
from repro.dist import make_bp_mesh, make_sharded_engine, shard_pgm

pgm = ising_grid(16, 2.5, seed=0)
mesh = make_bp_mesh()
ref = run_bp(pgm, LBP(), jax.random.key(0), eps=1e-6, max_rounds=4000)
assert bool(ref.converged)
spgm = shard_pgm(pgm, mesh)
for name in ["rlx", "rlxtree"]:
    engine = make_sharded_engine(name, mesh, eps=1e-6, max_rounds=20000)
    mono = engine.run(spgm, jax.random.key(7))
    assert bool(mono.converged), name
    d = float(jnp.max(jnp.abs(jnp.where(pgm.state_mask,
                                        mono.beliefs - ref.beliefs, 0.0))))
    assert d < 5e-3, (name, d)
    state = engine.init(spgm, jax.random.key(7))
    while not engine.finished(state):
        state = engine.step(state, chunk_rounds=37)
    chunked = engine.result(state)
    assert int(mono.rounds) == int(chunked.rounds), name
    np.testing.assert_array_equal(np.asarray(mono.logm),
                                  np.asarray(chunked.logm))
print("OK")
"""
        run_on_cpu_devices(code)

    def test_sharded_chunked_resume_bitwise(self, run_on_cpu_devices):
        """Chunked BPEngine.step under the 8-device mesh must match a
        monolithic sharded run bit-for-bit -- the engine's resume guarantee
        has to survive the shard_map backend."""
        code = r"""
import jax, numpy as np
from repro.pgm import ising_grid
from repro.dist import make_bp_mesh, make_sharded_engine, shard_pgm

pgm = ising_grid(12, 2.5, seed=0)
mesh = make_bp_mesh()
assert mesh.devices.size == 8
engine = make_sharded_engine("rnbp", mesh, eps=1e-4, max_rounds=1200)
spgm = shard_pgm(pgm, mesh)
mono = engine.run(spgm, jax.random.key(7))

state = engine.init(spgm, jax.random.key(7))
while not engine.finished(state):
    state = engine.step(state, chunk_rounds=23)
chunked = engine.result(state)

assert bool(mono.converged) and bool(chunked.converged)
assert int(mono.rounds) == int(chunked.rounds)
np.testing.assert_array_equal(np.asarray(mono.logm),
                              np.asarray(chunked.logm))
np.testing.assert_array_equal(np.asarray(mono.beliefs),
                              np.asarray(chunked.beliefs))
print("OK")
"""
        run_on_cpu_devices(code)

    def test_batched_bucket_through_sharded_fold(self, run_on_cpu_devices):
        """run_many with backend='sharded' routes whole buckets through the
        mesh-aware disjoint-union fold; per-graph beliefs must match the
        single-device engine within the sharded tolerance."""
        code = r"""
import jax, jax.numpy as jnp
from repro.core import BPConfig, BPEngine
from repro.pgm import ising_grid
from repro.dist import make_bp_mesh, make_sharded_engine

mesh = make_bp_mesh()
assert mesh.devices.size == 8
pgms = [ising_grid(10 + (i % 3), 2.0, seed=i) for i in range(6)]
sharded = make_sharded_engine("rnbp", mesh, eps=1e-4, max_rounds=1500)
ref = BPEngine(BPConfig(scheduler="rnbp", eps=1e-4, max_rounds=1500))
res_s = sharded.run_many(pgms, jax.random.key(3))
res_r = ref.run_many(pgms, jax.random.key(3))
for s, r in zip(res_s, res_r):
    assert bool(s.converged) and bool(r.converged)
    d = float(jnp.max(jnp.abs(s.beliefs - r.beliefs)))
    assert d < 5e-3, d
print("OK")
"""
        run_on_cpu_devices(code)

    def test_async_serving_through_sharded_backend(self, run_on_cpu_devices):
        """The async pipeline composes with backend='sharded': every
        resident bucket's union grid is laid out over the mesh, and
        evacuation + compaction work unchanged (per-graph beliefs match the
        single-device pipeline within the sharded tolerance)."""
        code = r"""
import jax, jax.numpy as jnp
from repro.core import BPConfig, BPEngine, serve_async
from repro.pgm import ising_grid
from repro.dist import make_bp_mesh, make_sharded_engine

mesh = make_bp_mesh()
assert mesh.devices.size == 8
fast = [ising_grid(8, 1.5, seed=s) for s in range(5)]
stream = fast[:2] + [ising_grid(8, 3.5, seed=0)] + fast[2:]
kw = dict(max_batch=3, chunk_rounds=48, compact=True, slots=2)
sharded = make_sharded_engine("lbp", mesh, eps=1e-5, max_rounds=192)
ref = BPEngine(BPConfig(scheduler="lbp", eps=1e-5, max_rounds=192))
rep_s = serve_async(sharded, stream, jax.random.key(0), **kw)
rep_r = serve_async(ref, stream, jax.random.key(0), **kw)
assert rep_s.stats.compactions >= 1 and rep_s.stats.evacuated == len(stream)
for s, r in zip(rep_s.results, rep_r.results):
    assert int(s.rounds) == int(r.rounds)
    d = float(jnp.max(jnp.abs(s.beliefs - r.beliefs)))
    assert d < 5e-3, d
print("OK")
"""
        run_on_cpu_devices(code)


class TestCheckpoint:
    def test_save_restore_roundtrip(self):
        tree = {"a": jnp.arange(12).reshape(3, 4).astype(jnp.float32),
                "b": {"c": jnp.ones((5,), jnp.bfloat16)}}
        with tempfile.TemporaryDirectory() as d:
            save_pytree(d, 7, tree, extra={"note": "x"})
            assert latest_step(d) == 7
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            got, extra = restore_pytree(d, 7, like)
            assert extra == {"note": "x"}
            np.testing.assert_array_equal(np.asarray(got["a"]),
                                          np.asarray(tree["a"]))

    def test_crash_mid_save_keeps_previous(self):
        tree = {"a": jnp.zeros((2,))}
        with tempfile.TemporaryDirectory() as d:
            save_pytree(d, 1, tree)
            os.makedirs(os.path.join(d, "step_000000002.tmp"))
            assert latest_step(d) == 1    # stale .tmp ignored

    def test_train_state_resume_exact(self):
        cfg = get("starcoder2_3b").reduced()
        from repro.models import build_model
        from repro.train.step import init_train_state, make_train_step
        model = build_model(cfg)
        state = init_train_state(model, jax.random.key(0))
        shape = dataclasses.replace(TRAIN_4K, seq_len=32, global_batch=4)
        pipe = SyntheticLM(cfg, shape)
        step = jax.jit(make_train_step(model))
        for i in range(3):
            state, _ = step(state, pipe.batch(i))
        with tempfile.TemporaryDirectory() as d:
            save_pytree(d, 3, state, extra={"data_step": 3})
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            restored, extra = restore_pytree(d, 3, like)
            s_a, _ = step(state, pipe.batch(extra["data_step"]))
            s_b, _ = step(restored, pipe.batch(extra["data_step"]))
            diff = jax.tree.map(
                lambda a, b: float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)))),
                s_a.params, s_b.params)
            assert max(jax.tree.leaves(diff)) == 0.0


class TestDataPipeline:
    def test_deterministic_across_restarts(self):
        cfg = get("qwen3_4b").reduced()
        shape = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=4)
        a = SyntheticLM(cfg, shape, seed=3).batch(17)
        b = SyntheticLM(cfg, shape, seed=3).batch(17)
        np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                      np.asarray(b["tokens"]))

    def test_tokens_in_range(self):
        cfg = get("qwen3_4b").reduced()
        shape = dataclasses.replace(TRAIN_4K, seq_len=64, global_batch=2)
        b = SyntheticLM(cfg, shape).batch(0)
        assert int(b["tokens"].max()) < cfg.vocab
        assert int(b["tokens"].min()) >= 0
        assert b["tokens"].shape == b["labels"].shape

    def test_learnable_structure(self):
        cfg = get("qwen3_4b").reduced()
        shape = dataclasses.replace(TRAIN_4K, seq_len=512, global_batch=2)
        b = SyntheticLM(cfg, shape).batch(0)
        t = np.asarray(b["tokens"])
        hits = np.mean(t[:, 1:] == (t[:, :-1] * 7 + 13) % cfg.vocab)
        # coin=0.5, and the source token itself survives its own coin with
        # p=0.5 -> expected bigram hit rate ~0.25 (>> chance 1/vocab)
        assert hits > 0.2


class TestFaultTolerance:
    def test_resilient_bp_chunked_converges_and_resumes(self):
        pgm = ising_grid(12, 2.5, seed=1)
        mono = run_bp(pgm, RnBP(low_p=0.7), jax.random.key(0), eps=1e-4,
                      max_rounds=2000)
        with tempfile.TemporaryDirectory() as d:
            chunked = run_bp_resilient(pgm, RnBP(low_p=0.7),
                                       jax.random.key(0), eps=1e-4,
                                       max_rounds=2000, rounds_per_chunk=37,
                                       ckpt_dir=d)
            assert bool(chunked.converged) == bool(mono.converged)
            again = run_bp_resilient(pgm, RnBP(low_p=0.7),
                                     jax.random.key(0), eps=1e-4,
                                     max_rounds=2000, rounds_per_chunk=37,
                                     ckpt_dir=d)
            assert int(again.rounds) == 0   # crash-resume: nothing to redo

    def test_resilient_bp_restores_legacy_checkpoint(self):
        """A pre-engine checkpoint ({logm, sstate} only) must resume, not
        crash the crash-recovery path: messages carry over, the chunked run
        finishes from there."""
        from repro.core import BPConfig, BPEngine
        pgm = ising_grid(10, 2.0, seed=3)
        sched = RnBP(low_p=0.7)
        engine = BPEngine(BPConfig(scheduler=sched, eps=1e-4,
                                   max_rounds=2000, chunk_rounds=10))
        state = engine.step(engine.init(pgm, jax.random.key(0)))
        with tempfile.TemporaryDirectory() as d:
            save_pytree(d, int(state.rounds),
                        {"logm": state.logm, "sstate": state.sched_state},
                        extra={"rounds": int(state.rounds)})
            res = run_bp_resilient(pgm, sched, jax.random.key(0), eps=1e-4,
                                   max_rounds=2000, rounds_per_chunk=40,
                                   ckpt_dir=d)
            assert bool(res.converged)
            assert int(res.rounds) > 0      # resumed and did new work

    def test_straggler_monitor(self):
        mon = StragglerMonitor(budget_factor=2.0)
        assert not mon.record(1.0)
        assert not mon.record(1.1)
        assert mon.record(5.0)
        assert mon.events == 1
        assert 0.9 < mon.ewma < 1.2     # EWMA not poisoned by the outlier

    def test_elastic_mesh_single_device(self):
        em = ElasticMesh(model_parallel=4)
        mesh = em.current()             # 1 device -> degrades gracefully
        assert mesh.devices.size == len(jax.devices())
        assert not em.changed()
