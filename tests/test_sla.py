"""SLA-aware serving conformance suite: deadline admission determinism,
eviction correctness, slot-packing parity, the learned effort predictor,
and no-starvation under sustained overload.

Every timing assertion runs on an injected :class:`SweepClock` (virtual
time = device sweeps), never on wall time -- the whole deadline/eviction
story is a pure function of scheduling decisions, so these pins hold
bit-for-bit on any machine. The companion invariant from the serving
suite carries over: a request's trajectory depends only on its padded
shape and ``fold_in(rng, rid)``, so eviction of *other* requests, slot
packing, and admission order can never change a surviving result bit.
"""

import json
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import (ADMISSION_POLICIES, BPConfig, BPEngine,
                        DeadlineAdmission, RidgeEffort, RoundsHistory,
                        ServingPipeline, SweepClock, serve_async)
from repro.core.serving import (AsyncServeResult, AsyncServeStats,
                                RequestRecord, _Group, _Staged)
from repro.pgm import chain_graph, ising_grid
from repro.serve import serve_routed
from repro.serve.router import RouterResult, RouterStats

CFG = BPConfig(scheduler="lbp", eps=1e-5, max_rounds=160, history=False)
#: one virtual second per device sweep; slots=1 keeps chunk accounting
#: exactly sequential so expected sync times are computable by hand.
KW = dict(slots=1, max_batch=2, chunk_rounds=16, prefetch=None)


@pytest.fixture(scope="module")
def engine():
    return BPEngine(CFG)


def _fast(seed=0):
    # ~15-25 LBP rounds to eps=1e-5 (measured, deterministic).
    return ising_grid(6, 1.5, seed=seed)


def _impossible():
    # Never converges within max_rounds=160 (measured, deterministic).
    return ising_grid(6, 3.5, seed=0)


def _assert_bitwise(got, want):
    assert int(got.rounds) == int(want.rounds)
    assert int(got.updates) == int(want.updates)
    np.testing.assert_array_equal(np.asarray(got.logm), np.asarray(want.logm))


def _timeline(rep):
    return [(r.rid, r.status, r.t_enqueue, r.t_admit, r.t_done,
             int(r.result.rounds)) for r in rep.records]


class TestSweepClock:
    """Deterministic virtual time: the fixed-clock injection every other
    test in this file relies on."""

    def test_virtual_time_arithmetic(self):
        clock = SweepClock()
        assert clock() == 0.0
        clock.on_chunk(64)
        assert clock() == 64.0
        clock.advance(5.5)
        assert clock() == 69.5

    def test_tau_scales_sweeps(self):
        clock = SweepClock(tau=0.25)
        clock.on_chunk(16)
        assert clock() == 4.0

    def test_tau_validation(self):
        with pytest.raises(ValueError):
            SweepClock(tau=0.0)
        with pytest.raises(ValueError):
            SweepClock(tau=-1.0)


class TestOverloadAttainment:
    """Deterministic overload on one lane, the whole stream staged at t=0:
    the impossible pair first (head-of-line blocking), then the fast
    backlog, then the express pair (tight budget) and the heavies (tight,
    but feasible if served early). Only ``deadline`` admits by slack and
    evicts the pair that can never converge, so it lands strictly more
    requests inside their budgets than any other policy."""

    SLO = {"impossible": 300.0, "fast": 2500.0, "express": 150.0,
           "heavy": 600.0}
    PIPE = dict(slots=1, max_batch=4, chunk_rounds=16, prefetch=None,
                growth=2.0)
    OTHERS = ("fifo", "residual", "windowed")

    def _stream(self):
        graphs = ([("impossible", ising_grid(6, 3.5, seed=s)) for s in (0, 2)]
                  + [("fast", _fast(s)) for s in range(6)]
                  + [("express", _fast(s)) for s in (10, 11)]
                  + [("heavy", ising_grid(6, 2.2, seed=0)),
                     ("heavy", ising_grid(6, 2.5, seed=4))])
        return [(i, pgm, self.SLO[kind])
                for i, (kind, pgm) in enumerate(graphs)]

    @pytest.fixture(scope="class")
    def reports(self, engine):
        return {policy: serve_async(engine, iter(self._stream()),
                                    jax.random.key(0), admission=policy,
                                    clock=SweepClock(), **self.PIPE)
                for policy in self.OTHERS + ("deadline",)}

    @pytest.mark.parametrize("other", OTHERS)
    def test_deadline_attains_strictly_more(self, reports, other):
        def attained(policy):
            return sum(r.within_slo for r in reports[policy].records)

        assert attained("deadline") > attained(other)

    def test_deadline_evicts_only_the_impossible_pair(self, reports):
        evicted = sorted(r.rid for r in reports["deadline"].records
                         if r.evicted)
        assert evicted == [0, 1]
        assert reports["deadline"].stats.evictions == 2
        for policy in self.OTHERS:
            assert not any(r.evicted for r in reports[policy].records)


class TestDeadlineDeterminism:
    """Acceptance: under an injected SweepClock the full serving timeline
    (admission order, sync times, evictions) is run-to-run identical --
    no wall-clock leak anywhere in the deadline path."""

    def _stream(self):
        return [(0, _impossible(), 40.0), (1, _fast(0), None),
                (2, _fast(1), 500.0), (3, _fast(2), 500.0)]

    def test_run_to_run_identical(self, engine):
        runs = []
        for _ in range(2):
            clock = SweepClock()
            rep = serve_async(engine, iter(self._stream()),
                              jax.random.key(0), admission="deadline",
                              clock=clock, **KW)
            runs.append((rep, _timeline(rep), clock.t,
                         list(rep.stats.eviction_log)))
        (a, tl_a, t_a, ev_a), (b, tl_b, t_b, ev_b) = runs
        assert tl_a == tl_b
        assert t_a == t_b
        assert ev_a == ev_b
        assert a.stats.evictions == b.stats.evictions
        for ra, rb in zip(a.records, b.records):
            _assert_bitwise(ra.result, rb.result)

    def test_wall_time_sleeps_do_not_move_virtual_time(self, engine):
        def slow_stream():
            for item in self._stream():
                time.sleep(0.01)        # wall time must be invisible
                yield item
        want = serve_async(engine, iter(self._stream()), jax.random.key(0),
                           admission="deadline", clock=SweepClock(), **KW)
        got = serve_async(engine, slow_stream(), jax.random.key(0),
                          admission="deadline", clock=SweepClock(), **KW)
        assert _timeline(got) == _timeline(want)


class TestEvictionCorrectness:
    """Evicted requests surface with partial beliefs and correct sweep
    accounting; survivors are bitwise-identical to a fifo run."""

    def test_midflight_eviction_partial_result_and_accounting(self, engine):
        # Width-2 bucket: 32 virtual s per chunk sync. The impossible
        # graph's deadline (40) falls between sync 1 (t=32) and sync 2
        # (t=64), so it is evicted at t=64 with 32 rounds on the clock.
        stream = [(0, _impossible(), 40.0), (1, _fast(0), None)]
        rep = serve_async(engine, iter(stream), jax.random.key(0),
                          admission="deadline", clock=SweepClock(), **KW)
        by_rid = {r.rid: r for r in rep.records}
        ev = by_rid[0]
        assert ev.status == "evicted" and ev.evicted
        assert not ev.within_slo
        assert not bool(ev.result.converged)
        rounds = int(ev.result.rounds)
        assert rounds == 32
        assert ev.t_done == 64.0
        # partial beliefs, not a silent drop: finite and normalized
        b = np.asarray(ev.result.beliefs)
        real = np.isfinite(b).any(axis=-1)
        mass = np.exp(b[real]).sum(axis=-1)
        np.testing.assert_allclose(mass, 1.0, rtol=1e-5)
        assert rep.stats.evictions == 1
        assert rep.stats.evicted_sweeps == rounds
        assert [rid for _, rid in rep.stats.eviction_log] == [0]
        ok = by_rid[1]
        assert ok.status == "completed" and ok.within_slo

    def test_survivors_bitwise_match_fifo_run(self, engine):
        graphs = [(0, _impossible(), 30.0), (1, _fast(0), None),
                  (2, _fast(1), 400.0), (3, chain_graph(30, seed=2), None),
                  (4, _fast(2), None)]
        dl = serve_async(engine, iter(graphs), jax.random.key(7),
                         admission="deadline", clock=SweepClock(), **KW)
        fifo = serve_async(engine,
                           iter([(rid, pgm) for rid, pgm, _ in graphs]),
                           jax.random.key(7), admission="fifo", **KW)
        fifo_by_rid = {r.rid: r.result for r in fifo.records}
        survivors = [r for r in dl.records if not r.evicted]
        assert {r.rid for r in survivors} == {1, 2, 3, 4}
        for rec in survivors:
            _assert_bitwise(rec.result, fifo_by_rid[rec.rid])

    def test_staged_eviction_prior_beliefs_zero_service(self, engine):
        # One lane: the impossible head occupies it for 160 rounds of
        # virtual time; the deadlined request expires while still staged
        # and must come back with prior beliefs and zero service time.
        def stream():
            yield (0, _impossible(), None)
            yield (1, _fast(0), 10.0)
        rep = serve_async(engine, stream(), jax.random.key(0),
                          admission="deadline", clock=SweepClock(),
                          slots=1, max_batch=1, chunk_rounds=16, prefetch=1)
        by_rid = {r.rid: r for r in rep.records}
        ev = by_rid[1]
        assert ev.status == "evicted"
        assert int(ev.result.rounds) == 0
        assert ev.t_admit == ev.t_done        # never entered a bucket
        assert ev.service_s == 0.0
        assert not bool(ev.result.converged)
        b = np.asarray(ev.result.beliefs)
        real = np.isfinite(b).any(axis=-1)
        np.testing.assert_allclose(np.exp(b[real]).sum(axis=-1), 1.0,
                                   rtol=1e-5)
        assert rep.stats.evictions == 1
        head = by_rid[0]
        assert head.status == "completed"     # no SLO: never given up on
        assert not bool(head.result.converged)

    def test_evict_false_never_gives_up(self, engine):
        stream = [(0, _impossible(), 40.0), (1, _fast(0), None)]
        rep = serve_async(engine, iter(stream), jax.random.key(0),
                          admission="deadline",
                          admission_kwargs={"evict": False},
                          clock=SweepClock(), **KW)
        assert rep.stats.evictions == 0
        by_rid = {r.rid: r for r in rep.records}
        assert by_rid[0].status == "completed"
        assert not by_rid[0].within_slo       # missed, but served


class TestSlotPackingParity:
    """The pick_many hook: its default single-pick path is exactly
    pick_group, and packing is bitwise-invisible to results -- for every
    registered policy."""

    def _fake_groups(self):
        a = _Group((64, 32, 2, 4, 4))
        a.queue.extend([
            _Staged(rid=0, elem=None, key=None, t_enqueue=1.0, score=0.3,
                    slo=900.0),
            _Staged(rid=1, elem=None, key=None, t_enqueue=2.0, score=0.1,
                    slo=50.0)])
        b = _Group((128, 64, 2, 8, 8))
        b.queue.extend([
            _Staged(rid=2, elem=None, key=None, t_enqueue=0.5, score=0.7,
                    slo=200.0)])
        return [a, b]

    def test_pick_many_free1_equals_pick_group_all_policies(self):
        for name, cls in sorted(ADMISSION_POLICIES.items()):
            policy = cls()
            if name == "windowed":
                # windowed consults the pipeline for exhaustion/targets; an
                # exhausted stub makes every group immediately ready.
                policy.pipeline = type("P", (), {"_exhausted": True,
                                                 "max_batch": 2,
                                                 "_groups": {}})()
            groups = self._fake_groups()
            want = policy.pick_group(groups, now=3.0)
            got = policy.pick_many(groups, now=3.0, free=1)
            assert got == [want], f"policy {name!r} diverges from pick_group"

    def test_deadline_pick_many_packs_by_urgency(self):
        policy = DeadlineAdmission()
        groups = self._fake_groups()
        # group a's head-of-queue urgency (slo 50 at t_enqueue 2) beats
        # group b's (slo 200): packing returns both, most urgent first.
        got = policy.pick_many(groups, now=3.0, free=2)
        assert got == [groups[0], groups[1]]
        assert policy.pick_many(groups, now=3.0, free=1) == [groups[0]]

    @pytest.mark.parametrize("name", sorted(ADMISSION_POLICIES))
    def test_packing_is_bitwise_invisible(self, engine, name):
        # Mixed shape families so multiple groups coexist and slots=3
        # actually packs; trajectory invariance demands identical results.
        stream = [(0, _fast(0), None), (1, chain_graph(30, seed=1), None),
                  (2, _fast(1), None), (3, chain_graph(34, seed=2), None),
                  (4, ising_grid(7, 1.5, seed=3), None)]
        kw = dict(max_batch=2, chunk_rounds=16, prefetch=None)
        one = serve_async(engine, iter(stream), jax.random.key(5),
                          admission=name, clock=SweepClock(), slots=1, **kw)
        packed = serve_async(engine, iter(stream), jax.random.key(5),
                             admission=name, clock=SweepClock(), slots=3,
                             **kw)
        a = {r.rid: r.result for r in one.records}
        b = {r.rid: r.result for r in packed.records}
        assert sorted(a) == sorted(b) == [0, 1, 2, 3, 4]
        for rid in a:
            _assert_bitwise(b[rid], a[rid])


class TestLearnedEffort:
    """The ridge effort predictor behind RoundsHistory.expect: beats the
    nearest-neighbor table it replaced, round-trips exactly, and cold
    starts safely."""

    KINDS = [(64, 32, 2, 4, 4), (256, 128, 2, 8, 8), (1024, 512, 2, 16, 16)]

    @staticmethod
    def _rounds(kind, score):
        # Ground truth linear in the ridge features: learnable exactly.
        return 5.0 + 20.0 * score + 3.0 * np.log1p(kind[0])

    def _observe_all(self, hist):
        for kind in self.KINDS[:2]:
            for score in (0.05, 0.2, 0.4, 0.6, 0.8):
                hist.observe(kind, score, self._rounds(kind, score))

    def test_ridge_beats_nearest_mae(self):
        ridge = RoundsHistory(predictor="ridge", l2=1e-3)
        nearest = RoundsHistory(predictor="nearest")
        self._observe_all(ridge)
        self._observe_all(nearest)
        # Held-out queries: unseen scores on seen kinds, plus a kind
        # nearest has never recorded (it can only fall back to default).
        queries = [(self.KINDS[0], 0.3), (self.KINDS[0], 0.7),
                   (self.KINDS[1], 0.1), (self.KINDS[1], 0.5),
                   (self.KINDS[2], 0.25), (self.KINDS[2], 0.65)]
        fallback = 30.0

        def mae(hist):
            errs = [abs(hist.expect(k, s, default=fallback)
                        - self._rounds(k, s)) for k, s in queries]
            return sum(errs) / len(errs)

        assert mae(ridge) < mae(nearest)
        assert mae(ridge) < 1.0           # linear truth: near-exact fit

    def test_ridge_cold_start_returns_none(self):
        model = RidgeEffort()
        x = RidgeEffort.features((64, 32, 2, 4, 4), 0.5)
        assert model.predict(x) is None
        model.fit_one(x, 10.0)
        assert model.predict(x) is None   # one point cannot anchor a slope
        model.fit_one(RidgeEffort.features((64, 32, 2, 4, 4), 0.9), 20.0)
        assert model.predict(x) is not None
        with pytest.raises(ValueError):
            RidgeEffort(l2=0.0)

    def test_expect_default_and_prior_seeding(self):
        cold = RoundsHistory()
        assert cold.expect((1, 2, 3), 0.5) is None
        assert cold.expect((1, 2, 3), 0.5, default=7.0) == 7.0
        assert cold.mean() is None
        assert cold.mean(default=3.0) == 3.0
        seeded = RoundsHistory(prior=42.0)
        assert seeded.expect((1, 2, 3), 0.5) == 42.0
        assert seeded.expect((1, 2, 3), 0.5, default=7.0) == 42.0
        assert seeded.mean((9, 9, 9)) == 42.0
        seeded.observe((1, 2, 3), 0.5, 11.0)
        seeded.observe((1, 2, 3), 0.6, 13.0)
        assert seeded.mean((1, 2, 3)) == pytest.approx(12.0)
        # an unseen kind now prefers the global mean over the prior
        assert seeded.mean((9, 9, 9)) == pytest.approx(12.0)

    def test_serialization_roundtrip_identical_predictions(self):
        hist = RoundsHistory(capacity=8, predictor="ridge", prior=17.0)
        self._observe_all(hist)
        hist.observe(self.KINDS[2], 0.33, 44.0, extra=(1.5, 0.2))
        blob = json.dumps(hist.to_dict())      # JSON-safe end to end
        back = RoundsHistory.from_dict(json.loads(blob))
        assert back.capacity == 8 and back.prior == 17.0
        assert back.predictor == "ridge"
        for kind in self.KINDS:
            for score in (0.0, 0.15, 0.5, 0.95):
                assert back.expect(kind, score) == hist.expect(kind, score)
            assert back.mean(kind) == hist.mean(kind)

    def test_ridge_model_roundtrip_exact(self):
        model = RidgeEffort(l2=0.5)
        rng = np.random.default_rng(0)
        for _ in range(10):
            model.fit_one(rng.normal(size=RidgeEffort.DIM),
                          float(rng.uniform(1, 100)))
        back = RidgeEffort.from_dict(model.to_dict())
        assert back.n_observations == model.n_observations
        x = rng.normal(size=RidgeEffort.DIM)
        assert back.predict(x) == model.predict(x)


class TestNoStarvation:
    """A generous-deadline request cannot be passed over forever by a
    stream of urgent arrivals: the aging counter force-admits it."""

    def test_aging_force_admits_passed_over_head(self):
        policy = DeadlineAdmission(aging=2)
        group = _Group((64, 32, 2, 4, 4))
        group.queue.append(_Staged(rid=0, elem=None, key=None,
                                   t_enqueue=0.0, slo=10_000.0))
        admitted = []
        for i in range(1, 6):
            group.queue.append(_Staged(rid=100 + i, elem=None, key=None,
                                       t_enqueue=float(i), slo=5.0))
            admitted += [s.rid for s in policy.take(group, 1)]
            if 0 in admitted:
                break
        # skipped at most `aging` times, then force-admitted
        assert 0 in admitted
        assert admitted.index(0) <= policy.aging

    def test_sustained_overload_serves_everyone(self, engine):
        # Every request is feasible; the generous one arrives first and
        # keeps losing the slack race -- it must still complete.
        stream = [(0, _fast(0), 10_000.0)] + \
                 [(i, _fast(i), 500.0) for i in range(1, 7)]
        rep = serve_async(engine, iter(stream), jax.random.key(0),
                          admission="deadline",
                          admission_kwargs={"aging": 2},
                          clock=SweepClock(), **KW)
        assert sorted(r.rid for r in rep.records) == list(range(7))
        assert rep.stats.evictions == 0
        assert all(r.status == "completed" for r in rep.records)
        assert {r.rid for r in rep.records if r.within_slo} >= {0}


class TestLifecycleAndRouting:
    """Teardown under in-flight eviction, and the router tier merging
    evicted records with replica attribution."""

    def _wait_threads(self, baseline, timeout=10.0):
        deadline = time.time() + timeout
        while threading.active_count() > baseline and time.time() < deadline:
            time.sleep(0.02)
        return threading.active_count()

    def test_close_under_inflight_eviction(self, engine):
        baseline = threading.active_count()
        stream = [(0, _impossible(), 40.0), (1, _fast(0), None),
                  (2, _fast(1), None)]
        pipe = ServingPipeline(engine, jax.random.key(0),
                               admission="deadline", clock=SweepClock(),
                               ingest_threads=1, **KW)
        gen = pipe.serve(iter(stream))
        first = next(gen)                  # mid-flight, work still resident
        assert first.rid in {0, 1, 2}
        gen.close()
        pipe.close()
        assert self._wait_threads(baseline) <= baseline
        with pytest.raises(ValueError):
            list(pipe.serve(iter([])))
        pipe.close()                       # idempotent

    def test_serve_routed_merges_evicted_with_attribution(self):
        clock = SweepClock()
        stream = [(0, _impossible(), 80.0),
                  (1, ising_grid(6, 3.5, seed=2), 80.0),
                  (2, _fast(0), None), (3, _fast(1), None),
                  (4, _fast(2), None), (5, _fast(3), None)]
        res = serve_routed(CFG, iter(stream), jax.random.key(0),
                           replicas=2, routing="round_robin", steal=False,
                           admission="deadline", clock=clock, slots=1,
                           max_batch=2, chunk_rounds=16, prefetch=4)
        assert sorted(r.rid for r in res.records) == list(range(6))
        evicted = [r for r in res.records if r.evicted]
        assert {r.rid for r in evicted} == {0, 1}
        for rec in evicted:
            assert rec.status == "evicted" and not rec.within_slo
            assert rec.replica == rec.rid % 2      # round_robin attribution
            assert not bool(rec.result.converged)
            assert np.isfinite(np.asarray(rec.result.beliefs)).any()
        assert sum(s.evictions for s in res.replica_stats) == 2
        completed = [r for r in res.records if not r.evicted]
        assert all(r.within_slo for r in completed)

    def test_router_percentiles_status_filter(self):
        def rec(rid, t_done, status="completed"):
            return RequestRecord(rid=rid, result=None, t_enqueue=0.0,
                                 t_admit=0.1, t_done=t_done, status=status)
        from repro.serve.replica import RoutedRecord
        records = [
            RoutedRecord(replica=0, kind=(1,), stolen=False, t_route=0.0,
                         record=rec(0, 2.0)),
            RoutedRecord(replica=1, kind=(1,), stolen=False, t_route=0.0,
                         record=rec(1, 0.25, status="evicted"))]
        res = RouterResult(records=records,
                           stats=RouterStats(policy="round_robin",
                                             steal=False, routed=[1, 1]),
                           replica_stats=[])
        assert res.latency_percentiles((50,))["p50"] == \
            pytest.approx(1125.0)          # mixed: the eviction lies
        assert res.latency_percentiles(
            (50,), status="completed")["p50"] == pytest.approx(2000.0)
        assert res.latency_percentiles(
            (50,), status="evicted")["p50"] == pytest.approx(250.0)
        assert not np.isnan(res.latency_percentiles(
            (50,), field="service", status=None)["p50"])
        with pytest.raises(ValueError):
            res.latency_percentiles(status="bogus")

    def test_async_percentiles_status_filter(self):
        recs = [RequestRecord(rid=0, result=None, t_enqueue=0.0,
                              t_admit=0.5, t_done=1.0),
                RequestRecord(rid=1, result=None, t_enqueue=0.0,
                              t_admit=0.05, t_done=0.1, status="evicted")]
        rep = AsyncServeResult(records=recs, stats=AsyncServeStats())
        assert rep.latency_percentiles(
            (50,), status="completed")["p50"] == pytest.approx(1000.0)
        assert rep.latency_percentiles(
            (50,), status="evicted")["p50"] == pytest.approx(100.0)
        assert not np.isnan(rep.latency_percentiles(
            (50,), status="evicted", field="admission")["p50"])
        with pytest.raises(ValueError):
            rep.latency_percentiles(status="nope")


class TestPropertySweeps:
    """Hypothesis property sweeps (each skips when hypothesis is absent --
    per-test importorskip, so the rest of this module always runs)."""

    def test_sweep_clock_accumulates_any_program(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @given(st.lists(st.tuples(st.booleans(),
                                  st.integers(min_value=0,
                                              max_value=10_000)),
                        max_size=30))
        @settings(max_examples=50, deadline=None)
        def check(program):
            clock = SweepClock()
            total = 0.0
            for is_chunk, amount in program:
                if is_chunk:
                    clock.on_chunk(amount)
                else:
                    clock.advance(float(amount))
                total += float(amount)
            assert clock() == pytest.approx(total)

        check()

    def test_ridge_features_fixed_width_and_finite(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        kinds = st.recursive(
            st.one_of(st.integers(min_value=-10**6, max_value=10**6),
                      st.text(max_size=3), st.booleans()),
            lambda inner: st.tuples(inner, inner), max_leaves=8)

        @given(kinds, st.floats(min_value=-1e6, max_value=1e6),
               st.lists(st.floats(min_value=-1e3, max_value=1e3),
                        max_size=4))
        @settings(max_examples=100, deadline=None)
        def check(kind, score, extra):
            x = RidgeEffort.features(kind, score, extra)
            assert x.shape == (RidgeEffort.DIM,)
            assert np.isfinite(x).all()
            assert x[0] == 1.0

        check()

    def test_history_roundtrip_predictions_identical(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        obs = st.lists(
            st.tuples(st.sampled_from([(64, 32, 2), (256, 64, 4)]),
                      st.floats(min_value=0.0, max_value=1.0),
                      st.floats(min_value=1.0, max_value=300.0)),
            min_size=0, max_size=12)

        @given(obs, st.floats(min_value=0.0, max_value=1.0))
        @settings(max_examples=50, deadline=None)
        def check(observations, query_score):
            hist = RoundsHistory(capacity=8)
            for kind, score, rounds in observations:
                hist.observe(kind, score, rounds)
            back = RoundsHistory.from_dict(hist.to_dict())
            for kind in [(64, 32, 2), (256, 64, 4), (999, 9, 9)]:
                assert back.expect(kind, query_score, default=-1.0) == \
                    hist.expect(kind, query_score, default=-1.0)

        check()
