"""Asynchronous BP serving: online request streams, double-buffered bucket
slots, prefetch staging, and bucket compaction.

``BPEngine.serve`` (repro.core.engine) made the engine a scheduler one level
up: it decides which graphs occupy device slots each chunk. But the legacy
driver materializes the whole request list, steps one resident bucket at a
time, and keeps a bucket at its admission width until its group finishes --
once the pending queue drains, evacuated slots are dead weight every
remaining chunk still pays for. This module rebuilds that loop as a
pipeline:

- **online streams**: requests arrive from any iterator; nothing needs the
  full workload up front. Arrivals are *staged* -- padded host-side (numpy,
  no XLA warm-up) and moved early with ``jax.device_put`` -- so admission
  and backfill never wait on host prep or H2D transfer.
- **double-buffered slots**: up to ``slots`` resident buckets are stepped
  per cycle. Every slot dispatches first (JAX async dispatch returns
  before the chunk finishes), then the host pulls and stages new arrivals
  *while the device crunches*, and only then does each slot sync and get
  serviced (evacuation, backfill, compaction). Host bucketing no longer
  idles the device, and a straggling bucket no longer idles the host.
- **bucket compaction**: when a group's queue has drained and the stream is
  exhausted, survivors re-bucket into a narrower batch (power-of-two
  widths, so at most log2(width) recompiles per shape family), removing
  the dead-slot sweeps that evacuation alone cannot -- a slot with no
  pending work to backfill still costs one device sweep per loop iteration
  at the old width.
- **admission policies**: *which staged request enters a bucket when* is a
  pluggable :class:`AdmissionPolicy` resolved through the
  ``ADMISSION_POLICIES`` registry (mirroring the scheduler and update-
  backend registries): ``"fifo"`` is the arrival-order default (bitwise the
  pre-policy behavior), ``"residual"`` lifts Residual BP's
  prioritize-by-expected-effort argument from message scheduling to request
  admission (a cheap residual-at-admit score, calibrated by per-kind
  observed-rounds history, co-batches similar-effort requests so stragglers
  stop pinning buckets of fast peers), ``"windowed"`` trades a small
  admission delay for fuller buckets (the p50-latency vs throughput knob),
  and ``"deadline"`` is the SLA tier: per-request latency budgets from the
  stream (``(rid, pgm, slo_s)`` triples), admission ordered by predicted
  slack, multiple groups packed into free slots per cycle (``pick_many``),
  and mid-flight eviction of requests whose residual decay says they will
  not make their deadline -- evicted requests surface as
  ``status="evicted"`` records with partial beliefs, never silently
  dropped. See ``docs/admission.md``.
- **threaded ingestion**: ``ingest_threads=N`` moves the stream pull onto
  feeder threads behind a bounded queue, so a source that blocks in
  ``__next__`` (a socket, a slow producer) no longer stalls device
  dispatch -- the serving loop keeps stepping resident buckets and drains
  the feeder opportunistically.

Trajectory invariance is the load-bearing property: a graph's trajectory
depends only on its own padded shape and RNG key (the batched loop body is
per-graph gated, and the update runs on a disjoint union), so neither the
slot count, nor backfill order, nor admission policy, nor compaction
changes any result bit. On a materialized ``Sequence`` the pipeline reuses
``serve``'s group-ceiling padding, making ``serve_async`` bitwise-identical
to the legacy driver -- which is now itself a thin wrapper over this
module.
"""

from __future__ import annotations

import dataclasses
import heapq
import queue as _queue
import threading
import time
from collections import deque
from typing import (Deque, Dict, Iterable, Iterator, List, Mapping, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batch import (BatchedPGM, RoundsHistory, _pow2_ceil,
                              bucket_key, bucket_shape, group_ceilings)
from repro.core.engine import (BPEngine, BPResult, BPState, ServeStats,
                               _load_slot)
from repro.core.graph import NEG_INF, PGM, pad_pgm_arrays
from repro.core.registry import Registry

__all__ = ["ADMISSION_POLICIES", "AdmissionPolicy", "AsyncServeResult",
           "AsyncServeStats", "DeadlineAdmission", "FIFOAdmission",
           "RequestRecord", "ResidualAdmission", "ServingPipeline",
           "SweepClock", "WindowedAdmission", "get_admission_policy",
           "list_admission_policies", "register_admission_policy",
           "serve_async"]


# --------------------------------------------------------------- records --

@dataclasses.dataclass
class RequestRecord:
    """One served request: its ``BPResult`` plus the host-side timeline.

    ``t_enqueue`` is when the request was pulled from the stream (queue-in),
    ``t_admit`` when it was loaded into a resident bucket slot, ``t_done``
    when its result was released after a chunk sync (pipeline-clock
    seconds, ``perf_counter`` by default; the result's arrays may still be
    materializing -- release is dispatch, not blocking). ``latency_s`` is
    the serving metric: queue-in to result release.

    ``status`` is ``"completed"`` for the normal release path and
    ``"evicted"`` when the admission policy gave up on the request (the
    ``deadline`` policy's hopeless-work call); an evicted record still
    carries the request's *partial* result -- beliefs at the messages it
    reached, ``converged=False`` -- never a silent drop. A request evicted
    before it ever entered a bucket has ``t_admit == t_done`` (zero
    service time) and prior beliefs. ``slo_s`` is the request's latency
    budget from the stream (``None`` = no deadline)."""

    rid: int                    # input position (also the RNG fold_in index)
    result: BPResult
    t_enqueue: float
    t_admit: float
    t_done: float
    slo_s: float | None = None
    status: str = "completed"

    @property
    def latency_s(self) -> float:
        """Queue-in -> result-release latency, seconds."""
        return self.t_done - self.t_enqueue

    @property
    def evicted(self) -> bool:
        """True when the policy gave up on this request before it finished
        (``status == "evicted"``); the result is partial."""
        return self.status == "evicted"

    @property
    def deadline(self) -> float | None:
        """Absolute completion deadline in pipeline-clock seconds
        (``t_enqueue + slo_s``), or ``None`` without an SLO."""
        return None if self.slo_s is None else self.t_enqueue + self.slo_s

    @property
    def within_slo(self) -> bool:
        """Did this request complete within its latency budget? Requests
        without an SLO count as within; evicted ones never do."""
        if self.status != "completed":
            return False
        return self.slo_s is None or self.latency_s <= self.slo_s

    @property
    def queue_s(self) -> float:
        """Time spent waiting for a bucket slot, seconds."""
        return self.t_admit - self.t_enqueue

    @property
    def service_s(self) -> float:
        """Time resident in a bucket slot, seconds."""
        return self.t_done - self.t_admit


@dataclasses.dataclass
class AsyncServeStats(ServeStats):
    """``ServeStats`` plus the async pipeline's own accounting.

    ``compactions`` counts re-bucketing events (``compaction_log`` records
    ``(chunk index, width before, width after)`` for each);
    ``buckets_opened`` counts slot admissions (fresh resident batches, i.e.
    compile-relevant shapes seen), and ``staged`` counts requests pulled
    from the stream and prefetched to the device. ``policy`` names the
    admission policy that drove the run; ``admission_holds`` counts
    admission checks the policy deferred (a ``windowed`` policy holding a
    bucket open to fill it); ``admission_widths`` logs the width of every
    opened bucket (suppressed by ``record_events=False``), the direct
    observable for the latency-vs-fullness tradeoff.

    Eviction accounting (the ``deadline`` policy's hopeless-work calls):
    ``evictions`` counts requests released with ``status="evicted"``
    (mid-flight *and* expired-while-staged), ``evicted_sweeps`` the device
    sweeps those requests had consumed when given up on (a subset of
    ``useful_sweeps`` -- work that ran but missed its SLO), and
    ``eviction_log`` records ``(chunk index, rid)`` per event."""

    compactions: int = 0
    #: (chunk index, width before, width after) per compaction event
    compaction_log: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)
    buckets_opened: int = 0
    staged: int = 0
    policy: str = "fifo"
    admission_holds: int = 0
    #: width of each opened bucket, in admission order
    admission_widths: List[int] = dataclasses.field(default_factory=list)
    evictions: int = 0
    evicted_sweeps: int = 0
    #: (chunk index, rid) per eviction event
    eviction_log: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class AsyncServeResult:
    """``serve_async`` output: per-request records in *completion* order
    plus pipeline stats. ``results`` re-sorts to input (rid) order, matching
    the legacy ``ServeResult.results`` contract."""

    records: List[RequestRecord]    # completion order
    stats: AsyncServeStats

    @property
    def results(self) -> List[BPResult]:
        """Per-request ``BPResult`` list indexed by rid. For the usual
        dense 0..n-1 rids this is input order; streams that supplied sparse
        explicit rids leave ``None`` gaps at the unused positions (rejected
        beyond a small sparsity factor -- use ``.records`` there)."""
        n = 1 + max((rec.rid for rec in self.records), default=-1)
        if n > 4 * len(self.records) + 64:
            raise ValueError(
                f"rids too sparse for a dense results list (max rid {n - 1} "
                f"over {len(self.records)} records); use .records instead")
        out: List[BPResult | None] = [None] * n
        for rec in self.records:
            out[rec.rid] = rec.result
        return out  # type: ignore[return-value]

    def latency_percentiles(
            self, qs: Sequence[float] = (50, 95, 99), *,
            field: str = "latency",
            status: str | None = None) -> Dict[str, float]:
        """Latency percentiles in ms, ``{"p50": ...}`` (NaN entries when no
        matching requests were served). ``field`` selects the timeline
        component so admission wait and device residency report separately
        instead of conflated into one number: ``"latency"`` (queue-in ->
        result, the end-to-end metric), ``"admission"`` (queue-in -> admit,
        the wait the admission *policy* controls -- ``windowed`` trades it
        up, a hot backfill path trades it down), or ``"service"`` (admit ->
        result, the device-side residency time). ``status`` filters the
        records: ``"completed"`` / ``"evicted"`` / ``None`` (all). Once a
        run evicts, the unfiltered number conflates a fast eviction with a
        fast completion -- SLO reporting wants ``status="completed"``."""
        attrs = {"latency": "latency_s", "admission": "queue_s",
                 "service": "service_s"}
        if field not in attrs:
            raise KeyError(f"field must be one of {sorted(attrs)}, "
                           f"got {field!r}")
        if status not in (None, "completed", "evicted"):
            raise ValueError("status must be None, 'completed' or 'evicted',"
                             f" got {status!r}")
        recs = self.records if status is None else \
            [r for r in self.records if r.status == status]
        if not recs:
            return {f"p{q:g}": float("nan") for q in qs}
        lat = np.array([getattr(r, attrs[field]) for r in recs]) * 1e3
        return {f"p{q:g}": float(np.percentile(lat, q)) for q in qs}


# ------------------------------------------------------------- internals --

@dataclasses.dataclass
class _Staged:
    """A request staged for admission: padded to its group's ceilings and
    already ``device_put`` (the prefetch). ``score`` is the admission
    policy's effort estimate (0.0 under FIFO); ``passed_over`` counts takes
    that skipped this request while it was the queue head (the residual
    policy's aging/no-starvation counter); ``slo`` is the latency budget
    the stream attached (seconds from ``t_enqueue``, ``None`` = no
    deadline) and ``extra`` the policy's per-request feature tuple
    (coupling stats, fed to the learned effort model)."""
    rid: int
    elem: PGM
    key: jax.Array
    t_enqueue: float
    score: float = 0.0
    passed_over: int = 0
    slo: float | None = None
    extra: Tuple[float, ...] = ()


class _Group:
    """One shape family: fixed padded-shape ceilings + its pending queue
    (enqueue order; policies may remove from the middle, so the head is
    always the oldest *remaining* request)."""

    __slots__ = ("ceilings", "queue")

    def __init__(self, ceilings: Tuple[int, int, int, int, int]):
        self.ceilings = ceilings
        self.queue: Deque[_Staged] = deque()


@dataclasses.dataclass
class _AdmitMeta:
    """Host-side per-request metadata carried while resident in a slot."""
    t_enqueue: float
    t_admit: float
    score: float
    slo: float | None = None
    extra: Tuple[float, ...] = ()


@dataclasses.dataclass(eq=False)     # remove-by-identity from the slot list
class _Slot:
    """One resident bucket: its group, engine state, and host-side caches
    (live rid per batch slot, last-synced per-graph rounds, admit times)."""
    group: _Group
    state: BPState
    live: List[int | None]
    rounds_host: np.ndarray
    r_before: np.ndarray
    #: rid -> admit-time metadata (enqueue/admit times, score, slo)
    meta: Dict[int, _AdmitMeta]

    @property
    def width(self) -> int:
        return len(self.live)


def _narrow_state(state: BPState, idx: Sequence[int]) -> BPState:
    """Gather batch slots ``idx`` out of a batched ``BPState`` (the
    compaction primitive): every per-graph leaf -- graph arrays, messages,
    scheduler carry, RNG keys, counters -- is sliced along the batch axis,
    so each kept graph's trajectory continues bit-for-bit in the narrower
    batch."""
    ia = jnp.asarray(list(idx), dtype=jnp.int32)
    take = lambda x: x[ia]                                    # noqa: E731
    return dataclasses.replace(
        state,
        graph=state.graph.take(ia),
        logm=take(state.logm),
        sched_state=jax.tree.map(take, state.sched_state),
        rng=state.rng[ia],
        rounds=take(state.rounds),
        done=take(state.done),
        updates=take(state.updates),
        unconverged_history=take(state.unconverged_history),
        max_residual=take(state.max_residual))


# ----------------------------------------------------- admission policies --

def _residual_at_admit(arrs: Mapping[str, np.ndarray]) -> float:
    """Max L-inf residual of one BP step from uniform messages, computed
    host-side in numpy over the padded arrays ``pad_pgm_arrays`` produced.

    This is the paper's residual r(m) (Eq. 4) evaluated at the initial
    message state -- the same quantity Residual BP prioritizes *messages*
    by, here evaluated once per *request* as its admission score. Numpy on
    purpose: scoring happens at staging time on the serving/feeder thread,
    and a jnp pass would pay one XLA compilation per fresh shape (the exact
    warm-up the numpy staging path exists to avoid)."""
    emask = np.asarray(arrs["edge_mask"])                      # (E,)
    smask = np.asarray(arrs["state_mask"])                     # (V, S)
    dst = np.asarray(arrs["edge_dst"])
    src = np.asarray(arrs["edge_src"])
    n_states = np.asarray(arrs["n_states"]).astype(np.float64)
    dst_mask = smask[dst]                                      # (E, S)
    logm = np.where(dst_mask, -np.log(n_states[dst])[:, None], NEG_INF)
    contrib = np.where(emask[:, None], logm, 0.0)
    vsum = np.zeros_like(smask, dtype=np.float64)
    np.add.at(vsum, dst, contrib)
    pre = (np.asarray(arrs["log_psi_v"]) + vsum)[src] \
        - logm[np.asarray(arrs["edge_rev"])]
    pre = np.where(smask[src], pre, NEG_INF)
    scores = np.asarray(arrs["log_psi_e"]) + pre[:, :, None]   # (E, S, S)
    m = np.maximum(scores.max(axis=1, keepdims=True), NEG_INF)
    cand = np.squeeze(m, 1) + np.log(
        np.maximum(np.exp(scores - m).sum(axis=1), 1e-38))
    x = np.where(dst_mask, cand, NEG_INF)
    mz = np.maximum(x.max(axis=1, keepdims=True), NEG_INF)
    z = np.squeeze(mz, 1) + np.log(np.maximum(
        np.where(dst_mask, np.exp(x - mz), 0.0).sum(axis=1), 1e-38))
    cand = np.where(dst_mask, cand - z[:, None], NEG_INF)
    d = np.where(dst_mask, np.abs(cand - logm), 0.0)
    resid = np.where(emask, d.max(axis=1), 0.0)
    return float(resid.max())


class AdmissionPolicy:
    """Base admission policy: *which staged request enters a bucket when*.

    The pipeline calls the hooks below at fixed points; the base
    implementations are exactly the pre-policy FIFO behavior, so a subclass
    overrides only the decisions it changes. Policies are addressable by
    string through ``ADMISSION_POLICIES`` (``get_admission_policy``), the
    same registry pattern as schedulers and update backends, so
    ``BPConfig(admission="residual")`` stays serializable end-to-end.

    Hooks (called on the serving thread):

    - ``score(pgm, arrs, group)`` -- per-request effort estimate computed at
      staging time (``arrs`` are the padded numpy arrays, pre-``device_put``).
    - ``features(pgm, arrs, group)`` -- extra per-request feature values
      (coupling stats) for the learned effort model; default none.
    - ``ready(group, now)`` -- may a new bucket open from this group now?
      (``windowed`` answers no while it gathers a fuller bucket.)
    - ``pick_group(groups, now)`` -- which ready group admits when a slot
      frees; default is cross-group FIFO by oldest staged head, the
      no-starvation choice.
    - ``pick_many(groups, now, free)`` -- slot packing: the groups to open
      buckets from *this admission cycle*, up to ``free`` slots. The
      default delegates to a single ``pick_group`` call (one group per
      cycle iteration -- bitwise the legacy path); a packing policy returns
      several at once so narrow co-arriving groups dispatch in the same
      device cycle instead of across cycles.
    - ``take(group, width, slot=None)`` -- remove and return up to ``width``
      staged requests; ``slot`` is the resident bucket being backfilled
      (``None`` when opening a fresh bucket).
    - ``cull(group, now)`` -- staged requests to give up on *before*
      admission (released as ``status="evicted"`` with prior beliefs);
      default none. The deadline policy culls expired requests.
    - ``should_evict(slot, rid, rounds, residual, now)`` -- mid-flight
      eviction: called per live unfinished request after each chunk sync
      (only when ``evicts`` is True) with its cumulative rounds and current
      max residual; True releases it as ``status="evicted"`` with its
      partial beliefs. Default never.
    - ``observe(group, score, rounds, service_s=...)`` -- completion
      feedback: the rounds a released request actually ran and its wall
      service time (feeds effort + pace calibration). Not called for
      evicted requests (their rounds are not a convergence count).
    - ``forget(rid)`` -- the request left its slot (released or evicted);
      drop any per-rid tracking state.
    - ``pull_bonus()`` -- extra requests the host should pull beyond
      ``prefetch`` (``windowed`` raises it to fill a held bucket).
    - ``wait_hint(groups, now)`` -- seconds the drive loop may sleep when
      nothing is admissible but work is staged (0 = no wait needed).
    """

    name = "base"
    #: policies that may evict (mid-flight or staged) set this True; the
    #: pipeline then fetches per-graph residuals at each sync and runs the
    #: cull/should_evict hooks (False skips that work entirely).
    evicts = False

    def __init__(self):
        self.pipeline: "ServingPipeline | None" = None

    def bind(self, pipeline: "ServingPipeline") -> "AdmissionPolicy":
        """Attach to the driving pipeline (called once from its
        constructor); returns self so construction chains. A policy
        instance holds pipeline-coupled state (the bound pipeline, any
        history), so sharing one across pipelines would silently read the
        wrong pipeline's groups/exhaustion -- rebinding refuses instead:
        pass a registry spec string (always constructed fresh) or a new
        instance per pipeline."""
        if self.pipeline is not None and self.pipeline is not pipeline:
            raise ValueError(
                f"{type(self).__name__} instance is already bound to a "
                "pipeline; admission policies are per-pipeline -- use a "
                "registry spec string or a fresh instance")
        self.pipeline = pipeline
        return self

    def score(self, pgm: PGM, arrs: Mapping[str, np.ndarray],
              group: _Group) -> float:
        """Effort estimate for one staged request; FIFO scores nothing."""
        return 0.0

    def features(self, pgm: PGM, arrs: Mapping[str, np.ndarray],
                 group: _Group) -> Tuple[float, ...]:
        """Extra per-request feature values for the learned effort model
        (coupling stats); the base policy computes none."""
        return ()

    def ready(self, group: _Group, now: float) -> bool:
        """May a fresh bucket open from ``group`` now? FIFO: always."""
        return True

    def pick_group(self, groups: Iterable[_Group], now: float):
        """The group to admit from: cross-group FIFO over ready groups
        (oldest staged head first), or ``None`` when nothing is
        admissible."""
        ready = [g for g in groups if g.queue and self.ready(g, now)]
        return min(ready, key=lambda g: g.queue[0].t_enqueue, default=None)

    def pick_many(self, groups: Iterable[_Group], now: float,
                  free: int) -> "List[_Group]":
        """The groups to open buckets from this admission cycle (at most
        ``free``, one bucket each). The default delegates to a single
        :meth:`pick_group` call -- exactly the legacy one-group-per-cycle
        admission, so every non-packing policy keeps its bitwise behavior;
        packing policies override to fill several free slots at once."""
        g = self.pick_group(groups, now)
        return [] if g is None else [g]

    def take(self, group: _Group, width: int,
             slot: "_Slot | None" = None) -> List[_Staged]:
        """Remove and return up to ``width`` staged requests from
        ``group``'s queue. FIFO pops the oldest."""
        return [group.queue.popleft()
                for _ in range(min(width, len(group.queue)))]

    def cull(self, group: _Group, now: float) -> List[_Staged]:
        """Remove and return staged requests to give up on before they are
        ever admitted (the deadline policy's expired-in-queue path); the
        base policy never culls."""
        return []

    def should_evict(self, slot: _Slot, rid: int, rounds: int,
                     residual: float, now: float) -> bool:
        """Mid-flight eviction decision for one live unfinished request
        (called per chunk sync, only when ``evicts``); the base policy
        never evicts."""
        return False

    def observe(self, group: _Group, score: float, rounds: int,
                service_s: float = 0.0,
                extra: Tuple[float, ...] = ()) -> None:
        """Completion feedback for one released request; FIFO ignores it."""

    def forget(self, rid: int) -> None:
        """Request ``rid`` left its slot; drop any per-rid tracking."""

    def pull_bonus(self) -> int:
        """Extra pull target beyond ``prefetch`` (0 for FIFO)."""
        return 0

    def wait_hint(self, groups: Iterable[_Group], now: float) -> float:
        """Seconds the drive loop may sleep when work is staged but nothing
        is admissible (only a holding policy returns > 0)."""
        return 0.0


class FIFOAdmission(AdmissionPolicy):
    """Arrival-order admission -- the default, and bitwise the pre-policy
    pipeline: buckets open from the group whose staged head has waited
    longest, requests enter in enqueue order, backfill pops the oldest.
    Zero scoring cost; the right choice when requests are effort-homogeneous
    or latency fairness dominates."""

    name = "fifo"


class ResidualAdmission(AdmissionPolicy):
    """Expected-effort admission: co-batch requests that will run similarly
    long, so stragglers stop pinning buckets of already-finished peers.

    Residual BP (Elidan et al.) orders *message* updates by residual --
    spend work where convergence is farthest. This policy lifts that idea
    one level up, to request admission: every staged request is scored by
    its **residual at admit** (one numpy BP step from uniform messages, the
    paper's r(m) evaluated at the initial state), and a per-kind
    :class:`~repro.core.batch.RoundsHistory` calibrates that proxy into
    expected rounds from what similar requests actually ran. Buckets are
    then composed by similarity: a fresh bucket seeds with the *oldest*
    staged request and fills with the nearest expected-effort neighbors;
    backfill picks the staged request closest to the mean expected effort
    of the slot's live occupants. Fast-converging requests ride
    fast buckets that release early; long-running ones co-batch and do
    useful work together -- the gated chunk body then wastes no sweeps on
    mixed-effort buckets (``tests/test_serving.py``,
    ``test_residual_cobatching_cuts_wasted_sweeps``).

    No-starvation: a fresh bucket always seeds with the oldest head, and a
    head skipped by ``aging`` consecutive takes is force-admitted next, so
    on any stream in which takes keep happening every staged request is
    admitted after at most ``aging`` further takes once it reaches the
    head. ``history_capacity`` bounds per-kind feedback kept
    (:class:`~repro.core.batch.RoundsHistory`)."""

    name = "residual"

    def __init__(self, aging: int = 16, history_capacity: int = 64,
                 history: RoundsHistory | None = None):
        super().__init__()
        if aging < 1:
            raise ValueError(f"aging must be >= 1, got {aging}")
        self.aging = aging
        # An explicit ``history`` may be shared across pipelines (it locks
        # internally): the router tier passes every replica one instance so
        # effort calibration pools instead of cold-starting per replica.
        # The *policy* stays per-pipeline (bind() enforces that); only the
        # observation store is shared.
        self.history = history if history is not None \
            else RoundsHistory(capacity=history_capacity)

    def score(self, pgm: PGM, arrs: Mapping[str, np.ndarray],
              group: _Group) -> float:
        return _residual_at_admit(arrs)

    def expected(self, group: _Group, score: float) -> float:
        """Expected rounds for an admission score: the history's prediction
        (learned ridge model by default, see
        :class:`~repro.core.batch.RoundsHistory`), falling back to the raw
        score before any feedback exists."""
        return self.history.expect(group.ceilings, score,
                                   default=float(score))

    def take(self, group: _Group, width: int,
             slot: "_Slot | None" = None) -> List[_Staged]:
        # Selection cost is O(queue * history_capacity) per take (one
        # expected() per staged element, each a bounded history scan). The
        # online path bounds the queue by ``prefetch``, so this is small
        # per cycle; for huge *materialized* streams (prefetch=None)
        # prefer a finite prefetch to keep admission work linear.
        q = group.queue
        width = min(width, len(q))
        if width == 0:
            return []
        head = q[0]
        anchor = None
        forced = head.passed_over >= self.aging
        if slot is not None and not forced:
            live = [self.expected(group, slot.meta[r].score)
                    for r in slot.live if r is not None]
            if live:
                anchor = sum(live) / len(live)
        if anchor is None:
            anchor = self.expected(group, head.score)
            forced = True       # fresh bucket (or aged head): seed = oldest
        exp = [self.expected(group, s.score) for s in q]
        pick = set(heapq.nsmallest(width, range(len(q)),
                                   key=lambda i: (abs(exp[i] - anchor), i)))
        if forced and 0 not in pick:
            pick.remove(max(pick, key=lambda i: (abs(exp[i] - anchor), i)))
            pick.add(0)
        if 0 not in pick:
            head.passed_over += 1
        chosen = [q[i] for i in sorted(pick)]
        kept = [s for i, s in enumerate(q) if i not in pick]
        q.clear()
        q.extend(kept)
        return chosen

    def observe(self, group: _Group, score: float, rounds: int,
                service_s: float = 0.0,
                extra: Tuple[float, ...] = ()) -> None:
        self.history.observe(group.ceilings, score, rounds, extra=extra)


class WindowedAdmission(AdmissionPolicy):
    """Delay-for-fullness admission -- the latency-vs-throughput knob.

    FIFO opens a bucket the moment one request is staged, so bursty or slow
    arrival processes produce narrow buckets that under-fill the device.
    This policy *holds* a group's first admission while its staged queue is
    below ``target`` (default: the pipeline's ``max_batch``), for at most
    ``window_s`` seconds of the head request's waiting time -- trading a
    bounded p50 admission wait for fuller buckets (fewer compiles, fewer
    per-bucket fixed costs, better device occupancy). While holding it
    raises the host's pull target (``pull_bonus``) so the window actually
    fills instead of merely waiting. Backfill of already-open buckets is
    never delayed (filling a running bucket is pure win), and exhaustion of
    the stream makes every group immediately ready, so a final partial
    bucket never waits out its window.

    The ``window_s`` bound is guaranteed for feeder-backed
    (``ingest_threads``) and non-blocking sources. A plain iterator that
    *blocks* in ``__next__`` can overshoot it: the fill pull runs on the
    serving thread, and a blocked ``next`` cannot be interrupted mid-call
    -- the general blocking-source caveat, so pair ``windowed`` with
    ``ingest_threads`` when the source can stall."""

    name = "windowed"

    def __init__(self, window_s: float = 0.01, target: int | None = None):
        super().__init__()
        if window_s < 0:
            raise ValueError(f"window_s must be >= 0, got {window_s}")
        if target is not None and target < 1:
            raise ValueError(f"target must be >= 1, got {target}")
        self.window_s = window_s
        self.target = target

    def _target(self) -> int:
        assert self.pipeline is not None
        return self.target or self.pipeline.max_batch or 0

    def ready(self, group: _Group, now: float) -> bool:
        assert self.pipeline is not None
        if self.pipeline._exhausted:
            return True
        t = self._target()
        if t and len(group.queue) >= t:
            return True
        return now - group.queue[0].t_enqueue >= self.window_s

    def pull_bonus(self) -> int:
        assert self.pipeline is not None
        t = self._target()
        if not t:
            return 0
        return sum(max(0, t - len(g.queue))
                   for g in self.pipeline._groups.values() if g.queue)

    def wait_hint(self, groups: Iterable[_Group], now: float) -> float:
        rem = [self.window_s - (now - g.queue[0].t_enqueue)
               for g in groups if g.queue]
        rem = [r for r in rem if r > 0]
        return min(rem) if rem else 0.0


def _coupling_stats(arrs: Mapping[str, np.ndarray]) -> Tuple[float, float]:
    """(mean, std) of |log pairwise potential| over real edge entries --
    the coupling-strength features the learned effort model regresses on
    (strong coupling correlates with slow convergence). Numpy on the
    staging path, same rationale as ``_residual_at_admit``."""
    lpe = np.asarray(arrs["log_psi_e"])                 # (E, S, S)
    emask = np.asarray(arrs["edge_mask"]).astype(bool)
    if not emask.any():
        return (0.0, 0.0)
    mag = np.abs(np.where(np.isfinite(lpe), lpe, 0.0))[emask]
    return (float(mag.mean()), float(mag.std()))


class SweepClock:
    """Deterministic virtual clock for SLA tests and benches: time is
    *device sweeps*, not wall seconds.

    Inject as ``ServingPipeline(clock=...)``: the pipeline reads ``now``
    via ``clock()`` and, because this class defines ``on_chunk``, advances
    it by ``tau`` virtual seconds per device sweep at every chunk sync.
    Requests staged up front at t=0 with SLOs in sweep units then make the
    whole deadline/eviction/attainment story a pure function of scheduling
    decisions -- identical on any machine, never sleeping on wall time.
    ``advance`` lets a test move time by hand (arrival processes)."""

    def __init__(self, tau: float = 1.0):
        if tau <= 0:
            raise ValueError(f"tau must be > 0, got {tau}")
        self.t = 0.0
        self.tau = float(tau)

    def __call__(self) -> float:
        return self.t

    def on_chunk(self, sweeps: int) -> None:
        """Pipeline hook: one chunk of ``sweeps`` device sweeps completed."""
        self.t += float(sweeps) * self.tau

    def advance(self, dt: float) -> None:
        """Move virtual time forward by ``dt`` seconds (manual control)."""
        self.t += float(dt)


class DeadlineAdmission(AdmissionPolicy):
    """SLA-aware admission: earliest-predicted-slack ordering, slot
    packing, and eviction of work that will not make its deadline.

    Requests carry a latency budget from the stream (``(rid, pgm, slo_s)``
    items, or ``default_slo``); *slack* is ``deadline - now - predicted
    service time``, with service predicted as the learned
    :class:`~repro.core.batch.RoundsHistory` rounds estimate times an
    EWMA-calibrated seconds-per-round pace per shape family. Three
    decisions follow:

    - **Admission order** (``take`` / ``pick_group``): least slack first --
      EDF generalized by predicted effort, so a lax request yields to an
      urgent one even when it arrived earlier. Requests without a deadline
      have infinite slack and order last; the same aging counter as
      ``residual`` force-admits a head skipped ``aging`` times, so they
      cannot starve under a sustained deadlined stream.
    - **Slot packing** (``pick_many``): fill *all* free slots in one
      admission cycle with the most-urgent distinct groups, so co-arriving
      narrow shape families dispatch in the same device cycle instead of
      serializing one per cycle.
    - **Eviction** (``should_evict`` / ``cull``): after each chunk sync the
      per-graph ``BPState`` residual gives the converging-too-slowly
      signal. A live request is hopeless when its deadline already passed,
      or when the observed residual decay rate (log-residual per round,
      the faster of last-interval and whole-trajectory slope, judged
      after ``grace`` syncs) projects convergence to
      ``eps`` past its deadline -- it is then released immediately as
      ``status="evicted"`` with its partial beliefs, freeing the slot for
      work that can still make its SLO. ``cull`` likewise gives up on
      staged requests whose deadline expired while queued (prior beliefs,
      zero service). ``evict=False`` keeps slack ordering but never gives
      up on work.

    ``safety`` scales the projected remaining time before comparing
    against the deadline (>1 = evict earlier); ``min_rate`` is the decay
    rate below which a request counts as stalled (projected never).
    ``history`` may be shared across pipelines (the router tier pools it),
    exactly as with ``residual``."""

    name = "deadline"

    def __init__(self, default_slo: float | None = None,
                 safety: float = 1.0, grace: int = 2,
                 min_rate: float = 1e-4, evict: bool = True,
                 pack: bool = True, aging: int = 16,
                 history_capacity: int = 64,
                 history: RoundsHistory | None = None):
        super().__init__()
        if default_slo is not None and default_slo < 0:
            raise ValueError(f"default_slo must be >= 0, got {default_slo}")
        if grace < 1:
            raise ValueError(f"grace must be >= 1, got {grace}")
        if aging < 1:
            raise ValueError(f"aging must be >= 1, got {aging}")
        self.default_slo = default_slo
        self.safety = float(safety)
        self.grace = grace
        self.min_rate = float(min_rate)
        self.evicts = bool(evict)
        self.pack = bool(pack)
        self.aging = aging
        self.history = history if history is not None \
            else RoundsHistory(capacity=history_capacity)
        self._pace: Dict[tuple, float] = {}     # kind -> EWMA sec/round
        self._pace_all: float | None = None
        #: rid -> (rounds, log residual, syncs seen, first-sync rounds,
        #: first-sync log residual) as of the last chunk sync
        self._track: Dict[int, Tuple[int, float, int, int, float]] = {}

    # -- scoring / features ------------------------------------------------

    def score(self, pgm: PGM, arrs: Mapping[str, np.ndarray],
              group: _Group) -> float:
        return _residual_at_admit(arrs)

    def features(self, pgm: PGM, arrs: Mapping[str, np.ndarray],
                 group: _Group) -> Tuple[float, ...]:
        return _coupling_stats(arrs)

    # -- slack -------------------------------------------------------------

    def _slo_of(self, staged: _Staged) -> float | None:
        return staged.slo if staged.slo is not None else self.default_slo

    def _deadline(self, staged: _Staged) -> float | None:
        slo = self._slo_of(staged)
        return None if slo is None else staged.t_enqueue + slo

    def _pace_of(self, ceilings: tuple) -> float:
        pace = self._pace.get(ceilings, self._pace_all)
        return 0.0 if pace is None else pace

    def slack(self, group: _Group, staged: _Staged, now: float) -> float:
        """Predicted slack seconds: time to deadline minus predicted
        service (expected rounds x calibrated pace). Infinite without a
        deadline; cold pace predicts zero service (pure EDF)."""
        deadline = self._deadline(staged)
        if deadline is None:
            return float("inf")
        est = self.history.expect(group.ceilings, staged.score,
                                  default=0.0, extra=staged.extra)
        return deadline - now - est * self._pace_of(group.ceilings)

    def _urgency(self, group: _Group, now: float) -> float:
        return min(self.slack(group, s, now) for s in group.queue)

    # -- admission ---------------------------------------------------------

    def pick_group(self, groups: Iterable[_Group], now: float):
        ready = [g for g in groups if g.queue and self.ready(g, now)]
        return min(ready, key=lambda g: (self._urgency(g, now),
                                         g.queue[0].t_enqueue, g.ceilings),
                   default=None)

    def pick_many(self, groups: Iterable[_Group], now: float,
                  free: int) -> List[_Group]:
        if not self.pack:
            return super().pick_many(groups, now, free)
        ready = [g for g in groups if g.queue and self.ready(g, now)]
        ready.sort(key=lambda g: (self._urgency(g, now),
                                  g.queue[0].t_enqueue, g.ceilings))
        return ready[:free]

    def take(self, group: _Group, width: int,
             slot: "_Slot | None" = None) -> List[_Staged]:
        q = group.queue
        width = min(width, len(q))
        if width == 0:
            return []
        now = self.pipeline.clock() if self.pipeline is not None else 0.0
        order = sorted(range(len(q)),
                       key=lambda i: (self.slack(group, q[i], now),
                                      q[i].t_enqueue, q[i].rid))
        pick = set(order[:width])
        head = q[0]
        if 0 not in pick:
            if head.passed_over >= self.aging:      # aged: force-admit
                pick.remove(order[width - 1])
                pick.add(0)
            else:
                head.passed_over += 1
        chosen = [q[i] for i in sorted(pick)]
        kept = [s for i, s in enumerate(q) if i not in pick]
        q.clear()
        q.extend(kept)
        return chosen

    def cull(self, group: _Group, now: float) -> List[_Staged]:
        if not self.evicts:
            return []
        expired = [s for s in group.queue
                   if (d := self._deadline(s)) is not None and now >= d]
        if expired:
            gone = set(id(s) for s in expired)
            kept = [s for s in group.queue if id(s) not in gone]
            group.queue.clear()
            group.queue.extend(kept)
        return expired

    # -- eviction ----------------------------------------------------------

    def should_evict(self, slot: _Slot, rid: int, rounds: int,
                     residual: float, now: float) -> bool:
        meta = slot.meta[rid]
        slo = meta.slo if meta.slo is not None else self.default_slo
        if slo is None:
            return False
        eps = self.pipeline.engine.config.eps \
            if self.pipeline is not None else 1e-3
        if residual <= eps:
            return False                # converged: releases on this sync
        deadline = meta.t_enqueue + slo
        if now >= deadline:
            return True                 # already missed: stop burning sweeps
        logr = float(np.log(max(residual, 1e-300)))
        prev = self._track.get(rid)
        if prev is None:
            self._track[rid] = (rounds, logr, 1, rounds, logr)
            return False                # need a trajectory before judging
        rounds_prev, logr_prev, syncs, r0, logr0 = prev
        self._track[rid] = (rounds, logr, syncs + 1, r0, logr0)
        if syncs + 1 < self.grace:
            return False
        dr = rounds - rounds_prev
        if dr <= 0:
            return False
        # Residual decay is non-monotone: a transient plateau in the last
        # interval must not doom a request whose whole-trajectory slope is
        # healthy, so project with the more optimistic of the two rates.
        rate = (logr_prev - logr) / dr  # log-residual decay per round
        if rounds > r0:
            rate = max(rate, (logr0 - logr) / (rounds - r0))
        if rate <= self.min_rate:       # stalled / diverging: never makes it
            return True
        est_rounds = (logr - float(np.log(eps))) / rate
        eta = now + self.safety * est_rounds * self._pace_of(
            slot.group.ceilings)
        return eta > deadline

    # -- feedback ----------------------------------------------------------

    def observe(self, group: _Group, score: float, rounds: int,
                service_s: float = 0.0,
                extra: Tuple[float, ...] = ()) -> None:
        self.history.observe(group.ceilings, score, rounds, extra=extra)
        if rounds > 0 and service_s > 0:
            pace = service_s / rounds
            old = self._pace.get(group.ceilings)
            self._pace[group.ceilings] = pace if old is None \
                else 0.5 * old + 0.5 * pace
            self._pace_all = pace if self._pace_all is None \
                else 0.5 * self._pace_all + 0.5 * pace

    def forget(self, rid: int) -> None:
        self._track.pop(rid, None)


#: name -> AdmissionPolicy class; names are the canonical serialized form
#: (``BPConfig(admission=...)`` / ``serve_async(admission=...)``). A
#: ``Registry`` (dict subclass): plain-dict reads keep working.
ADMISSION_POLICIES: Registry[type] = Registry("admission policy", {
    "fifo": FIFOAdmission,
    "residual": ResidualAdmission,
    "windowed": WindowedAdmission,
    "deadline": DeadlineAdmission,
})


def register_admission_policy(name: str, *, overwrite: bool = False):
    """Class decorator registering an :class:`AdmissionPolicy` subclass
    under ``name`` (lowercased), making it addressable by string spec --
    ``serve_async(..., admission="mine")`` -- exactly like
    ``register_scheduler`` does for schedulers. The class must be
    constructible from keyword arguments so specs stay serializable.
    Duplicate names raise ``ValueError`` unless ``overwrite=True``."""
    return ADMISSION_POLICIES.register(name, overwrite=overwrite)


def list_admission_policies() -> List[str]:
    """Sorted registered admission-policy names (valid
    ``BPConfig.admission`` / ``serve_async(admission=...)`` specs)."""
    return ADMISSION_POLICIES.names()


def get_admission_policy(spec, **kwargs) -> AdmissionPolicy:
    """Resolve an admission-policy spec: a registry name (+ constructor
    kwargs) or an already-built :class:`AdmissionPolicy` instance (kwargs
    must then be empty). The string form is what ``BPConfig.admission``
    serializes."""
    if isinstance(spec, str):
        return ADMISSION_POLICIES.lookup(spec)(**kwargs)
    if kwargs:
        raise ValueError("admission kwargs only apply to string specs, got "
                         f"instance {type(spec).__name__} plus {kwargs}")
    return spec


# ----------------------------------------------------- threaded ingestion --

_FEEDER_DONE = object()
_FEEDER_EXHAUSTED = object()


class _IngestFeeder:
    """Feeder threads pulling the request iterator into a bounded queue.

    The stream's ``__next__`` runs on daemon feeder threads (serialized by
    a lock, so any plain iterator is safe); pulled items enter a
    ``queue.Queue(maxsize)`` whose bound is the host-memory guard -- a full
    queue blocks the *feeder*, never the serving loop. Each item is stamped
    under the lock with its arrival index (the auto-rid, so rid assignment
    matches the unthreaded path item for item) and its pull time (the
    request's ``t_enqueue``). Iterator exceptions are captured and re-raised
    on the serving thread once the queue drains. ``close()`` (called from
    ``serve``'s finally, so an abandoned generator or a staging-time error
    cannot leak threads) stops the workers: puts are bounded waits
    re-checking the stop flag, so a worker blocked on a full queue exits
    promptly instead of pinning the source forever."""

    def __init__(self, it: Iterator, threads: int, maxsize: int,
                 clock=time.perf_counter):
        self._it = it
        self._clock = clock
        self._lock = threading.Lock()
        self._q: _queue.Queue = _queue.Queue(maxsize=max(1, maxsize))
        self._n = 0
        self._live = threads
        self._error: BaseException | None = None
        self._stop = False
        self._threads = [threading.Thread(target=self._worker, daemon=True)
                         for _ in range(threads)]
        for t in self._threads:
            t.start()

    def _put(self, x) -> bool:
        """Bounded-wait put that aborts once ``close()`` ran (a plain
        blocking put could pin a worker on a full queue forever)."""
        while not self._stop:
            try:
                self._q.put(x, timeout=0.05)
                return True
            except _queue.Full:
                continue
        return False

    def _worker(self) -> None:
        while True:
            with self._lock:
                if self._error is not None or self._stop:
                    break
                try:
                    item = next(self._it)
                except StopIteration:
                    break
                except BaseException as e:     # surface on serving thread
                    self._error = e
                    break
                rid, self._n = self._n, self._n + 1
                t = self._clock()
            if not self._put((rid, item, t)):  # blocks when full: the bound
                return
        self._put(_FEEDER_DONE)

    def close(self, *, join_timeout: float = 2.0) -> None:
        """Stop the feeder: workers quit pulling at their next check, the
        queue is drained so any worker blocked in ``put`` unblocks (dropping
        staged-but-unserved items -- the caller abandoned them), and worker
        threads are joined. A worker blocked inside the *source's*
        ``__next__`` cannot be interrupted mid-call; the bounded join leaves
        such a (daemon) thread behind rather than hanging shutdown -- the
        general blocking-source caveat."""
        self._stop = True
        while True:
            try:
                self._q.get_nowait()
            except _queue.Empty:
                break
        deadline = time.perf_counter() + max(0.0, join_timeout)
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.perf_counter()))

    def get(self, block: bool):
        """Next ``(auto_rid, item, t_pull)``; ``None`` when nothing is
        available right now (non-blocking miss), or the exhausted sentinel
        once every feeder thread has finished."""
        while True:
            try:
                got = self._q.get(block=block)
            except _queue.Empty:
                return None
            if got is _FEEDER_DONE:
                self._live -= 1
                if self._live == 0:
                    if self._error is not None:
                        raise self._error
                    return _FEEDER_EXHAUSTED
                continue
            return got


# --------------------------------------------------------------- pipeline --

class ServingPipeline:
    """The asynchronous serving driver (see module docstring).

    One pipeline instance serves one stream through one ``BPEngine``.
    ``serve(stream)`` is a generator yielding a ``RequestRecord`` per
    request *in completion order* -- consume it incrementally for online
    workloads, or use :func:`serve_async` to collect everything.

    Knobs: ``slots`` bounds resident buckets stepped per cycle (2 =
    double-buffering; 1 reproduces the legacy serve cadence exactly);
    ``prefetch`` is the staged-request low-water mark the host keeps pulled
    ahead of admission (``None`` = drain the stream eagerly up front);
    ``evacuate``/``compact`` toggle the straggler policies; ``admission``
    picks the admission policy -- a registry spec string (``"fifo"`` |
    ``"residual"`` | ``"windowed"``, constructed with ``admission_kwargs``)
    or a prebuilt :class:`AdmissionPolicy`; ``None`` defers to the engine's
    ``BPConfig.admission``. ``ingest_threads=N`` moves the stream pull onto
    ``N`` feeder threads behind a bounded queue (``ingest_queue`` items,
    default max(prefetch, 2N)) so a source that blocks in ``__next__`` no
    longer stalls device dispatch. ``record_events=False`` drops the
    per-request evacuation/compaction/width logs (counters stay), bounding
    host memory on indefinitely long streams; ``plan`` maps a
    ``bucket_key`` to explicit group ceilings (the materialized-stream
    compat path) -- without it each request pads to its own deterministic
    ``bucket_shape`` ceilings, the online policy.

    The stream may yield ``PGM``s (rid = arrival order), explicit
    ``(rid, PGM)`` pairs, or ``(rid, PGM, slo_s)`` triples attaching a
    latency budget (seconds from enqueue; ``rid=None`` keeps arrival-order
    rids) that deadline-aware policies read and every ``RequestRecord``
    reports via ``within_slo``. ``clock`` replaces the pipeline's time
    source (default ``time.perf_counter``) -- inject a
    :class:`SweepClock` for deterministic virtual-time tests/benches; a
    clock exposing ``on_chunk(sweeps)`` is advanced by the pipeline at
    every chunk sync. Per-request RNG keys are ``fold_in(rng, rid)``,
    so results are independent of every pipeline knob -- admission policy
    included; only the *padded shape* policy (plan vs online) can alter
    stochastic-scheduler trajectories, the caveat shared with ``run_many``.
    Without ``ingest_threads`` the stream is pulled on the serving thread:
    a source that blocks in ``__next__`` delays servicing.

    Lifecycle: a pipeline is also a context manager -- ``with
    ServingPipeline(...) as pipe`` guarantees ``close()`` on exit, which
    stops and joins any live ingest feeder threads (an abandoned ``serve``
    generator already closes its own feeder, but only once its ``finally``
    runs; owners that must not leak threads call ``close()`` explicitly --
    the router tier's replica teardown does).
    """

    def __init__(self, engine: BPEngine, rng: jax.Array, *,
                 growth: float = 2.0, max_batch: int | None = None,
                 chunk_rounds: int | None = None, evacuate: bool = True,
                 compact: bool = True, slots: int = 2,
                 prefetch: int | None = 8,
                 record_events: bool = True,
                 plan: Dict[tuple, tuple] | None = None,
                 admission: "str | AdmissionPolicy | None" = None,
                 admission_kwargs: Mapping | None = None,
                 ingest_threads: int = 0,
                 ingest_queue: int | None = None,
                 clock=None):
        if engine.is_serial:
            raise NotImplementedError(
                "serving needs a frontier scheduler (srbp is host-serial)")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_batch is not None and max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if ingest_threads < 0:
            raise ValueError(
                f"ingest_threads must be >= 0, got {ingest_threads}")
        cfg = engine.config
        self.engine = engine
        self.rng = rng
        self.growth = growth
        self.max_batch = max_batch
        self.chunk = (chunk_rounds or cfg.chunk_rounds
                      or max(1, cfg.max_rounds // 16))
        self.evacuate = evacuate
        self.compact = compact
        self.slots = slots
        self.prefetch = prefetch
        self.record_events = record_events
        self.plan = plan
        self.ingest_threads = ingest_threads
        self.ingest_queue = ingest_queue
        self.clock = clock if clock is not None else time.perf_counter
        self._clock_on_chunk = getattr(self.clock, "on_chunk", None)
        if admission is None:
            admission = getattr(cfg, "admission", "fifo")
            if admission_kwargs is None:
                admission_kwargs = dict(getattr(cfg, "admission_kwargs", ()))
        self.policy = get_admission_policy(
            admission, **dict(admission_kwargs or {})).bind(self)
        self.stats = AsyncServeStats(policy=self.policy.name)
        self._groups: Dict[tuple, _Group] = {}
        self._exhausted = False
        self._arrival = 0
        # Duplicate-rid detection only applies once the stream supplies
        # explicit (rid, PGM) pairs; auto-assigned rids are unique by
        # construction, so the common online path stores nothing per
        # request (long-lived streams must not grow host memory).
        self._explicit_rids = False
        self._seen_rids: set[int] = set()
        self._feeder: _IngestFeeder | None = None
        self._closed = False

    # -- staging (host padding + device_put prefetch) ----------------------

    def _group_for(self, pgm: PGM) -> _Group:
        if self.plan is not None:
            key = bucket_key(pgm, self.growth)
            ceilings = self.plan[key]
        else:
            key = ceilings = bucket_shape(pgm, self.growth)
        group = self._groups.get(key)
        if group is None:
            group = self._groups[key] = _Group(ceilings)
        return group

    def _stage(self, rid: int, pgm: PGM, t_enqueue: float,
               slo: float | None = None) -> None:
        if self._explicit_rids:         # rid = RNG fold_in index: must be 1:1
            if rid in self._seen_rids:
                raise ValueError(f"duplicate request id {rid} in stream")
            self._seen_rids.add(rid)
        group = self._group_for(pgm)
        e, v, s, re_, rv = group.ceilings
        arrs = pad_pgm_arrays(pgm, n_edges=e, n_vertices=v, n_states=s)
        score = self.policy.score(pgm, arrs, group)
        extra = tuple(self.policy.features(pgm, arrs, group))
        # The prefetch: H2D starts now, overlapped with device compute.
        elem = PGM(n_real_vertices=rv, n_real_edges=re_,
                   **jax.device_put(arrs))
        group.queue.append(_Staged(
            rid, elem, jax.random.fold_in(self.rng, rid), t_enqueue,
            score=score, slo=slo, extra=extra))
        self.stats.staged += 1

    def _staged_count(self) -> int:
        return sum(len(g.queue) for g in self._groups.values())

    def _pump(self, it, target: float, block: bool = False) -> None:
        """Pull requests until ``target`` are staged (or the stream ends).
        With a feeder source, ``block=False`` only drains what the feeder
        already pulled (an empty feeder queue returns immediately -- the
        non-stalling property); a plain iterator blocks in ``next`` either
        way."""
        while not self._exhausted and self._staged_count() < target:
            if isinstance(it, _IngestFeeder):
                got = it.get(block)
                if got is None:
                    return
                if got is _FEEDER_EXHAUSTED:
                    self._exhausted = True
                    return
                rid_auto, item, t = got
            else:
                try:
                    item = next(it)
                except StopIteration:
                    self._exhausted = True
                    return
                t = self.clock()
                rid_auto = self._arrival
            slo = None
            if isinstance(item, tuple):
                if len(item) == 3:
                    rid, pgm, slo = item
                    slo = None if slo is None else float(slo)
                else:
                    rid, pgm = item
                if rid is None:         # keep arrival-order rid assignment
                    rid = rid_auto
                else:
                    self._explicit_rids = True
            else:
                rid, pgm = rid_auto, item
            self._arrival += 1
            self._stage(int(rid), pgm, t, slo=slo)

    # -- slot lifecycle ----------------------------------------------------

    def _admit(self, group: _Group) -> _Slot:
        """Open a resident bucket from the group's queue: width =
        min(max_batch, pending), composition chosen by the admission
        policy, stacked from prefetched elements."""
        width = min(self.max_batch or len(group.queue), len(group.queue))
        take = self.policy.take(group, width)
        batch = BatchedPGM(pgm=jax.tree.map(
            lambda *xs: jnp.stack(xs), *[s.elem for s in take]))
        keys = jnp.stack([s.key for s in take])
        state = self.engine.init(batch, keys)
        t = self.clock()
        self.stats.buckets_opened += 1
        if self.record_events:
            self.stats.admission_widths.append(len(take))
        return _Slot(group=group, state=state,
                     live=[s.rid for s in take],
                     rounds_host=np.zeros(len(take), np.int64),
                     r_before=np.zeros(len(take), np.int64),
                     meta={s.rid: _AdmitMeta(s.t_enqueue, t, s.score,
                                             slo=s.slo, extra=s.extra)
                           for s in take})

    def _release(self, slot: _Slot, j: int, rounds: int) -> RequestRecord:
        rid = slot.live[j]
        assert rid is not None
        result = self.engine._slice_result(slot.state, j)
        slot.live[j] = None
        self.stats.evacuated += 1
        if self.record_events:      # O(requests) log; off for infinite streams
            self.stats.evacuation_log.append((self.stats.chunks, rid))
        meta = slot.meta.pop(rid)
        t_done = self.clock()
        self.policy.observe(slot.group, meta.score, rounds,
                            service_s=max(t_done - meta.t_admit, 0.0),
                            extra=meta.extra)
        self.policy.forget(rid)
        return RequestRecord(rid=rid, result=result,
                             t_enqueue=meta.t_enqueue,
                             t_admit=meta.t_admit, t_done=t_done,
                             slo_s=meta.slo)

    def _evict(self, slot: _Slot, j: int, rounds: int) -> RequestRecord:
        """Release batch slot ``j`` as *evicted*: the partial beliefs at
        the last chunk sync, ``status="evicted"``, sweep accounting under
        ``evicted_sweeps``. The policy is not ``observe``d -- an evicted
        round count is a truncation artifact, not a convergence effort
        sample -- but its per-rid tracking is dropped via ``forget``."""
        rid = slot.live[j]
        assert rid is not None
        result = self.engine._slice_result(slot.state, j)
        slot.live[j] = None
        self.stats.evacuated += 1
        self.stats.evictions += 1
        self.stats.evicted_sweeps += rounds
        if self.record_events:
            self.stats.eviction_log.append((self.stats.chunks, rid))
        meta = slot.meta.pop(rid)
        self.policy.forget(rid)
        return RequestRecord(rid=rid, result=result,
                             t_enqueue=meta.t_enqueue,
                             t_admit=meta.t_admit, t_done=self.clock(),
                             slo_s=meta.slo, status="evicted")

    def _evict_staged(self, group: _Group,
                      staged: _Staged) -> RequestRecord:
        """Give up on a request whose deadline expired while queued: zero
        service, prior beliefs (the BP fixed point of zero rounds --
        normalized unary potentials, since uniform initial messages cancel
        in per-vertex normalization), ``status="evicted"``."""
        lpv = np.asarray(staged.elem.log_psi_v)                # (V, S)
        smask = np.asarray(staged.elem.state_mask).astype(bool)
        x = np.where(smask, lpv, NEG_INF)
        m = np.maximum(x.max(axis=1, keepdims=True), NEG_INF)
        z = m + np.log(np.maximum(
            np.where(smask, np.exp(x - m), 0.0).sum(axis=1, keepdims=True),
            1e-38))
        beliefs = jnp.asarray(np.where(smask, x - z, NEG_INF),
                              dtype=jnp.float32)
        dst = np.asarray(staged.elem.edge_dst)
        n_states = np.asarray(staged.elem.n_states).astype(np.float64)
        logm = jnp.asarray(                 # the round-0 uniform messages
            np.where(smask[dst], -np.log(n_states[dst])[:, None], NEG_INF),
            dtype=jnp.float32)
        cfg = self.engine.config
        hist = jnp.full((cfg.max_rounds if cfg.history else 1,), -1,
                        jnp.int32)
        result = BPResult(
            beliefs=beliefs, logm=logm,
            rounds=jnp.int32(0), updates=jnp.uint32(0),
            converged=jnp.asarray(False),
            max_residual=jnp.float32(staged.score),
            unconverged_history=hist, sched_state=None)
        self.stats.evictions += 1
        if self.record_events:
            self.stats.eviction_log.append((self.stats.chunks, staged.rid))
        t = self.clock()
        self.policy.forget(staged.rid)
        return RequestRecord(rid=staged.rid, result=result,
                             t_enqueue=staged.t_enqueue,
                             t_admit=t, t_done=t,
                             slo_s=staged.slo, status="evicted")

    def _cull(self) -> Iterator[RequestRecord]:
        """Ask the policy for staged requests to give up on (expired
        deadlines) and release them with prior beliefs."""
        now = self.clock()
        for group in self._groups.values():
            for staged in self.policy.cull(group, now):
                yield self._evict_staged(group, staged)

    def _backfill(self, slot: _Slot, j: int) -> None:
        staged = self.policy.take(slot.group, 1, slot=slot)[0]
        slot.state = _load_slot(slot.state, jnp.int32(j), staged.elem,
                                staged.key, scheduler=self.engine.scheduler)
        slot.live[j] = staged.rid
        slot.rounds_host[j] = 0
        slot.meta[staged.rid] = _AdmitMeta(staged.t_enqueue, self.clock(),
                                           staged.score, slo=staged.slo,
                                           extra=staged.extra)
        self.stats.backfilled += 1

    def _maybe_compact(self, slot: _Slot) -> None:
        """Re-bucket survivors into a narrower batch once no backfill can
        ever arrive (queue drained, stream exhausted). Pow2 target widths
        bound recompiles at log2(width) per shape family; surplus slots are
        filled with already-dead entries, which the gated chunk body keeps
        inert."""
        if not (self.compact and self.evacuate and self._exhausted
                and not slot.group.queue):
            return
        keep = [j for j, rid in enumerate(slot.live) if rid is not None]
        if not keep:
            return
        new_w = _pow2_ceil(len(keep))
        if new_w >= slot.width:
            return
        dead = [j for j, rid in enumerate(slot.live) if rid is None]
        chosen = sorted(keep + dead[:new_w - len(keep)])
        self.stats.compactions += 1
        if self.record_events:
            self.stats.compaction_log.append(
                (self.stats.chunks, slot.width, new_w))
        slot.state = _narrow_state(slot.state, chosen)
        slot.live = [slot.live[j] for j in chosen]
        slot.rounds_host = slot.rounds_host[chosen]
        slot.r_before = slot.r_before[chosen]

    def _service(self, slot: _Slot) -> Iterable[RequestRecord]:
        """Sync one stepped slot and apply the straggler policies: account
        sweeps, release finished graphs, backfill freed slots from the
        group queue, then consider compaction."""
        state = slot.state
        r_after = np.asarray(jax.device_get(state.rounds))   # blocks on chunk
        done = np.asarray(jax.device_get(state.done))
        max_rounds = self.engine.config.max_rounds
        inner = self.engine.scheduler.inner_sweeps
        self.stats.chunks += 1
        chunk_sweeps = int(state.chunk_iters) * inner * slot.width
        self.stats.device_sweeps += chunk_sweeps
        self.stats.useful_sweeps += int(sum(
            int(r_after[j] - slot.r_before[j])
            for j in range(slot.width) if slot.live[j] is not None))
        slot.rounds_host = r_after.copy()
        if self._clock_on_chunk is not None:   # virtual clocks tick in sweeps
            self._clock_on_chunk(chunk_sweeps)
        if not self.evacuate:
            # Run-to-completion baseline: release everything only when the
            # whole bucket is finished; never backfill, never compact.
            if all(bool(done[j]) or r_after[j] >= max_rounds
                   for j in range(slot.width)):
                for j in range(slot.width):
                    yield self._release(slot, j, int(r_after[j]))
            return
        for j in range(slot.width):
            if slot.live[j] is None:
                continue
            if bool(done[j]) or r_after[j] >= max_rounds:
                yield self._release(slot, j, int(r_after[j]))
                if slot.group.queue:
                    self._backfill(slot, j)
        if self.policy.evicts:
            # Mid-flight eviction: per-graph residuals at this sync are the
            # converging-too-slowly signal; hopeless requests release now
            # (partial beliefs) instead of burning sweeps to max_rounds.
            resid = np.asarray(jax.device_get(state.max_residual))
            now = self.clock()
            for j in range(slot.width):
                rid = slot.live[j]
                if rid is None:
                    continue
                if self.policy.should_evict(slot, rid, int(r_after[j]),
                                            float(resid[j]), now):
                    yield self._evict(slot, j, int(r_after[j]))
                    if slot.group.queue:
                        self._backfill(slot, j)
        # Slots that went dead while the queue was momentarily empty are
        # revived by later arrivals -- without this, an online straggler
        # bucket would burn dead-slot sweeps while new same-shape requests
        # queue behind it.
        for j in range(slot.width):
            if slot.live[j] is None and slot.group.queue:
                self._backfill(slot, j)
        self._maybe_compact(slot)

    # -- the drive loop ----------------------------------------------------

    def _admissible(self) -> _Group | None:
        """The group the admission policy would open a bucket from now
        (cross-group FIFO under the default policies, so a minority shape
        family cannot starve behind a sustained majority one)."""
        return self.policy.pick_group(self._groups.values(), self.clock())

    def _await_work(self, it) -> bool:
        """Nothing is resident: wait until something becomes admissible.
        Returns False when serving is finished (stream exhausted, nothing
        staged). Blocks on the source only when nothing at all is staged;
        when work is staged but held (an open admission window), pulls
        toward the policy's fill target and sleeps out (a slice of) the
        window instead."""
        if not self._staged_count():
            if self._exhausted:
                return False
            self._pump(it, 1, block=True)
            return bool(self._staged_count()) or not self._exhausted
        before = self._staged_count()
        target = before + self.policy.pull_bonus()
        if target > before:
            self._pump(it, target)
        hint = self.policy.wait_hint(self._groups.values(), self.clock())
        if self._staged_count() == before and hint > 0:
            time.sleep(min(hint, 0.05))
        return True

    def serve(self, stream: Iterable) -> Iterator[RequestRecord]:
        """Drive ``stream`` through the pipeline, yielding one
        ``RequestRecord`` per request in completion order.

        Each cycle: (1) admit staged groups into free slots (which groups,
        which requests, and when are the admission policy's calls), (2)
        dispatch a chunk on every slot (JAX async dispatch -- non-blocking),
        (3) pull and stage new arrivals while the device runs (from the
        feeder queue when ``ingest_threads`` is set, never blocking on the
        source), (4) sync + service each slot, yielding released results.
        Terminates when the stream is exhausted and every admitted graph
        has been released."""
        if self._closed:
            raise ValueError("ServingPipeline is closed")
        it = iter(stream)
        if self.ingest_threads:
            bound = self.ingest_queue or max(self.prefetch or 8,
                                             2 * self.ingest_threads)
            it = self._feeder = _IngestFeeder(it, self.ingest_threads, bound,
                                              clock=self.clock)
        try:
            yield from self._drive(it)
        finally:
            # An abandoned generator or a staging error must not leak
            # feeder threads blocked on a full queue.
            if isinstance(it, _IngestFeeder):
                it.close()
            self._feeder = None

    def close(self) -> None:
        """Shut the pipeline down: stop (and join) any live ingest feeder
        threads and refuse further ``serve`` calls. Idempotent. The
        ``serve`` generator already closes its feeder in a ``finally``;
        ``close`` exists for owners that hold the pipeline itself (the
        router's replica teardown, a ``with`` block) and must guarantee no
        thread survives even if the generator was never started or was
        abandoned mid-``yield``. Staged-but-unserved requests are dropped
        -- the caller abandoned them."""
        self._closed = True
        feeder, self._feeder = self._feeder, None
        if feeder is not None:
            feeder.close()

    def __enter__(self) -> "ServingPipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        """Context-manager exit: ``close()`` -- feeder threads joined."""
        self.close()

    def _drive(self, it) -> Iterator[RequestRecord]:
        """The cycle loop behind ``serve`` (source already feeder-wrapped)."""
        resident: List[_Slot] = []
        if self.prefetch is None:
            self._pump(it, float("inf"), block=True)
        while True:
            yield from self._cull()     # expired-while-staged give-ups
            while len(resident) < self.slots:
                free = self.slots - len(resident)
                picks = self.policy.pick_many(self._groups.values(),
                                              self.clock(), free)
                if not picks:
                    self._pump(it, max(1, self.prefetch or 1)
                               + self.policy.pull_bonus())
                    picks = self.policy.pick_many(self._groups.values(),
                                                  self.clock(), free)
                    if not picks:
                        if self._staged_count():   # held by an open window
                            self.stats.admission_holds += 1
                        break
                # The packing path: fill every free slot this cycle from
                # the policy's ranked groups. The default pick_many returns
                # one group, reproducing the legacy one-admit-per-iteration
                # cadence (and its pump interleaving) exactly.
                for group in picks[:free]:
                    if group.queue:
                        resident.append(self._admit(group))
            if not resident:
                if not self._await_work(it):
                    return
                continue
            for slot in resident:
                slot.r_before = slot.rounds_host.copy()
                slot.state = self.engine.step(slot.state,
                                              chunk_rounds=self.chunk)
            if self.prefetch:
                # Host-side staging overlapped with the in-flight chunks.
                # Dead slots whose group queue is empty raise the pull
                # target: staged work from *other* groups must not stop us
                # from fetching requests that could revive them. A holding
                # policy (windowed) adds its fill deficit on top.
                hunger = sum(1 for slot in resident for rid in slot.live
                             if rid is None and not slot.group.queue)
                self._pump(it, self.prefetch + hunger
                           + self.policy.pull_bonus())
            for slot in list(resident):
                yield from self._service(slot)
                if all(rid is None for rid in slot.live):
                    resident.remove(slot)


def _materialized_plan(pgms: Sequence[PGM], growth: float):
    """Legacy-compatible plan for a fully materialized stream: group by
    ``bucket_key``, pad every member to its *group's* joint ceilings, and
    feed requests in sorted-key order -- exactly the legacy ``serve``
    policy, so trajectories (and with ``slots=1``, even sweep accounting)
    coincide."""
    keyed: Dict[tuple, List[int]] = {}
    for i, p in enumerate(pgms):
        keyed.setdefault(bucket_key(p, growth), []).append(i)
    plan, ordered = {}, []
    for key in sorted(keyed):
        idx = keyed[key]
        plan[key] = group_ceilings([pgms[i] for i in idx])
        ordered.extend((i, pgms[i]) for i in idx)
    return plan, ordered


def serve_async(engine: BPEngine, stream, rng: jax.Array, *,
                growth: float = 2.0, max_batch: int | None = None,
                chunk_rounds: int | None = None, evacuate: bool = True,
                compact: bool = True, slots: int = 2,
                prefetch: int | None = 8,
                record_events: bool = True,
                admission: "str | AdmissionPolicy | None" = None,
                admission_kwargs: Mapping | None = None,
                ingest_threads: int = 0,
                ingest_queue: int | None = None,
                clock=None) -> AsyncServeResult:
    """Serve a request stream through the asynchronous pipeline.

    ``stream`` is either a materialized ``Sequence[PGM]`` -- padded with the
    legacy group-ceiling plan, so per-request results are *bitwise
    identical* to ``BPEngine.serve`` on the same inputs -- or any iterator
    of PGMs (the online path: each request pads to its deterministic
    ``bucket_shape`` ceilings the moment it arrives, no global knowledge
    needed). Iterator items may also be ``(rid, PGM)`` pairs or
    ``(rid, PGM, slo_s)`` deadline triples -- see :class:`ServingPipeline`.
    ``admission``/``admission_kwargs`` select the admission policy
    (``"fifo"`` | ``"residual"`` | ``"windowed"`` | ``"deadline"``;
    ``None`` defers to the engine's ``BPConfig.admission``),
    ``ingest_threads``/``ingest_queue`` enable the threaded ingestion
    feeder, and ``clock`` injects a virtual time source (a
    :class:`SweepClock` makes SLA behavior deterministic) -- see
    :class:`ServingPipeline` and ``docs/admission.md``. This wrapper just
    collects the generator into an :class:`AsyncServeResult` (records in
    completion order, ``.results`` in input order)."""
    plan = None
    # Only a sequence of bare PGMs takes the materialized-plan path:
    # (rid, pgm[, slo]) tuple sequences keep their explicit rids (the plan
    # would renumber them by position) and stream online.
    if isinstance(stream, Sequence) and (
            not stream or isinstance(stream[0], PGM)):
        plan, stream = _materialized_plan(list(stream), growth)
    pipe = ServingPipeline(engine, rng, growth=growth, max_batch=max_batch,
                           chunk_rounds=chunk_rounds, evacuate=evacuate,
                           compact=compact, slots=slots, prefetch=prefetch,
                           record_events=record_events, plan=plan,
                           admission=admission,
                           admission_kwargs=admission_kwargs,
                           ingest_threads=ingest_threads,
                           ingest_queue=ingest_queue, clock=clock)
    records = list(pipe.serve(stream))
    return AsyncServeResult(records=records, stats=pipe.stats)
