"""Unified, resumable BP engine: config-driven entry, chunked stepping,
converged-graph evacuation.

The paper's central knob is the *scheduling policy* (LBP/RBP/RS/RnBP); the
engine makes it -- and everything else -- one frozen, serializable
``BPConfig`` behind one inference loop:

    engine = BPEngine(BPConfig(scheduler="rnbp",
                               scheduler_kwargs={"low_p": 0.4},
                               eps=1e-3, max_rounds=2000))
    res = engine.run(pgm, jax.random.key(0))            # one-shot
    res_list = engine.run_many(pgms, jax.random.key(0)) # bucketed stream

Chunked resume is first-class instead of a private ``_init_logm`` backdoor:

    state = engine.init(pgm, rng)           # BPState: a checkpointable pytree
    while not engine.finished(state):
        state = engine.step(state)          # one jitted chunk of <= chunk_rounds
    res = engine.result(state)

``step`` carries the *entire* trajectory (messages, scheduler state, the RNG
stream, round/update counters, history), so N rounds via repeated ``step``
are bit-identical to N rounds in one ``run`` -- the property the resilience
layer (repro.ft) and the serving driver both build on.

On the batched path ``step`` returns per-graph convergence, which the
serving layer exploits: between chunks, converged graphs are *evacuated*
(their results released immediately) and their batch slots *backfilled* from
the pending queue, so straggler rounds stop costing the whole bucket. Sweep
accounting (device vs useful) quantifies the win against the
run-every-bucket-to-completion baseline. The serving *pipeline* -- online
request iterators, double-buffered slot dispatch, prefetch staging, bucket
compaction -- lives in ``repro.core.serving``; ``serve(stream)`` here is its
synchronous compatibility wrapper.

``run_bp`` / ``run_bp_batch`` / ``run_bp_many`` / ``run_srbp`` remain as
deprecated wrappers with exact-trajectory parity (they delegate here).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import messages as M
from repro.core.batch import BatchedPGM, batch_keys, bucket_pgms
from repro.core.graph import PGM
from repro.core.schedulers import get_scheduler
from repro.core.schedulers.base import Scheduler

__all__ = ["BPConfig", "BPEngine", "BPResult", "BPState", "ServeResult",
           "ServeStats"]


# --------------------------------------------------------------- results --

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BPResult:
    """Finished-trajectory record returned by ``BPEngine.run``/``result``.

    Shapes below are the single-graph case; on the batched path every field
    carries a leading ``(B,)`` axis. ``converged`` is True iff every real
    edge's residual fell below the config's ``eps`` within ``max_rounds``
    sweeps; ``beliefs`` are valid either way (the best marginals at exit).
    """

    beliefs: jax.Array          # (V, S) log-marginals ((B, V, S) batched)
    logm: jax.Array             # (E, S) final messages
    rounds: jax.Array           # () int32: bulk sweeps executed
    updates: jax.Array          # () uint32: committed messages (exact count;
                                #   cast at the boundary -- f32 accumulation
                                #   lost precision past ~16M messages)
    converged: jax.Array        # () bool
    max_residual: jax.Array     # () f32 at exit
    unconverged_history: jax.Array  # (max_rounds,) int32, -1 past exit
    sched_state: Any            # scheduler carry (chunked-resume leftover)


# ---------------------------------------------------------------- config --

def _freeze_kwargs(kw) -> Tuple[Tuple[str, Any], ...]:
    if isinstance(kw, Mapping):
        return tuple(sorted(kw.items()))
    return tuple(kw)


@dataclasses.dataclass(frozen=True)
class BPConfig:
    """Frozen, hashable inference config; the engine's single entry knob.

    ``scheduler`` is a registry spec string ("lbp"/"rbp"/"rs"/"rnbp" --
    serializable end-to-end via ``to_dict``/``from_dict``) or a prebuilt
    ``Scheduler`` instance; ``scheduler_kwargs`` feed the registry
    constructor. ``"srbp"`` selects the host-serial baseline (``run`` only).

    ``backend`` picks the message-update implementation by name ("ref" |
    "pallas", resolved through ``repro.kernels.ops.UPDATE_BACKENDS``) or is a
    ``(pgm, logm) -> (cand, resid)`` callable. ``batch_backend`` optionally
    overrides the batched path with a natively batched update (callable or
    "pallas"); the default folds the bucket into a disjoint union and reuses
    the single-graph ``backend``.

    ``chunk_rounds`` bounds rounds per ``step`` (None = run to
    ``max_rounds`` in one chunk); ``history`` sizes the per-round
    unconverged-count buffer (paper Figs 2/4).

    ``admission`` is the *serving-side* policy knob: a registry spec string
    ("fifo" | "windowed" | "residual", resolved through
    ``repro.core.serving.ADMISSION_POLICIES``; ``admission_kwargs`` feed
    the constructor) or a prebuilt ``AdmissionPolicy``. It only matters to
    ``serve``/``serve_async``/``ServingPipeline`` -- one-shot ``run`` paths
    ignore it -- and rides the config so a serialized deployment spec pins
    its admission behavior alongside its scheduler.
    """

    scheduler: Any = "lbp"
    scheduler_kwargs: Any = ()
    eps: float = 1e-3
    max_rounds: int = 2000
    damping: float = 0.0
    backend: Any = "ref"
    batch_backend: Any = None
    chunk_rounds: int | None = None
    history: bool = True
    admission: Any = "fifo"
    admission_kwargs: Any = ()

    def __post_init__(self):
        object.__setattr__(self, "scheduler_kwargs",
                           _freeze_kwargs(self.scheduler_kwargs))
        object.__setattr__(self, "admission_kwargs",
                           _freeze_kwargs(self.admission_kwargs))
        if not self.eps > 0:
            raise ValueError(f"eps must be > 0, got {self.eps}")
        if self.max_rounds < 1:
            raise ValueError(f"max_rounds must be >= 1, got {self.max_rounds}")
        if not 0.0 <= self.damping < 1.0:
            raise ValueError(f"damping must be in [0, 1), got {self.damping}")
        if self.chunk_rounds is not None and self.chunk_rounds < 1:
            raise ValueError("chunk_rounds must be >= 1 or None, got "
                             f"{self.chunk_rounds}")

    def make_scheduler(self) -> Scheduler:
        return get_scheduler(self.scheduler, **dict(self.scheduler_kwargs))

    def to_dict(self) -> dict:
        """JSON-ready form. Requires a string (or registered) scheduler spec
        and string backends -- the serializable subset."""
        from repro.core.schedulers import scheduler_spec
        d = dataclasses.asdict(self)
        if not isinstance(self.scheduler, str):
            name, kw = scheduler_spec(self.scheduler)
            d["scheduler"], d["scheduler_kwargs"] = name, _freeze_kwargs(kw)
        for f in ("backend", "batch_backend"):
            if d[f] is not None and not isinstance(d[f], str):
                raise ValueError(f"{f} is a callable; not serializable")
        if not isinstance(d["admission"], str):
            raise ValueError("admission is a policy instance; use a registry "
                             "spec string for a serializable config")
        d["scheduler_kwargs"] = dict(d["scheduler_kwargs"])
        d["admission_kwargs"] = dict(d["admission_kwargs"])
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "BPConfig":
        return cls(**dict(d))


# ----------------------------------------------------------------- state --

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BPState:
    """Resumable trajectory state -- everything a chunk boundary must carry.

    Single-graph states hold scalar counters; batched states carry a leading
    (B,) axis on every counter plus per-graph RNG keys. ``chunk_iters`` is
    bookkeeping (loop iterations executed by the last ``step``), not part of
    the trajectory.
    """

    graph: Any                  # PGM | BatchedPGM
    logm: jax.Array             # (E, S) / (B, E, S) current messages
    sched_state: Any            # scheduler carry
    rng: jax.Array              # carried key / (B,) keys
    rounds: jax.Array           # () / (B,) int32 cumulative rounds
    done: jax.Array             # () / (B,) bool per-graph convergence
    updates: jax.Array          # () / (B,) uint32 committed messages
    unconverged_history: jax.Array  # (H,) / (B, H) int32
    max_residual: jax.Array     # () / (B,) f32
    chunk_iters: jax.Array      # () int32, diagnostics only

    @property
    def batched(self) -> bool:
        return isinstance(self.graph, BatchedPGM)

    @property
    def size(self) -> int:
        return self.graph.size if self.batched else 1


# ------------------------------------------------------- chunked kernels --

def _carry_of(state: BPState):
    return (state.logm, state.sched_state, state.rng, state.rounds,
            state.done, state.updates, state.unconverged_history,
            state.max_residual, jnp.int32(0))


def _state_with(state: BPState, carry) -> BPState:
    logm, sstate, rng, rounds, done, updates, hist, max_r, iters = carry
    return dataclasses.replace(
        state, logm=logm, sched_state=sstate, rng=rng, rounds=rounds,
        done=done, updates=updates, unconverged_history=hist,
        max_residual=max_r, chunk_iters=iters)


@partial(jax.jit, static_argnames=("scheduler", "damping", "update_fn",
                                   "track_history"))
def _chunk_single(pgm: PGM, carry, limit, eps, *, scheduler: Scheduler,
                  damping: float, update_fn: Callable, track_history: bool):
    """Run the frontier loop (paper Algorithm 1) until convergence or
    ``rounds >= limit``. Body identical to the historic ``run_bp`` loop, so
    chunked execution reproduces monolithic trajectories bit-for-bit."""

    def cond(c):
        _, _, _, rounds, done, _, _, _, _ = c
        return (~done) & (rounds < limit)

    def body(c):
        logm, sstate, rng, rounds, done, updates, hist, _, iters = c
        with jax.named_scope("bp.select"):
            rng, sel_key = jax.random.split(rng)
        cand, r = update_fn(pgm, logm)
        with jax.named_scope("bp.select"):
            unconverged = jnp.sum((r >= eps)
                                  & pgm.edge_mask).astype(jnp.int32)
            frontier, sstate = scheduler.select(pgm, r, eps, sel_key, sstate,
                                                unconverged)
        with jax.named_scope("bp.commit"):
            # Converged -> commit nothing (IsConverged precedes Update in
            # Alg. 1).
            newly_done = unconverged == 0
            frontier = frontier & ~newly_done
            logm = M.apply_frontier(logm, cand, frontier, damping)
        # Residual Splash: h-1 extra masked sweeps inside the same frontier.
        for _ in range(scheduler.inner_sweeps - 1):
            cand, _ = update_fn(pgm, logm)
            with jax.named_scope("bp.commit"):
                logm = M.apply_frontier(logm, cand, frontier, damping)
        with jax.named_scope("bp.commit"):
            updates = updates + jnp.sum(frontier).astype(jnp.uint32) \
                * jnp.uint32(scheduler.inner_sweeps)
            if track_history:
                hist = hist.at[rounds].set(unconverged)
            rounds = rounds + jnp.where(newly_done, 0,
                                        jnp.int32(scheduler.inner_sweeps))
            max_r = jnp.max(r)
        return (logm, sstate, rng, rounds, newly_done, updates, hist, max_r,
                iters + 1)

    return jax.lax.while_loop(cond, body, carry)


def _where_keys(mask: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    return jnp.where(mask, new, old)


def _bcast_where(mask: jax.Array, new: jax.Array, old: jax.Array) -> jax.Array:
    m = mask.reshape(mask.shape + (1,) * (jnp.ndim(new) - 1))
    return jnp.where(m, new, old)


@partial(jax.jit, static_argnames=("scheduler", "damping", "update_fn",
                                   "batch_update_fn", "track_history"))
def _chunk_batch(batch: BatchedPGM, carry, limit, eps, *,
                 scheduler: Scheduler, damping: float, update_fn: Callable,
                 batch_update_fn: Callable | None, track_history: bool):
    """Whole-bucket frontier loop until every graph converges or reaches its
    per-graph ``limit`` (B,). Each graph's body effects are gated on its own
    ``active`` flag, so graphs at different cumulative rounds (evacuation
    backfill) each reproduce their solo trajectory exactly: a frozen graph
    commits nothing, consumes no RNG, and advances no counters."""
    bpgm = batch.pgm
    b, e = batch.size, batch.n_edges
    s = batch.n_states_max
    if batch_update_fn is None:
        # Mesh-aware fold: a sharded backend (repro.dist) advertises its
        # mesh, and the (B*E) union grid is laid out across it.
        union = batch.folded(mesh=getattr(update_fn, "mesh", None),
                             axis=getattr(update_fn, "axis", "bp"))

        def batch_update_fn(_, logm):
            cand, r = update_fn(union, logm.reshape(b * e, s))
            return cand.reshape(b, e, s), r.reshape(b, e)

    select = jax.vmap(
        lambda p, r, k, st, u: scheduler.select(p, r, eps, k, st, u))
    commit = jax.vmap(partial(M.apply_frontier, damping=damping))

    def cond(c):
        _, _, _, rounds, done, _, _, _, _ = c
        return jnp.any((~done) & (rounds < limit))

    def body(c):
        logm, sstate, keys, rounds, done, updates, hist, _, iters = c
        with jax.named_scope("bp.select"):
            active = (~done) & (rounds < limit)                 # (B,)
            split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
            keys = _where_keys(active, split[:, 0], keys)
            sel_keys = split[:, 1]
        cand, r = batch_update_fn(bpgm, logm)
        with jax.named_scope("bp.select"):
            unconverged = jnp.sum((r >= eps) & bpgm.edge_mask,
                                  axis=1).astype(jnp.int32)     # (B,)
            frontier, new_sstate = select(bpgm, r, sel_keys, sstate,
                                          unconverged)
            sstate = jax.tree.map(partial(_bcast_where, active),
                                  new_sstate, sstate)
        with jax.named_scope("bp.commit"):
            newly_done = (unconverged == 0) & active
            frontier = frontier & active[:, None] & ~newly_done[:, None]
            logm = commit(logm, cand, frontier)
        for _ in range(scheduler.inner_sweeps - 1):
            cand, _ = batch_update_fn(bpgm, logm)
            with jax.named_scope("bp.commit"):
                logm = commit(logm, cand, frontier)
        with jax.named_scope("bp.commit"):
            updates = updates + jnp.sum(frontier, axis=1).astype(jnp.uint32) \
                * jnp.uint32(scheduler.inner_sweeps)
            if track_history:
                hist = jax.vmap(lambda h, i, u, a: jnp.where(
                    a, h.at[i].set(u), h))(hist, rounds, unconverged, active)
            rounds = rounds + jnp.where(newly_done | ~active, 0,
                                        jnp.int32(scheduler.inner_sweeps))
            max_r = jnp.max(r, axis=1)
        return (logm, sstate, keys, rounds, done | newly_done, updates, hist,
                max_r, iters + 1)

    return jax.lax.while_loop(cond, body, carry)


@partial(jax.jit, static_argnames=("scheduler", "track_history", "hist_len"))
def _init_single(pgm: PGM, rng, *, scheduler: Scheduler, track_history: bool,
                 hist_len: int):
    return (M.init_messages(pgm), scheduler.init(pgm), rng, jnp.int32(0),
            jnp.asarray(False), jnp.uint32(0),
            jnp.full((hist_len if track_history else 1,), -1, jnp.int32),
            jnp.float32(jnp.inf))


@partial(jax.jit, static_argnames=("scheduler", "track_history", "hist_len"))
def _init_batch(batch: BatchedPGM, keys, *, scheduler: Scheduler,
                track_history: bool, hist_len: int):
    b = batch.size
    return (jax.vmap(M.init_messages)(batch.pgm),
            jax.vmap(scheduler.init)(batch.pgm), keys,
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
            jnp.zeros((b,), jnp.uint32),
            jnp.full((b, hist_len if track_history else 1), -1, jnp.int32),
            jnp.full((b,), jnp.inf, jnp.float32))


@jax.jit
def _beliefs_single(pgm: PGM, logm):
    return M.beliefs(pgm, logm)


@jax.jit
def _beliefs_batch(bpgm: PGM, logm):
    return jax.vmap(M.beliefs)(bpgm, logm)


@partial(jax.jit, static_argnames=("scheduler",))
def _load_slot(state: BPState, j, elem: PGM, key, *, scheduler: Scheduler):
    """Replace batch slot ``j`` with a fresh graph: swap the graph leaves and
    reset the slot's trajectory (messages, scheduler state, counters, RNG)
    exactly as ``init`` would for a solo run."""
    batch = state.graph
    new_pgm = jax.tree.map(lambda full, one: full.at[j].set(one),
                           batch.pgm, elem)
    sstate = jax.tree.map(lambda full, one: full.at[j].set(one),
                          state.sched_state, scheduler.init(elem))
    return dataclasses.replace(
        state,
        graph=dataclasses.replace(batch, pgm=new_pgm),
        logm=state.logm.at[j].set(M.init_messages(elem)),
        sched_state=sstate,
        rng=state.rng.at[j].set(key),
        rounds=state.rounds.at[j].set(0),
        done=state.done.at[j].set(False),
        updates=state.updates.at[j].set(0),
        unconverged_history=state.unconverged_history.at[j].set(-1),
        max_residual=state.max_residual.at[j].set(jnp.inf))


# ------------------------------------------------------- serving driver --

@dataclasses.dataclass
class ServeStats:
    """Sweep accounting for ``BPEngine.serve``.

    Sweeps are counted in *masked update passes per graph slot* (one loop
    iteration of a B-wide bucket = B device sweeps x ``inner_sweeps``);
    ``useful_sweeps`` counts only rounds advanced on live graphs, so
    ``wasted_sweeps`` is exactly the straggler/padding overhead evacuation
    is meant to shrink."""

    chunks: int = 0
    device_sweeps: int = 0
    useful_sweeps: int = 0
    evacuated: int = 0
    backfilled: int = 0
    #: (chunk index at evacuation, input graph index) per evacuated graph
    evacuation_log: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)

    @property
    def wasted_sweeps(self) -> int:
        return self.device_sweeps - self.useful_sweeps


@dataclasses.dataclass
class ServeResult:
    """``BPEngine.serve`` output: one ``BPResult`` per request (input
    order, each sliced to single-graph shapes) plus the run's sweep
    accounting (``ServeStats``)."""

    results: List[BPResult]     # per-request, input order
    stats: ServeStats


# ---------------------------------------------------------------- engine --

class BPEngine:
    """The unified BP inference engine (see module docstring).

    One engine instance = one resolved (scheduler, backend) pair; reuse it
    across calls so jit caches stay warm. All methods accept either a single
    ``PGM`` or a ``BatchedPGM`` bucket; ``run_many``/``serve`` take
    heterogeneous graph lists.
    """

    def __init__(self, config: BPConfig | None = None, **overrides):
        config = config or BPConfig()
        if overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        self.is_serial = (isinstance(config.scheduler, str)
                          and config.scheduler.lower() == "srbp")
        self.scheduler: Scheduler | None = (
            None if self.is_serial else config.make_scheduler())
        self.update_fn = self._resolve_backend(config.backend)
        self.batch_update_fn = (
            None if config.batch_backend is None
            else self._resolve_backend(config.batch_backend, batched=True))

    @staticmethod
    def _resolve_backend(backend, *, batched: bool = False) -> Callable:
        if callable(backend):
            return backend
        if backend == "ref" and not batched:
            return M.ref_update
        from repro.kernels.ops import get_update_fn
        return get_update_fn(backend, batched=batched)

    # -- lifecycle ---------------------------------------------------------

    def init(self, graph: PGM | BatchedPGM, rng: jax.Array) -> BPState:
        """Fresh trajectory state for ``graph``. ``rng`` is one key (split
        per-graph for buckets) or a (B,) key array."""
        if self.is_serial:
            raise NotImplementedError(
                "scheduler='srbp' is host-serial: use run(), not init/step")
        cfg, sched = self.config, self.scheduler
        if isinstance(graph, BatchedPGM):
            carry = _init_batch(graph, batch_keys(rng, graph),
                                scheduler=sched, track_history=cfg.history,
                                hist_len=cfg.max_rounds)
        else:
            carry = _init_single(graph, rng, scheduler=sched,
                                 track_history=cfg.history,
                                 hist_len=cfg.max_rounds)
        return BPState(graph, *carry, chunk_iters=jnp.int32(0))

    def step(self, state: BPState, *,
             chunk_rounds: int | None = None) -> BPState:
        """Advance one jitted chunk: at most ``chunk_rounds`` further rounds
        (per graph), stopping early on convergence. A finished state is a
        no-op. Bit-identical to running the same total rounds in one chunk.
        """
        cfg = self.config
        chunk = chunk_rounds or cfg.chunk_rounds or cfg.max_rounds
        limit = jnp.minimum(state.rounds + chunk, cfg.max_rounds)
        kw = dict(scheduler=self.scheduler, damping=cfg.damping,
                  update_fn=self.update_fn, track_history=cfg.history)
        if state.batched:
            carry = _chunk_batch(state.graph, _carry_of(state), limit,
                                 cfg.eps, batch_update_fn=self.batch_update_fn,
                                 **kw)
        else:
            carry = _chunk_single(state.graph, _carry_of(state), limit,
                                  cfg.eps, **kw)
        return _state_with(state, carry)

    def finished(self, state: BPState) -> bool:
        """True when every graph converged or exhausted ``max_rounds``."""
        return bool(jnp.all(state.done |
                            (state.rounds >= self.config.max_rounds)))

    def result(self, state: BPState) -> BPResult:
        """Finalize a state into a ``BPResult`` (computes beliefs)."""
        if state.batched:
            beliefs = _beliefs_batch(state.graph.pgm, state.logm)
        else:
            beliefs = _beliefs_single(state.graph, state.logm)
        return BPResult(beliefs=beliefs, logm=state.logm, rounds=state.rounds,
                        updates=state.updates, converged=state.done,
                        max_residual=state.max_residual,
                        unconverged_history=state.unconverged_history,
                        sched_state=state.sched_state)

    # -- one-shot ----------------------------------------------------------

    def run(self, graph: PGM | BatchedPGM, rng: jax.Array | None = None, *,
            state: BPState | None = None) -> BPResult:
        """One-shot inference. With ``chunk_rounds`` set, runs chunk by chunk
        (same trajectory, checkpointable); otherwise one ``while_loop``.
        ``state`` resumes an existing trajectory instead of starting fresh.
        For ``scheduler='srbp'`` runs the host-serial baseline and returns an
        ``SRBPResult``.

        Under an active ``jax.profiler`` trace the call is a ``bp.run`` span
        on the calling thread, with ``bp.init``, ``bp.step`` (dispatch),
        ``bp.finished`` (the blocking sync) and ``bp.result`` in it."""
        with TraceAnnotation("bp.run"):
            if self.is_serial:
                from repro.core.serial import srbp_run
                kw = dict(self.config.scheduler_kwargs)
                return srbp_run(graph, eps=self.config.eps, **kw)
            if state is None:
                if rng is None:
                    raise ValueError("run() needs an rng key (or a state)")
                with TraceAnnotation("bp.init"):
                    state = self.init(graph, rng)
            while True:
                with TraceAnnotation("bp.finished"):
                    if self.finished(state):
                        break
                with TraceAnnotation("bp.step"):
                    state = self.step(state)
            with TraceAnnotation("bp.result"):
                return self.result(state)

    def run_many(self, pgms: Sequence[PGM], rng: jax.Array, *,
                 growth: float = 2.0,
                 max_batch: int | None = None) -> List[BPResult]:
        """Bucket ``pgms`` (shape-homogeneous padded batches), run each
        bucket, return per-graph results in input order. Per-graph keys are
        ``fold_in(rng, input position)`` so the RNG stream is independent of
        the bucketing policy. (Stochastic schedulers draw per-edge
        randomness over the *padded* edge axis, so a bucketing change that
        re-pads a graph can still alter RnBP/RBP trajectories -- the fixed
        point reached, not the answer quality.)"""
        results: List[BPResult | None] = [None] * len(pgms)
        for bucket in bucket_pgms(pgms, growth=growth, max_batch=max_batch):
            keys = jnp.stack([jax.random.fold_in(rng, i)
                              for i in bucket.indices])
            res = self.run(bucket.batch, keys)
            for j, gi in enumerate(bucket.indices):
                results[gi] = jax.tree.map(lambda x: x[j], res)
        return results  # type: ignore[return-value]

    # -- serving with evacuation ------------------------------------------

    def _slice_result(self, state: BPState, j: int) -> BPResult:
        elem = state.graph.graph(j)
        sub = jax.tree.map(lambda x: x[j], (
            state.logm, state.rounds, state.done, state.updates,
            state.unconverged_history, state.max_residual, state.sched_state))
        logm, rounds, done, updates, hist, max_r, sstate = sub
        return BPResult(beliefs=_beliefs_single(elem, logm), logm=logm,
                        rounds=rounds, updates=updates, converged=done,
                        max_residual=max_r, unconverged_history=hist,
                        sched_state=sstate)

    def serve(self, stream: Sequence[PGM], rng: jax.Array, *,
              growth: float = 2.0, max_batch: int | None = None,
              chunk_rounds: int | None = None,
              evacuate: bool = True) -> ServeResult:
        """Serve a materialized request stream through rolling, evacuating
        buckets -- the synchronous compatibility wrapper over
        ``repro.core.serving`` (one resident bucket, no compaction, stream
        staged up front: the legacy cadence, chunk for chunk).

        Requests are grouped by bucket shape key and padded to their
        *group's* joint ceiling; each group runs as one resident batch of
        width ``min(max_batch, group size)``. After every chunk, converged
        (or round-exhausted) graphs are evacuated -- their results released
        immediately -- and their slots backfilled from the group's pending
        queue, so one straggler no longer holds a whole bucket's worth of
        finished work hostage. ``evacuate=False`` is the run-every-bucket-
        to-completion baseline (the PR-1 behavior) over the *same* padded
        groups, so its per-graph results and sweep accounting are exactly
        comparable.

        Per-graph RNG keys are ``fold_in(rng, input position)``, so results
        are independent of ``max_batch``/``evacuate`` and match ``run_many``
        whenever the padded shapes coincide (always true for same-shape
        groups). Caveat shared with ``run_many``: stochastic schedulers
        draw per-edge randomness over the *padded* edge axis, so policies
        that change a graph's padded shape (group ceiling here vs.
        per-sub-bucket max in ``run_many``) can legitimately alter
        RnBP/RBP trajectories -- the fixed point, not the answer quality.

        For online iterators, pipelined host/device overlap, bucket
        compaction, non-FIFO admission policies, and threaded ingestion,
        use ``repro.core.serving.serve_async`` (bitwise-equal per-request
        results on the same materialized stream). The config's
        ``admission`` policy applies here too (the default ``"fifo"``
        reproduces the historic cadence exactly).
        """
        from repro.core.serving import serve_async
        rep = serve_async(self, list(stream), rng, growth=growth,
                          max_batch=max_batch, chunk_rounds=chunk_rounds,
                          evacuate=evacuate, compact=False, slots=1,
                          prefetch=None)
        return ServeResult(rep.results, rep.stats)
