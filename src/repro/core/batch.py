"""Batched multi-graph BP engine: vmap-able PGM buckets + padded batches.

A single sparse PGM rarely saturates a many-core device; the serving
workload is *many independent* inference problems per device step. This
module provides the batching primitive every scaling layer builds on:

- ``BatchedPGM``: B same-shape graphs stacked leaf-wise. The element ``PGM``
  keeps bucket-ceiling *static* metadata (shared treedef / one compilation)
  while per-graph real sizes ride along as traced ``(B,)`` scalars, which the
  schedulers consume via ``traced_edge_count``/``traced_vertex_count``.
- ``bucket_pgms``: groups heterogeneous graphs into buckets keyed by
  power-of-two (edge, state) ceilings, bounding padding waste at ~2x per
  axis, then pads each graph to its bucket shape with ``pad_pgm``.
The batched *loop* lives in ``repro.core.engine`` (one gated
``lax.while_loop`` whose per-slice body reproduces the solo trajectory
exactly; the message update runs on the bucket's *disjoint union* --
``BatchedPGM.folded()`` offsets vertex/edge ids so B graphs become one
(B*E)-edge graph riding the unmodified single-graph update, Pallas kernel
included). ``run_bp_batch`` / ``run_bp_many`` remain here as deprecated
wrappers around ``BPEngine``.
"""

from __future__ import annotations

import dataclasses
import threading
import warnings
from collections import deque
from typing import (Any, Callable, Deque, Dict, List, Mapping, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import EDGE_PAD, PGM, VERTEX_PAD, pad_pgm_arrays
from repro.core.schedulers.base import Scheduler

__all__ = ["BatchedPGM", "Bucket", "RidgeEffort", "RoundsHistory",
           "batch_keys", "bucket_key", "bucket_pgms", "bucket_shape",
           "group_ceilings", "run_bp_batch", "run_bp_many"]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BatchedPGM:
    """B graphs padded to one (E, V, S) bucket shape, stacked leaf-wise.

    ``pgm`` is an element-``PGM`` whose array leaves carry a leading batch
    axis -- ``edge_src (B, E)``, ``log_psi_e (B, E, S, S)``, ... -- and whose
    static ints are the bucket ceilings. Slicing out ``graph(i)`` yields a
    standalone ``PGM`` that runs through plain ``run_bp`` and reproduces the
    batched trajectory of graph ``i`` exactly.
    """

    pgm: PGM

    @property
    def size(self) -> int:
        return self.pgm.edge_src.shape[0]

    @property
    def n_edges(self) -> int:
        return self.pgm.edge_src.shape[1]

    @property
    def n_vertices(self) -> int:
        return self.pgm.log_psi_v.shape[1]

    @property
    def n_states_max(self) -> int:
        return self.pgm.log_psi_v.shape[2]

    def graph(self, i: int) -> PGM:
        """Extract graph ``i`` as a standalone (bucket-padded) PGM."""
        return jax.tree.map(lambda x: x[i], self.pgm)

    def folded(self, mesh=None, *, axis: str = "bp") -> PGM:
        """The bucket as one disjoint-union PGM with B*E edges, B*V
        vertices: graph ``b``'s vertex ``u`` becomes ``b*V + u``. Message
        updates on the union are bitwise those of the member graphs (no
        cross edges; per-vertex segments keep their edge order), so the
        whole bucket rides the unmodified single-graph update path -- one
        segment-sum, one Pallas launch -- with the batch axis folded into
        the edge axis.

        With ``mesh`` given (a 1-D ``jax.sharding.Mesh`` whose axis is
        ``axis``), the folded (B*E,) edge grid is sharding-constrained over
        the mesh and the small vertex tables replicated, so XLA lays the
        union out shard-ready for the ``"sharded"`` update backend
        (``repro.dist``). Per-graph E is a multiple of EDGE_PAD and reverse
        pairs sit at adjacent even indices, so any even per-shard split of
        B*E keeps reverse lookups shard-local."""
        p = self.pgm
        b, e, v = self.size, self.n_edges, self.n_vertices
        off_v = (jnp.arange(b, dtype=jnp.int32) * v)[:, None]
        off_e = (jnp.arange(b, dtype=jnp.int32) * e)[:, None]
        union = PGM(
            edge_src=(p.edge_src + off_v).reshape(-1),
            edge_dst=(p.edge_dst + off_v).reshape(-1),
            edge_rev=(p.edge_rev + off_e).reshape(-1),
            edge_mask=p.edge_mask.reshape(-1),
            log_psi_e=p.log_psi_e.reshape(b * e, *p.log_psi_e.shape[2:]),
            log_psi_v=p.log_psi_v.reshape(b * v, -1),
            state_mask=p.state_mask.reshape(b * v, -1),
            n_states=p.n_states.reshape(-1),
            n_real_vertices=b * v, n_real_edges=b * e,
            edge_count=jnp.int32(b * e), vertex_count=jnp.int32(b * v))
        if mesh is None:
            return union
        from jax.sharding import NamedSharding, PartitionSpec as P
        wsc = jax.lax.with_sharding_constraint
        shard = lambda x, spec: wsc(x, NamedSharding(mesh, spec))
        edge, rep = P(axis), P(None, None)
        return dataclasses.replace(
            union,
            edge_src=shard(union.edge_src, edge),
            edge_dst=shard(union.edge_dst, edge),
            edge_rev=shard(union.edge_rev, edge),
            edge_mask=shard(union.edge_mask, edge),
            log_psi_e=shard(union.log_psi_e, P(axis, None, None)),
            log_psi_v=shard(union.log_psi_v, rep),
            state_mask=shard(union.state_mask, rep),
            n_states=shard(union.n_states, P(None)))

    def take(self, indices) -> "BatchedPGM":
        """Narrow the batch to the given slot ``indices`` (gather along the
        batch axis) -- the compaction primitive. Static ceilings (treedef)
        are preserved, so the kept graphs' padded shapes -- and hence their
        trajectories -- are untouched; only the batch width changes (one
        recompile per new width)."""
        ia = jnp.asarray(indices, dtype=jnp.int32)
        return BatchedPGM(pgm=jax.tree.map(lambda x: x[ia], self.pgm))

    @classmethod
    def from_pgms(cls, pgms: Sequence[PGM], *,
                  n_edges: int | None = None,
                  n_vertices: int | None = None,
                  n_states: int | None = None,
                  n_real_edges: int | None = None,
                  n_real_vertices: int | None = None) -> "BatchedPGM":
        """Pad ``pgms`` to their joint max (E, V, S) shape -- or the given
        explicit ceilings -- and stack.

        Explicit ceilings let a rolling batch (engine evacuation/backfill)
        reserve the *group-wide* shape and static-metadata ceilings up
        front, so any graph of the group can later be loaded into any slot
        without changing the treedef or retracing.

        Padding + stacking run in numpy (one device transfer per field at
        the end): a fresh mixed-shape stream would otherwise trigger one
        tiny XLA compilation per (pad op, shape) pair -- seconds of hidden
        warm-up before the engine ever runs.
        """
        assert len(pgms) > 0, "empty batch"
        e_b = n_edges or max(p.n_edges for p in pgms)
        v_b = n_vertices or max(p.n_vertices for p in pgms)
        s_b = n_states or max(p.n_states_max for p in pgms)
        padded = [pad_pgm_arrays(p, n_edges=e_b, n_vertices=v_b,
                                 n_states=s_b) for p in pgms]
        stacked = {k: jnp.asarray(np.stack([d[k] for d in padded]))
                   for k in padded[0]}
        return cls(pgm=PGM(
            n_real_vertices=(n_real_vertices
                             or max(p.n_real_vertices for p in pgms)),
            n_real_edges=(n_real_edges
                          or max(p.n_real_edges for p in pgms)), **stacked))


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One shape-homogeneous batch plus the input positions it came from."""
    indices: Tuple[int, ...]
    batch: BatchedPGM


def bucket_key(pgm: PGM, growth: float = 2.0) -> tuple:
    """Bucket shape key: (growth-factor ceiling of the padded edge count,
    pow2-ceil state count). Graphs sharing a key share a padded bucket shape
    -- and, for the evacuating server, a backfill pool."""
    import math
    if not growth > 1.0:
        raise ValueError(f"growth must be > 1 (got {growth}); use 2.0 for "
                         "pow2 buckets or math.inf for a single bucket")
    e = _round_up(max(pgm.n_real_edges, 1), EDGE_PAD)
    if math.isinf(growth):
        ekey = 0
    elif growth == 2.0:
        ekey = _pow2_ceil(e)
    else:
        ekey = math.ceil(math.log(e, growth) - 1e-9)
    return (ekey, _pow2_ceil(pgm.n_states_max))


def bucket_shape(pgm: PGM, growth: float = 2.0) -> tuple[int, int, int,
                                                         int, int]:
    """Per-request deterministic padded-shape ceilings for *online*
    bucketing: ``(n_edges, n_vertices, n_states, n_real_edges,
    n_real_vertices)``.

    Unlike ``group_ceilings`` (the materialized-stream policy: joint max
    over a known group), these depend only on the request itself -- the
    edge axis takes its ``growth``-factor ceiling (as ``bucket_key``), the
    vertex and state axes their pow2 ceilings -- so an online server can
    pad, stage, and batch a request the moment it arrives, and any two
    requests with equal ceilings share a bucket. The static real-count
    ceilings are set to the padded ceilings (a valid upper bound; note
    size-derived scheduler constants like RBP's ``k = p * n_real_edges``
    then scale with the bucket, not the graph -- the same class of caveat
    as any re-padding). Requires finite ``growth``: ``inf`` has no
    per-request shape."""
    import math
    if not growth > 1.0 or math.isinf(growth):
        raise ValueError("online bucketing needs finite growth > 1, got "
                         f"{growth}")
    e = max(_round_up(max(pgm.n_real_edges, 1), EDGE_PAD), pgm.n_edges)
    if growth == 2.0:
        e_c = _pow2_ceil(e)
    else:
        k = math.ceil(math.log(e, growth) - 1e-9)
        e_c = max(_round_up(int(math.ceil(growth ** k)), EDGE_PAD), e)
    v_c = _pow2_ceil(max(_round_up(pgm.n_real_vertices + 1, VERTEX_PAD),
                         pgm.n_vertices))
    s_c = _pow2_ceil(pgm.n_states_max)
    return (e_c, v_c, s_c, e_c, v_c)


def group_ceilings(pgms: Sequence[PGM]) -> tuple[int, int, int, int, int]:
    """Joint padded-shape and static-metadata ceilings over a graph group:
    ``(n_edges, n_vertices, n_states, n_real_edges, n_real_vertices)``."""
    return (max(p.n_edges for p in pgms),
            max(p.n_vertices for p in pgms),
            max(p.n_states_max for p in pgms),
            max(p.n_real_edges for p in pgms),
            max(p.n_real_vertices for p in pgms))


def bucket_pgms(pgms: Sequence[PGM], *,
                growth: float = 2.0,
                max_batch: int | None = None) -> List[Bucket]:
    """Group heterogeneous graphs into padded, shape-homogeneous buckets.

    Bucket key = (growth-factor ceiling of the padded edge count, pow2-ceil
    state count): within a bucket no graph pays more than ~``growth``x
    padding on the edge axis (the dominant cost, ``log_psi_e`` is E*S^2) or
    ~2x on the state axis. The vertex axis simply takes the bucket max --
    V <= E for connected graphs, so it never dominates.

    ``growth`` is the compile-vs-compute policy knob: 2.0 (default) bounds
    padding waste at 2x per graph and suits steady-state traffic over few
    shape families; large values (or ``inf`` for one bucket) collapse a
    shape-diverse stream into few XLA compilations -- the dominant cost when
    serving cold traffic whose request shapes are effectively unbounded.
    ``max_batch`` caps graphs per bucket (VMEM/HBM guard).
    """
    keyed: dict[tuple, List[int]] = {}
    for i, p in enumerate(pgms):
        keyed.setdefault(bucket_key(p, growth), []).append(i)
    buckets = []
    for key in sorted(keyed):
        idx = keyed[key]
        chunks = ([idx] if not max_batch else
                  [idx[i:i + max_batch] for i in range(0, len(idx), max_batch)])
        for chunk in chunks:
            batch = BatchedPGM.from_pgms([pgms[i] for i in chunk])
            buckets.append(Bucket(indices=tuple(chunk), batch=batch))
    return buckets


class RidgeEffort:
    """Tiny incrementally-fit ridge regression predicting rounds-to-converge.

    The learned half of effort calibration: each completed request
    contributes one ``(features, rounds)`` observation via normal-equation
    accumulators (``A^T A`` / ``A^T y``, O(d^2) per fit, d = ``DIM``), and
    ``predict`` solves the l2-regularized system lazily. Features come from
    :meth:`features`: a bias, the admission score (residual-at-admit), the
    log-scaled edge/state ceilings mined from the kind tuple, and up to two
    caller-supplied extras (the deadline policy passes coupling-strength
    stats). Because size enters as a *feature* rather than a table key, one
    global model generalizes across kinds -- an unseen bucket shape gets a
    prediction from the first observation of any other shape, which the
    nearest-neighbor table it replaces never could.

    ``to_dict``/``from_dict`` round-trip the accumulators exactly (JSON-safe
    nested lists), so a warm effort model can ship with a deployment spec.
    Not internally locked: :class:`RoundsHistory` serializes access."""

    #: feature dimension: [1, score, log1p(edges), log1p(states), extra0,
    #: extra1]
    DIM = 6

    def __init__(self, l2: float = 1.0):
        if l2 <= 0:
            raise ValueError(f"l2 must be > 0, got {l2}")
        self.l2 = float(l2)
        self._ata = np.zeros((self.DIM, self.DIM), dtype=np.float64)
        self._aty = np.zeros(self.DIM, dtype=np.float64)
        self._n = 0
        self._w: np.ndarray | None = None

    @staticmethod
    def features(kind, score: float,
                 extra: Sequence[float] = ()) -> np.ndarray:
        """The fixed-width feature vector for one request: ``[1, score,
        log1p(edge ceiling), log1p(state ceiling), extra...]``, zero-padded
        to ``DIM``. Numeric leaves are mined from the (possibly nested)
        ``kind`` tuple -- serving kinds are ``bucket_shape`` ceilings
        ``(E, V, S, rE, rV)``, router kinds wrap them in ``("routed", ...)``
        -- with non-numeric leaves skipped, so any hashable kind works."""
        nums: List[float] = []

        def walk(x):
            if isinstance(x, bool):
                return
            if isinstance(x, (int, float, np.integer, np.floating)):
                nums.append(float(x))
            elif isinstance(x, (tuple, list)):
                for y in x:
                    walk(y)

        walk(kind)
        f = [1.0, float(score)]
        f += [float(np.log1p(abs(nums[i]))) for i in (0, 2)
              if i < len(nums)]                    # edge / state ceilings
        f += [float(v) for v in list(extra)[:RidgeEffort.DIM - len(f)]]
        f += [0.0] * (RidgeEffort.DIM - len(f))
        return np.asarray(f[:RidgeEffort.DIM], dtype=np.float64)

    @property
    def n_observations(self) -> int:
        """Observations fitted so far."""
        return self._n

    def fit_one(self, x: np.ndarray, y: float) -> None:
        """Accumulate one observation (features ``x``, observed rounds
        ``y``) into the normal equations; invalidates the cached solve."""
        x = np.asarray(x, dtype=np.float64)
        self._ata += np.outer(x, x)
        self._aty += float(y) * x
        self._n += 1
        self._w = None

    def predict(self, x: np.ndarray) -> float | None:
        """Predicted rounds for features ``x`` (clipped at 0; ``None``
        until at least two observations were fitted -- one point cannot
        anchor a slope)."""
        if self._n < 2:
            return None
        if self._w is None:
            self._w = np.linalg.solve(
                self._ata + self.l2 * np.eye(self.DIM), self._aty)
        return max(float(np.dot(x, self._w)), 0.0)

    def to_dict(self) -> dict:
        """JSON-ready accumulator state (exact round-trip)."""
        return {"l2": self.l2, "n": self._n,
                "ata": self._ata.tolist(), "aty": self._aty.tolist()}

    @classmethod
    def from_dict(cls, d: Mapping) -> "RidgeEffort":
        """Rebuild a model from :meth:`to_dict` output."""
        m = cls(l2=float(d["l2"]))
        m._n = int(d["n"])
        m._ata = np.asarray(d["ata"], dtype=np.float64)
        m._aty = np.asarray(d["aty"], dtype=np.float64)
        return m


class RoundsHistory:
    """Bounded, thread-safe effort calibration: per-kind observations plus
    (by default) a learned :class:`RidgeEffort` predictor over them.

    A *kind* is any hashable key naming a family of similar requests -- the
    serving layer uses the bucket-shape ceilings (``bucket_shape`` /
    ``group_ceilings`` tuples), so graphs that share a padded shape share a
    history. ``observe(kind, score, rounds)`` records one finished request's
    (admission score, rounds actually run); ``expect(kind, score)`` predicts
    the rounds a new request will need; ``mean(kind)`` is the score-free
    aggregate the router tier uses for effort-in-flight load estimates.

    ``predictor`` picks the expectation model: ``"ridge"`` (default) fits
    one incremental :class:`RidgeEffort` regression over (score, size, extra)
    features of *every* observation -- cross-kind generalization, so unseen
    shapes stop cold-starting -- while ``"nearest"`` is the original
    per-kind nearest-recorded-score lookup. Both fall back, in order, to
    the kind's nearest observation, the constructor ``prior`` (the
    prior-seeding knob: a deployment's known typical rounds), and finally
    the caller's ``default=`` -- so callers no longer need a ``None``
    branch. ``capacity`` bounds observations kept per kind (a deque, so
    drifting workloads age out), keeping host memory O(kinds) on
    indefinitely long streams.

    This is the feedback half of Residual-BP-style admission
    (``repro.core.serving.ResidualAdmission`` and the ``deadline`` policy's
    slack prediction): the cheap residual-at-admit proxy orders requests,
    and this history calibrates that proxy into expected effort from what
    actually happened to similar requests.

    All methods lock, so one instance may be shared across serving threads
    -- ``repro.serve`` hands every replica the same history, pooling effort
    calibration instead of cold-starting it per replica."""

    def __init__(self, capacity: int = 64, *, predictor: str = "ridge",
                 prior: float | None = None, l2: float = 1.0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if predictor not in ("ridge", "nearest"):
            raise ValueError(
                f"predictor must be 'ridge' or 'nearest', got {predictor!r}")
        self.capacity = capacity
        self.predictor = predictor
        self.prior = None if prior is None else float(prior)
        self._model = RidgeEffort(l2=l2) if predictor == "ridge" else None
        self._hist: Dict[Any, Deque[Tuple[float, float]]] = {}
        self._lock = threading.Lock()

    def observe(self, kind, score: float, rounds: float,
                extra: Sequence[float] = ()) -> None:
        """Record one completed request of ``kind``: its admission score,
        the rounds it actually ran before release, and optional extra
        feature values (coupling stats) for the learned predictor."""
        with self._lock:
            dq = self._hist.get(kind)
            if dq is None:
                dq = self._hist[kind] = deque(maxlen=self.capacity)
            dq.append((float(score), float(rounds)))
            if self._model is not None:
                self._model.fit_one(
                    RidgeEffort.features(kind, score, extra), rounds)

    def _nearest(self, kind, score: float) -> float | None:
        dq = self._hist.get(kind)
        if not dq:
            return None
        return min(dq, key=lambda sr: abs(sr[0] - float(score)))[1]

    def expect(self, kind, score: float, *, default: float | None = None,
               extra: Sequence[float] = ()) -> float | None:
        """Expected rounds for a new request of ``kind`` with admission
        ``score``: the ridge prediction when the model has data (any kind's
        data -- size is a feature), else the kind's nearest recorded score,
        else the seeded ``prior``, else ``default``. Callers that always
        need a number pass ``default=`` instead of branching on ``None``."""
        with self._lock:
            if self._model is not None:
                est = self._model.predict(
                    RidgeEffort.features(kind, score, extra))
                if est is not None:
                    return est
            est = self._nearest(kind, score)
            if est is not None:
                return est
            return self.prior if self.prior is not None else default

    def mean(self, kind=None, *, default: float | None = None
             ) -> float | None:
        """Mean observed rounds across every record of ``kind`` -- the
        score-free effort estimate for callers with no admission score at
        hand (request routing). An unseen kind falls back to the global
        mean over *all* kinds (``kind=None`` asks for that directly), then
        the seeded ``prior``, then ``default``."""
        with self._lock:
            if kind is not None:
                dq = self._hist.get(kind)
                if dq:
                    return sum(r for _, r in dq) / len(dq)
            total = n = 0.0
            for dq in self._hist.values():
                total += sum(r for _, r in dq)
                n += len(dq)
            if n:
                return total / n
            return self.prior if self.prior is not None else default

    def to_dict(self) -> dict:
        """JSON-ready snapshot: config, per-kind observations (kinds keyed
        by ``repr``), and the ridge accumulators. Round-trips through
        :meth:`from_dict` to a history with identical predictions."""
        with self._lock:
            return {
                "capacity": self.capacity, "predictor": self.predictor,
                "prior": self.prior,
                "model": None if self._model is None
                else self._model.to_dict(),
                "hist": [[repr(k), [list(sr) for sr in dq]]
                         for k, dq in self._hist.items()],
            }

    @classmethod
    def from_dict(cls, d: Mapping) -> "RoundsHistory":
        """Rebuild a history from :meth:`to_dict` output. Kind keys were
        serialized by ``repr`` and are restored via ``ast.literal_eval``
        (serving kinds are literal tuples); non-literal kinds keep their
        repr string as the key -- predictions still work, size features
        simply read as absent."""
        import ast
        h = cls(capacity=int(d["capacity"]), predictor=d["predictor"],
                prior=d.get("prior"))
        if d.get("model") is not None:
            h._model = RidgeEffort.from_dict(d["model"])
        for krepr, obs in d.get("hist", ()):
            try:
                kind = ast.literal_eval(krepr)
            except (ValueError, SyntaxError):
                kind = krepr
            dq = deque(maxlen=h.capacity)
            dq.extend((float(s), float(r)) for s, r in obs)
            h._hist[kind] = dq
        return h

    def __len__(self) -> int:
        with self._lock:
            return sum(len(dq) for dq in self._hist.values())


def batch_keys(rng: jax.Array, batch: BatchedPGM | int) -> jax.Array:
    """(B,) per-graph RNG keys from one base key (or pass-through if the
    caller already supplies a (B,) key array)."""
    b = batch if isinstance(batch, int) else batch.size
    if rng.ndim == 1 and rng.shape[0] == b and jnp.issubdtype(
            rng.dtype, jax.dtypes.prng_key):
        return rng
    return jax.random.split(rng, b)


def _deprecated(name: str) -> None:
    warnings.warn(
        f"{name} is deprecated: use repro.core.BPEngine with a BPConfig "
        "(config-driven scheduler/backend, chunked resume, evacuation)",
        DeprecationWarning, stacklevel=3)


def run_bp_batch(batch: BatchedPGM,
                 scheduler: Scheduler,
                 rng: jax.Array,
                 *,
                 eps: float = 1e-3,
                 max_rounds: int = 2000,
                 damping: float = 0.0,
                 update_fn: Callable | None = None,
                 track_history: bool = False):
    """Deprecated wrapper: ``BPEngine(BPConfig(...)).run(batch, rng)``.

    Exact-trajectory parity with the historic one-``while_loop``
    implementation (the engine runs the same gated body); returns a
    ``BPResult`` whose every field carries a leading batch axis, each slice
    equal to the graph's solo ``run_bp`` trajectory.
    """
    from repro.core.engine import BPConfig, BPEngine
    _deprecated("run_bp_batch")
    cfg = BPConfig(scheduler=scheduler, eps=eps, max_rounds=max_rounds,
                   damping=damping,
                   backend=update_fn if update_fn is not None else "ref",
                   history=track_history)
    return BPEngine(cfg).run(batch, rng)


def run_bp_many(pgms: Sequence[PGM],
                scheduler: Scheduler,
                rng: jax.Array,
                *,
                growth: float = 2.0,
                max_batch: int | None = None,
                **bp_kwargs: Any):
    """Deprecated wrapper: ``BPEngine(BPConfig(...)).run_many(pgms, rng)``
    (or ``.serve(...)`` for the evacuating path). Per-graph keys are
    ``fold_in(rng, input position)``, independent of bucketing."""
    from repro.core.engine import BPConfig, BPEngine
    _deprecated("run_bp_many")
    cfg = BPConfig(scheduler=scheduler,
                   eps=bp_kwargs.pop("eps", 1e-3),
                   max_rounds=bp_kwargs.pop("max_rounds", 2000),
                   damping=bp_kwargs.pop("damping", 0.0),
                   backend=bp_kwargs.pop("update_fn", None) or "ref",
                   history=bp_kwargs.pop("track_history", False))
    if bp_kwargs:
        raise TypeError(f"unknown arguments: {sorted(bp_kwargs)}")
    return BPEngine(cfg).run_many(pgms, rng, growth=growth,
                                  max_batch=max_batch)
