"""Pairwise discrete MRF representation for many-core Belief Propagation.

The paper (Van der Merwe et al., 2019) stores the PGM as an adjacency list
with per-edge/vertex IDs assigned to CUDA threads. The TPU/XLA analogue is a
*static-shape, padded, structure-of-arrays* layout:

- every undirected edge {i, j} becomes two *directed* edges (i->j), (j->i);
  message ``m[e]`` lives on directed edge ``e``,
- ``edge_rev[e]`` gives the index of the opposing directed edge (needed to
  exclude ``m_{j->i}`` when computing ``m_{i->j}``),
- vertices may have heterogeneous state counts (protein-folding graphs range
  2..81); everything is padded to ``n_states`` with masked ``-NEG_INF``
  potentials,
- edge and vertex arrays are padded to lane-friendly multiples so the Pallas
  kernel can put the edge dimension on the 128-wide lane axis.

All arrays are plain ``jnp`` arrays registered as a pytree so a ``PGM`` can be
passed through ``jax.jit`` / ``shard_map`` unchanged.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial, wraps
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Large-negative stand-in for log(0). Chosen so that summing ~1e2 of them in
# float32 stays far from -inf/NaN territory while exp() underflows to exactly 0.
NEG_INF = -1.0e30

# Edge-count padding multiple. 128 = TPU lane width; the Pallas message kernel
# tiles edges along lanes.
EDGE_PAD = 128
VERTEX_PAD = 8


#: ``jax.monitoring`` event: seconds each ``build_pgm``/``build_pgm_uniform``
#: call took (host work and the dispatch of its device copies).
BUILD_EVENT = "/repro/pgm/build_duration"

#: ``jax.monitoring`` scalar: each build's in-edge table width D (the
#: largest real in-degree), or 0 where the builder left the table out.
IN_EDGE_WIDTH = "/repro/pgm/in_edge_width"

#: The builders keep a (V, D) in-edge table only where it is compact:
#: V * D <= IN_EDGE_SLACK * (real directed edges). On TPU v5e a scatter-add
#: costs about 9 ns per edge and a gather about 1.3-1.4 ns per row (Ising
#: 200x200 round loop), so summing by D gathers of V rows wins while the
#: table holds up to about 6x the real edges; the factor 2 keeps well inside
#: that. Grids, chains and regular LDPC codes pass; hub-heavy graphs (stars,
#: skewed contact maps) keep the scatter-add.
IN_EDGE_SLACK = 2

#: Unused in-edge table slots: out of range for any edge axis, so the
#: fill-mode gather reads them as 0.
IN_EDGE_FILL = np.iinfo(np.int32).max


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _records_build(build):
    """Record each call's duration as ``BUILD_EVENT``."""
    @wraps(build)
    def timed(*args, **kwargs):
        t0 = time.perf_counter()
        pgm = build(*args, **kwargs)
        jax.monitoring.record_event_duration_secs(
            BUILD_EVENT, time.perf_counter() - t0)
        return pgm
    return timed


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PGM:
    """Padded, directed-edge MRF.

    Shapes (E = padded directed-edge count, V = padded vertex count + 1 dummy,
    S = padded state count):
      edge_src, edge_dst, edge_rev : (E,)  int32
      edge_mask                    : (E,)  bool    True for real edges
      log_psi_e                    : (E, S, S) f32  [x_src, x_dst]
      log_psi_v                    : (V, S) f32     NEG_INF at invalid states
      state_mask                   : (V, S) bool
      n_states                     : (V,)  int32
      in_edges                     : (V, D) int32 or None. Row v lists the
          real edges into v in ascending edge id, then ``IN_EDGE_FILL``;
          D is the largest real in-degree. Only the builders fill it, and
          only where it is compact (``IN_EDGE_SLACK``); every re-padded,
          stacked, folded or sharded PGM carries None and sums by
          ``segment_sum``.
      src_mask                     : (E, S) bool or None:
          ``state_mask[edge_src]``, the source states each edge's message
          is computed over. The engine's round loops fill it once per
          chunk (``messages.with_src_mask``); where it is None the prelude
          gathers it each call.
    """

    edge_src: jax.Array
    edge_dst: jax.Array
    edge_rev: jax.Array
    edge_mask: jax.Array
    log_psi_e: jax.Array
    log_psi_v: jax.Array
    state_mask: jax.Array
    n_states: jax.Array
    # Static metadata (ints, not traced). Under batching these hold the
    # *bucket ceiling* (max real count over the batch) so every graph in a
    # bucket shares one treedef; the traced per-graph counts live below.
    n_real_vertices: int = dataclasses.field(metadata=dict(static=True))
    n_real_edges: int = dataclasses.field(metadata=dict(static=True))  # directed
    # Traced real counts, () int32. Schedulers must size frontiers from these
    # (via ``traced_edge_count``/``traced_vertex_count``) so the same trace
    # serves every graph of a vmapped bucket. ``None`` falls back to the
    # static ints for hand-built PGMs.
    edge_count: jax.Array | None = None
    vertex_count: jax.Array | None = None
    in_edges: jax.Array | None = None
    src_mask: jax.Array | None = None

    @property
    def n_edges(self) -> int:
        """Padded directed edge count."""
        return self.edge_src.shape[0]

    @property
    def n_vertices(self) -> int:
        """Padded vertex count (includes 1 dummy sink vertex)."""
        return self.log_psi_v.shape[0]

    @property
    def n_states_max(self) -> int:
        return self.log_psi_v.shape[1]

    def traced_edge_count(self) -> jax.Array:
        """() int32 real directed-edge count, traced (batch-safe)."""
        if self.edge_count is None:
            return jnp.int32(self.n_real_edges)
        return self.edge_count

    def traced_vertex_count(self) -> jax.Array:
        """() int32 real vertex count, traced (batch-safe)."""
        if self.vertex_count is None:
            return jnp.int32(self.n_real_vertices)
        return self.vertex_count

    def degree(self) -> jax.Array:
        """In-degree per vertex (== out-degree; graph is symmetric)."""
        return jax.ops.segment_sum(
            self.edge_mask.astype(jnp.int32), self.edge_dst,
            num_segments=self.n_vertices)


def in_edge_table(edge_dst: np.ndarray, edge_mask: np.ndarray,
                  n_vertices: int) -> jax.Array | None:
    """(V, D) int32 table of each vertex's real in-edges, ascending, padded
    with ``IN_EDGE_FILL``; None where it is not compact
    (V * D > IN_EDGE_SLACK * real edges). Records ``IN_EDGE_WIDTH``."""
    real = np.flatnonzero(edge_mask)
    dst = edge_dst[real]
    order = np.argsort(dst, kind="stable")
    real, dst = real[order], dst[order]
    counts = np.bincount(dst, minlength=n_vertices)
    width = int(counts.max(initial=0))
    if width == 0 or n_vertices * width > IN_EDGE_SLACK * real.size:
        jax.monitoring.record_scalar(IN_EDGE_WIDTH, 0)
        return None
    first = np.cumsum(counts) - counts           # first sorted slot per vertex
    table = np.full((n_vertices, width), IN_EDGE_FILL, dtype=np.int32)
    table[dst, np.arange(real.size) - first[dst]] = real
    jax.monitoring.record_scalar(IN_EDGE_WIDTH, width)
    return jnp.asarray(table)


@_records_build
def build_pgm_uniform(
    n_vertices: int,
    edges: np.ndarray,          # (E_und, 2)
    unary: np.ndarray,          # (V, S) linear-space
    pairwise: np.ndarray,       # (E_und, S, S) linear-space
    *,
    edge_pad: int = EDGE_PAD,
    dtype=jnp.float32,
) -> PGM:
    """Vectorized builder for uniform state-count graphs (Ising/chain at any
    scale -- the python-loop path in ``build_pgm`` is O(E) interpreter time).
    """
    edges = np.asarray(edges, dtype=np.int64)
    e_und = edges.shape[0]
    e_dir = 2 * e_und
    s = unary.shape[1]
    e_pad = _round_up(max(e_dir, 1), edge_pad)
    v_pad = _round_up(n_vertices + 1, VERTEX_PAD)
    dummy = n_vertices

    edge_src = np.full((e_pad,), dummy, dtype=np.int32)
    edge_dst = np.full((e_pad,), dummy, dtype=np.int32)
    edge_rev = np.arange(e_pad, dtype=np.int32)
    edge_mask = np.zeros((e_pad,), dtype=bool)
    log_psi_e = np.zeros((e_pad, s, s), dtype=np.float32)
    log_psi_v = np.full((v_pad, s), NEG_INF, dtype=np.float32)
    state_mask = np.zeros((v_pad, s), dtype=bool)
    n_states = np.full((v_pad,), 1, dtype=np.int32)

    fwd = np.arange(0, e_dir, 2)
    bwd = fwd + 1
    edge_src[fwd], edge_dst[fwd] = edges[:, 0], edges[:, 1]
    edge_src[bwd], edge_dst[bwd] = edges[:, 1], edges[:, 0]
    edge_rev[fwd], edge_rev[bwd] = bwd, fwd
    edge_mask[:e_dir] = True
    lp = np.log(pairwise.astype(np.float64)).astype(np.float32)
    log_psi_e[fwd] = lp
    log_psi_e[bwd] = np.swapaxes(lp, 1, 2)
    log_psi_v[:n_vertices] = np.log(unary.astype(np.float64))
    state_mask[:n_vertices] = True
    n_states[:n_vertices] = s
    log_psi_v[dummy:, 0] = 0.0
    state_mask[dummy:, 0] = True

    return PGM(
        edge_src=jnp.asarray(edge_src), edge_dst=jnp.asarray(edge_dst),
        edge_rev=jnp.asarray(edge_rev), edge_mask=jnp.asarray(edge_mask),
        log_psi_e=jnp.asarray(log_psi_e, dtype=dtype),
        log_psi_v=jnp.asarray(log_psi_v, dtype=dtype),
        state_mask=jnp.asarray(state_mask), n_states=jnp.asarray(n_states),
        n_real_vertices=n_vertices, n_real_edges=e_dir,
        edge_count=jnp.int32(e_dir), vertex_count=jnp.int32(n_vertices),
        in_edges=in_edge_table(edge_dst, edge_mask, v_pad))


@_records_build
def build_pgm(
    n_vertices: int,
    edges: np.ndarray,              # (E_und, 2) int, undirected vertex pairs
    unary: Sequence[np.ndarray],    # per-vertex (S_i,) potentials, linear space
    pairwise: Sequence[np.ndarray],  # per-undirected-edge (S_i, S_j), linear
    *,
    edge_pad: int = EDGE_PAD,
    state_pad_to: int | None = None,
    dtype=jnp.float32,
) -> PGM:
    """Build a padded PGM from host-side numpy potentials (linear space).

    Potentials must be strictly positive (MRF definition, psi: -> R+).
    """
    edges = np.asarray(edges, dtype=np.int64)
    assert edges.ndim == 2 and edges.shape[1] == 2
    e_und = edges.shape[0]
    e_dir = 2 * e_und

    n_states_arr = np.array([len(u) for u in unary], dtype=np.int32)
    s_max = int(n_states_arr.max()) if len(unary) else 1
    if state_pad_to is not None:
        s_max = max(s_max, state_pad_to)

    e_pad = _round_up(max(e_dir, 1), edge_pad)
    v_pad = _round_up(n_vertices + 1, VERTEX_PAD)  # +1 dummy sink vertex
    dummy = n_vertices  # padded edges point at the dummy vertex

    edge_src = np.full((e_pad,), dummy, dtype=np.int32)
    edge_dst = np.full((e_pad,), dummy, dtype=np.int32)
    edge_rev = np.arange(e_pad, dtype=np.int32)  # padded edges self-reverse
    edge_mask = np.zeros((e_pad,), dtype=bool)
    log_psi_e = np.zeros((e_pad, s_max, s_max), dtype=np.float32)
    log_psi_v = np.full((v_pad, s_max), NEG_INF, dtype=np.float32)
    state_mask = np.zeros((v_pad, s_max), dtype=bool)
    n_states = np.ones((v_pad,), dtype=np.int32)

    for v in range(n_vertices):
        s = int(n_states_arr[v])
        u = np.asarray(unary[v], dtype=np.float64)
        assert u.shape == (s,) and np.all(u > 0), f"bad unary at vertex {v}"
        log_psi_v[v, :s] = np.log(u)
        state_mask[v, :s] = True
        n_states[v] = s
    # Dummy vertex: single valid state with psi=1 so padded edges stay inert.
    log_psi_v[dummy:, 0] = 0.0
    state_mask[dummy:, 0] = True

    for k in range(e_und):
        i, j = int(edges[k, 0]), int(edges[k, 1])
        si, sj = int(n_states_arr[i]), int(n_states_arr[j])
        p = np.asarray(pairwise[k], dtype=np.float64)
        assert p.shape == (si, sj) and np.all(p > 0), f"bad pairwise at edge {k}"
        fwd, bwd = 2 * k, 2 * k + 1
        edge_src[fwd], edge_dst[fwd] = i, j
        edge_src[bwd], edge_dst[bwd] = j, i
        edge_rev[fwd], edge_rev[bwd] = bwd, fwd
        edge_mask[fwd] = edge_mask[bwd] = True
        lp = np.log(p)
        log_psi_e[fwd, :si, :sj] = lp
        log_psi_e[bwd, :sj, :si] = lp.T

    return PGM(
        edge_src=jnp.asarray(edge_src),
        edge_dst=jnp.asarray(edge_dst),
        edge_rev=jnp.asarray(edge_rev),
        edge_mask=jnp.asarray(edge_mask),
        log_psi_e=jnp.asarray(log_psi_e, dtype=dtype),
        log_psi_v=jnp.asarray(log_psi_v, dtype=dtype),
        state_mask=jnp.asarray(state_mask),
        n_states=jnp.asarray(n_states),
        n_real_vertices=n_vertices,
        n_real_edges=e_dir,
        edge_count=jnp.int32(e_dir),
        vertex_count=jnp.int32(n_vertices),
        in_edges=in_edge_table(edge_dst, edge_mask, v_pad),
    )


def pad_pgm_arrays(pgm: PGM, *, n_edges: int, n_vertices: int,
                   n_states: int) -> dict:
    """Host-side (numpy) re-padding of a PGM's arrays to larger shapes.

    Deliberately numpy: bucketing pads many graphs of *distinct* shapes, and
    doing it in jnp costs one tiny XLA compilation per (op, shape) pair --
    seconds of hidden warm-up per fresh request stream. Returns a field
    dict; ``pad_pgm``/``BatchedPGM.from_pgms`` convert to device arrays
    once at the end.
    """
    e0, v0, s0 = pgm.n_edges, pgm.n_vertices, pgm.n_states_max
    assert n_edges >= e0 and n_vertices >= v0 and n_states >= s0, \
        f"cannot shrink ({e0},{v0},{s0}) -> ({n_edges},{n_vertices},{n_states})"
    de, dv, ds = n_edges - e0, n_vertices - v0, n_states - s0
    dummy = pgm.n_real_vertices

    log_psi_v = np.pad(np.asarray(pgm.log_psi_v), ((0, dv), (0, ds)),
                       constant_values=NEG_INF)
    state_mask = np.pad(np.asarray(pgm.state_mask), ((0, dv), (0, ds)))
    if dv:
        # new padding vertices: one valid zero-potential state (like dummy)
        log_psi_v[v0:, 0] = 0.0
        state_mask[v0:, 0] = True
    return dict(
        edge_src=np.pad(np.asarray(pgm.edge_src), (0, de),
                        constant_values=dummy),
        edge_dst=np.pad(np.asarray(pgm.edge_dst), (0, de),
                        constant_values=dummy),
        edge_rev=np.concatenate([np.asarray(pgm.edge_rev),
                                 np.arange(e0, n_edges, dtype=np.int32)]),
        edge_mask=np.pad(np.asarray(pgm.edge_mask), (0, de)),
        log_psi_e=np.pad(np.asarray(pgm.log_psi_e),
                         ((0, de), (0, ds), (0, ds))),
        log_psi_v=log_psi_v,
        state_mask=state_mask,
        n_states=np.pad(np.asarray(pgm.n_states), (0, dv),
                        constant_values=1),
        edge_count=np.int32(pgm.n_real_edges),
        vertex_count=np.int32(pgm.n_real_vertices),
    )


def pad_pgm(pgm: PGM, *, n_edges: int, n_vertices: int, n_states: int,
            n_real_edges: int | None = None,
            n_real_vertices: int | None = None) -> PGM:
    """Re-pad a PGM to larger shared shapes (the bucketing primitive).

    Extra edges point at the graph's own dummy vertex with ``edge_mask``
    False; extra vertices get a single valid zero-potential state; extra
    state columns are masked out -- all inert by the same conventions the
    builders use, so BP on the padded graph commits the same messages on
    real edges. The optional ``n_real_*`` override the *static* metadata to
    a bucket ceiling (shared treedef across a batch); the traced per-graph
    counts are preserved. The result has no in-edge table (``in_edges`` is
    None): its sums run by ``segment_sum``.
    """
    arrs = pad_pgm_arrays(pgm, n_edges=n_edges, n_vertices=n_vertices,
                          n_states=n_states)
    return PGM(
        n_real_vertices=(pgm.n_real_vertices if n_real_vertices is None
                         else n_real_vertices),
        n_real_edges=(pgm.n_real_edges if n_real_edges is None
                      else n_real_edges),
        **{k: jnp.asarray(v) for k, v in arrs.items()},
    )
