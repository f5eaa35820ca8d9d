"""The benchmark's one door into the system under test.

Everything the benchmark takes from the program passes through here: the
engine built from a configuration's ``engine`` block and the program's
graph builders. The yardstick (generators, reference, trace reduction,
work counts, peaks) imports none of it.
"""

from __future__ import annotations

import numpy as np

from bench.reference import Graph


def engine(cfg: dict):
    """``BPEngine`` for the configuration's ``engine`` block."""
    from repro.core import BPConfig, BPEngine

    e = cfg["engine"]
    return BPEngine(BPConfig(
        scheduler=e["scheduler"], scheduler_kwargs=e.get("scheduler_kwargs", {}),
        eps=float(e["eps"]), max_rounds=int(e["max_rounds"]),
        backend=e["backend"], chunk_rounds=e.get("chunk_rounds")))


def table_dtype(cfg: dict, control: bool):
    """The tables' dtype: the configuration's, or for the control run the
    program's own lower-precision tables (bfloat16)."""
    import jax.numpy as jnp

    if control:
        return jnp.bfloat16
    return jnp.dtype(cfg["engine"]["dtype"])


def pgm(g: Graph, dtype):
    """The program's graph for ``g``, built by its own builders."""
    from repro.core.graph import build_pgm, build_pgm_uniform

    pair = np.exp(g.log_pair)
    if np.all(g.n_states == g.n_states[0]):
        return build_pgm_uniform(g.n_vertices, g.edges,
                                 np.exp(g.log_unary), pair, dtype=dtype)
    s = g.n_states
    return build_pgm(
        g.n_vertices, g.edges,
        [np.exp(u[:k]) for u, k in zip(g.log_unary, s)],
        [p[:s[a], :s[b]] for p, (a, b) in zip(pair, g.edges)],
        dtype=dtype)


def messages(logm, g: Graph) -> np.ndarray:
    """The program's (padded) messages on ``g``'s directed edges, float64,
    with -inf at states the destination does not have."""
    from bench.reference import directed

    _, dst, _ = directed(g)
    s = g.log_unary.shape[1]
    m = np.asarray(logm, np.float64)[:g.n_directed, :s]
    return np.where(np.isfinite(g.log_unary[dst]), m, -np.inf)


def beliefs(b, g: Graph) -> np.ndarray:
    """The program's (padded) beliefs on ``g``'s vertices, float64."""
    s = g.log_unary.shape[1]
    out = np.asarray(b, np.float64)[:g.n_vertices, :s]
    return np.where(np.isfinite(g.log_unary), out, -np.inf)


def check_layout(p, g: Graph) -> None:
    """Raise unless the program's directed edges are ``g``'s, in the order
    the reference uses (edge 2k = ``edges[k]``, 2k+1 its reverse), with
    padding only after them. The answer's messages are read in that order."""
    from bench.reference import directed

    src, dst, _ = directed(g)
    n = g.n_directed
    ok = (np.array_equal(np.asarray(p.edge_src)[:n], src)
          and np.array_equal(np.asarray(p.edge_dst)[:n], dst)
          and bool(np.all(np.asarray(p.edge_mask)[:n]))
          and not np.any(np.asarray(p.edge_mask)[n:]))
    if not ok:
        raise RuntimeError("the program's edge layout is not the graph's: "
                           "its messages cannot be read")
