"""Jit'd public wrappers around the Pallas message-update kernel.

``pallas_update(pgm, logm)`` is a drop-in replacement for
``repro.core.messages.ref_update`` (same (E, S) layout at the boundary); it
handles the transpose to kernel layout and edge padding to the block size.
On the CPU it runs the Pallas interpreter; on a TPU it compiles the kernel.

``semiring="max"`` runs the kernel's max-product body instead (MAP, a
``max_product_update`` equivalent); it is registered as ``"pallas_max"``,
so ``BPConfig(backend="pallas_max")`` reaches it by name.

``pallas_update_t`` is the layout-native variant used by the perf-tuned BP
loop, which keeps messages transposed (S, E) across rounds so the two
transposes per round disappear (see EXPERIMENTS.md SSPerf, BP iterations).

A bucket needs no entry of its own: the engine folds a ``BatchedPGM``'s
(B, E) edges into one (B*E,) disjoint union and calls the single-graph
update on it, so one kernel launch covers the whole bucket.

``triton_update`` is the GPU-class equivalent
(``repro.kernels.triton_update``): same fused pipeline in the engine's
native edge-major layout (zero boundary transposes), blocked over edges
with states in registers, lowered through Pallas's Triton path on CUDA
devices and through the interpreter on the CPU -- plus a
``semiring="max"`` mode so MAP workloads run fused too. Registered as
``"triton"``; ``BPConfig(backend="triton")`` reaches it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import messages as M
from repro.core.graph import PGM
from repro.core.registry import Registry
from repro.kernels.message_update import fused_update_t, pick_block_edges
from repro.kernels.triton_update import fused_update_e

__all__ = ["UPDATE_BACKENDS", "kernel_operands_t", "pallas_update",
           "make_pallas_update", "triton_update", "make_triton_update",
           "register_update_backend", "list_backends", "get_update_fn"]


#: The platform each fused kernel compiles for. On the CPU both run in the
#: Pallas interpreter (the same program, for tests); anywhere else the
#: kernel meant for that platform must be chosen -- an interpreter on an
#: accelerator would be slow without saying so.
_KERNEL_PLATFORM = {"pallas": "tpu", "triton": "gpu"}


def _platform() -> str:
    return jax.default_backend()


def _resolve_interpret(backend: str, interpret: bool | None) -> bool:
    """``interpret`` for kernel ``backend`` ("pallas" | "triton"): an explicit
    value wins; otherwise True on the CPU, False on the kernel's own
    platform, and an error naming the right backend anywhere else."""
    if interpret is not None:
        return interpret
    platform = _platform()
    if platform == "cpu":
        return True
    if platform == _KERNEL_PLATFORM[backend]:
        return False
    meant = [b for b, p in _KERNEL_PLATFORM.items() if p == platform]
    raise RuntimeError(
        f"backend {backend!r} is the {_KERNEL_PLATFORM[backend]} kernel and "
        f"would only be interpreted on {platform!r}; use backend "
        f"{(meant or ['ref'])[0]!r} there (or pass interpret= explicitly)")


def kernel_operands_t(pgm: PGM):
    """Precompute the static transposed operands (do once per graph)."""
    with jax.named_scope("bp.layout"):
        logpsi_t = jnp.transpose(pgm.log_psi_e, (1, 2, 0))  # (S, S, E)
        dmask_t = pgm.state_mask[pgm.edge_dst].T            # (S, E)
        return logpsi_t, dmask_t


@functools.partial(jax.jit, static_argnames=("interpret", "semiring"))
def pallas_update(pgm: PGM, logm: jax.Array, *, interpret: bool | None = None,
                  semiring: str = "sum"):
    """(cand (E,S), resid (E,)) -- kernel-backed ``ref_update`` (or, with
    ``semiring="max"``, ``max_product_update``) equivalent."""
    interpret = _resolve_interpret("pallas", interpret)
    pre = M.edge_prelude(pgm, logm)                          # (E, S)
    logpsi_t, dmask_t = kernel_operands_t(pgm)
    with jax.named_scope("bp.layout"):
        pre_t, logm_t = pre.T, logm.T
    with jax.named_scope("bp.update"):
        new_t, resid = fused_update_t(
            logpsi_t, pre_t, logm_t, dmask_t, interpret=interpret,
            semiring=semiring)
    with jax.named_scope("bp.layout"):
        return new_t.T, resid


def make_pallas_update(interpret: bool | None = None, *,
                       semiring: str = "sum"):
    """Static-arg-free closure suitable for ``run_bp(update_fn=...)``."""
    interpret = _resolve_interpret("pallas", interpret)

    def update_fn(pgm: PGM, logm: jax.Array):
        return pallas_update(pgm, logm, interpret=interpret,
                             semiring=semiring)

    return update_fn


# ------------------------------------------------- triton (GPU) backend --

@functools.partial(jax.jit, static_argnames=("interpret", "semiring",
                                             "blk_e"))
def triton_update(pgm: PGM, logm: jax.Array, *, interpret: bool | None = None,
                  semiring: str = "sum", blk_e: int | None = None):
    """(cand (E,S), resid (E,)) -- GPU-kernel-backed ``ref_update`` (or, with
    ``semiring="max"``, ``max_product_update``) equivalent. Edge-major all
    the way: no layout transposes at the boundary."""
    interpret = _resolve_interpret("triton", interpret)
    pre = M.edge_prelude(pgm, logm)                          # (E, S)
    dmask = pgm.state_mask[pgm.edge_dst]                     # (E, S)
    return fused_update_e(pgm.log_psi_e, pre, logm, dmask,
                          semiring=semiring, interpret=interpret,
                          blk_e=blk_e)


def make_triton_update(interpret: bool | None = None, *,
                       semiring: str = "sum", blk_e: int | None = None):
    """Static-arg-free closure for ``BPConfig(backend="triton")``: resolves
    ``interpret`` once (Triton lowering on GPU, interpreter on the CPU) so
    the returned callable is jit-cache-stable."""
    interpret = _resolve_interpret("triton", interpret)

    def update_fn(pgm: PGM, logm: jax.Array):
        return triton_update(pgm, logm, interpret=interpret,
                             semiring=semiring, blk_e=blk_e)

    return update_fn


# ------------------------------------------------- backend registry ------
# Message-update backends addressable by BPConfig.backend string. "ref" is
# the pure-jnp oracle; "pallas" the fused kernel (interpreted on the CPU);
# "maxprod" and "pallas_max" the same pair for max-product.

def _make_sharded_update(**kwargs):
    # Lazy import: repro.dist builds on the engine, which resolves backends
    # through this registry -- importing at call time breaks the cycle.
    from repro.dist import make_sharded_update
    return make_sharded_update(**kwargs)


#: name -> zero/kwarg factory returning an ``update_fn``. A ``Registry``
#: (dict subclass): plain-dict reads keep working.
UPDATE_BACKENDS = Registry("update backend", {
    "ref": lambda: M.ref_update,
    # Max-product (MAP) semiring: scheduling is semiring-agnostic (paper
    # SSV), so swapping the update swaps the inference task -- the LDPC
    # decoding workload serves through the unchanged engine/serving stack
    # with BPConfig(backend="maxprod") and map_assignment on the result.
    "maxprod": lambda: M.max_product_update,
    "pallas": make_pallas_update,
    # The same TPU kernel's max-product body: MAP with the update fused on
    # the chip ("maxprod" stays as its pure-jnp oracle).
    "pallas_max": functools.partial(make_pallas_update, semiring="max"),
    # GPU-class fused kernel (Pallas Triton lowering, edge-major blocks,
    # states in registers; interpreted on the CPU so CPU CI exercises the
    # same program). semiring="max" kwarg serves MAP.
    "triton": make_triton_update,
    # Multi-device shard_map update over the edge axis (repro.dist). With
    # no kwargs a mesh over all devices is built at resolve time, so
    # BPConfig(backend="sharded") stays a serializable string. The edge
    # axis must split evenly over the mesh (padded counts are multiples of
    # 128, so power-of-two meshes <= 64 always work); run_bp_sharded
    # re-pads single graphs that don't.
    "sharded": _make_sharded_update,
})


def register_update_backend(name: str, *, overwrite: bool = False):
    """Decorator registering an update-backend factory under ``name``
    (lowercased). Duplicates raise ``ValueError`` unless ``overwrite=True``."""
    return UPDATE_BACKENDS.register(name, overwrite=overwrite)


def list_backends():
    """Sorted registered backend names (valid ``BPConfig.backend`` specs)."""
    return UPDATE_BACKENDS.names()


def get_update_fn(name: str, **kwargs):
    """Resolve a backend name to an update callable (see the registry
    above). ``kwargs`` (e.g. ``interpret=``) pass through to the factory."""
    return UPDATE_BACKENDS.lookup(name)(**kwargs)
