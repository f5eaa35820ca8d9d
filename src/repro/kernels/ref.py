"""Pure-jnp oracles for the fused message-update kernels.

``fused_update_t_ref`` mirrors ``message_update.fused_update_t`` exactly
(same transposed (S, E) layout, same masking/normalization semantics);
``fused_update_e_ref`` mirrors ``triton_update.fused_update_e`` in the
GPU-native edge-major (E, S) layout for both semirings. Tests
assert_allclose against these across shape/dtype/semiring sweeps. The
underlying math also lives in ``repro.core.messages``; this module is the
kernel-layout contract. Both take ``exp``/``log`` from
``repro.kernels.explog``, as the kernels and ``repro.core.messages`` do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.explog import exp, log

NEG_INF = -1.0e30


def fused_update_t_ref(logpsi_t: jax.Array,   # (S, S, E)
                       pre_t: jax.Array,      # (S, E)
                       logm_t: jax.Array,     # (S, E)
                       dmask_t: jax.Array):   # (S, E) bool-ish
    scores = logpsi_t + pre_t[:, None, :]
    m = jnp.maximum(jnp.max(scores, axis=0), NEG_INF)
    s = jnp.sum(exp(scores - m[None]), axis=0)
    cand = m + log(s)
    dmask = dmask_t != 0
    cand = jnp.where(dmask, cand, NEG_INF)
    zm = jnp.maximum(jnp.max(cand, axis=0), NEG_INF)
    zs = jnp.sum(jnp.where(dmask, exp(cand - zm[None]), 0.0), axis=0)
    z = zm + log(zs)
    new = jnp.where(dmask, cand - z[None], NEG_INF)
    resid = jnp.max(jnp.where(dmask, jnp.abs(new - logm_t), 0.0), axis=0)
    return new, resid


def fused_update_e_ref(logpsi: jax.Array,   # (E, S, S)
                       pre: jax.Array,      # (E, S)
                       logm: jax.Array,     # (E, S)
                       dmask: jax.Array,    # (E, S) bool-ish
                       *, semiring: str = "sum"):
    """Edge-major oracle for ``triton_update.fused_update_e`` (both
    semirings). ``semiring="max"`` reproduces ``max_product_update``'s
    max-normalize; ``"sum"`` the LSE pipeline of ``fused_update_t_ref``."""
    scores = logpsi + pre[:, :, None]
    dmask = dmask != 0
    if semiring == "max":
        cand = jnp.max(scores, axis=1)
        cand = jnp.where(dmask, cand, NEG_INF)
        z = jnp.max(cand, axis=1)
        new = jnp.where(dmask, cand - z[:, None], NEG_INF)
    else:
        m = jnp.maximum(jnp.max(scores, axis=1), NEG_INF)
        s = jnp.sum(exp(scores - m[:, None, :]), axis=1)
        cand = m + log(s)
        cand = jnp.where(dmask, cand, NEG_INF)
        zm = jnp.maximum(jnp.max(cand, axis=1), NEG_INF)
        zs = jnp.sum(jnp.where(dmask, exp(cand - zm[:, None]), 0.0),
                     axis=1)
        z = zm + log(zs)
        new = jnp.where(dmask, cand - z[:, None], NEG_INF)
    resid = jnp.max(jnp.where(dmask, jnp.abs(new - logm), 0.0), axis=1)
    return new, resid
