"""MAP stereo through the fused TPU kernel's max-product body.

The max body of ``fused_update_t`` (interpret mode on the CPU) against the
pure-jnp max-product math of ``repro.core.messages``, bitwise, over state
counts, masks and ragged edge counts; ``BPConfig(backend="pallas_max")``
against ``"maxprod"`` on a small ``stereo_pair_mrf``, round for round;
max-product on a one-row pair (a chain, where it is exact) against the
brute-force MAP; and the generator's determinism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BPConfig, BPEngine
from repro.core import messages as M
from repro.core.exact import brute_force_map
from repro.core.graph import NEG_INF
from repro.kernels.message_update import fused_update_t, pick_block_edges
from repro.kernels.ops import pallas_update
from repro.pgm import stereo_pair_mrf


def _operands(rng, e, s, *, all_masked_frac=0.1):
    """Edge-major (logpsi, pre, logm, dmask) with masked source and
    destination states and some all-masked edges."""
    logpsi = rng.standard_normal((e, s, s)).astype(np.float32)
    n_src = rng.integers(1, s + 1, size=e)
    pre = np.where(np.arange(s)[None, :] < n_src[:, None],
                   rng.standard_normal((e, s)), NEG_INF).astype(np.float32)
    dmask = np.arange(s)[None, :] < rng.integers(1, s + 1, size=e)[:, None]
    dmask[rng.random(e) < all_masked_frac] = False
    logm = np.where(dmask, rng.standard_normal((e, s)), NEG_INF)
    return logpsi, pre, logm.astype(np.float32), dmask


def _max_product_math(logpsi, pre, logm, dmask):
    """``max_product_update``'s ops after its prelude, on raw operands."""
    cand = M.propagate_max(logpsi, pre)
    cand = jnp.where(dmask, cand, NEG_INF)
    z = jnp.max(jnp.where(dmask, cand, NEG_INF), axis=1)
    cand = jnp.where(dmask, cand - z[:, None], NEG_INF)
    return cand, jnp.max(jnp.where(dmask, jnp.abs(cand - logm), 0.0), axis=1)


@pytest.mark.parametrize("s", [2, 5, 16, 17])
def test_max_body_is_bitwise_the_jnp_math(s):
    blk = pick_block_edges(s)
    rng = np.random.default_rng(s)
    for e in (1, 130, blk + 300):       # one edge, a part block, ragged
        logpsi, pre, logm, dmask = _operands(rng, e, s)
        new_t, resid = fused_update_t(
            jnp.transpose(logpsi, (1, 2, 0)), pre.T, logm.T, dmask.T,
            interpret=True, semiring="max")
        want, want_r = _max_product_math(logpsi, pre, logm, dmask)
        np.testing.assert_array_equal(np.asarray(new_t).T, np.asarray(want))
        np.testing.assert_array_equal(np.asarray(resid), np.asarray(want_r))
        dead = ~dmask.any(axis=1)
        assert np.all(np.asarray(new_t).T[dead] == np.float32(NEG_INF))
        assert np.all(np.asarray(resid)[dead] == 0.0)


def test_unknown_semiring_is_refused():
    z = jnp.zeros((2, 128))
    with pytest.raises(ValueError, match="semiring"):
        fused_update_t(jnp.zeros((2, 2, 128)), z, z, z, interpret=True,
                       semiring="min")


def test_pallas_update_max_equals_max_product_update():
    inst = stereo_pair_mrf(6, 9, 16, seed=4)
    rng = np.random.default_rng(0)
    logm = jnp.asarray(rng.normal(size=(inst.pgm.n_edges, 16)), jnp.float32)
    got, got_r = pallas_update(inst.pgm, logm, interpret=True,
                               semiring="max")
    want, want_r = M.max_product_update(inst.pgm, logm)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The jnp path zeroes padded edges' residuals; the kernel leaves them
    # to the engine, which masks them (and whose padded messages are inert).
    real = np.asarray(inst.pgm.edge_mask)
    np.testing.assert_array_equal(np.asarray(got_r)[real],
                                  np.asarray(want_r)[real])


def _engine(backend):
    return BPEngine(BPConfig(scheduler="rnbp",
                             scheduler_kwargs={"low_p": 0.7, "high_p": 1.0},
                             eps=1e-3, max_rounds=500, history=False,
                             backend=backend))


def test_pallas_max_follows_maxprod_round_for_round():
    pgm = stereo_pair_mrf(10, 12, 16, seed=0).pgm
    key = jax.random.key(1)
    got = _engine("pallas_max").run(pgm, key)
    want = _engine("maxprod").run(pgm, key)
    assert bool(want.converged)
    assert int(got.rounds) == int(want.rounds)
    assert int(got.updates) == int(want.updates)
    np.testing.assert_array_equal(np.asarray(got.logm),
                                  np.asarray(want.logm))
    np.testing.assert_array_equal(np.asarray(got.beliefs),
                                  np.asarray(want.beliefs))


@pytest.mark.parametrize("n_disp", [3, 4])
def test_one_row_map_is_the_brute_force_map(n_disp):
    inst = stereo_pair_mrf(1, 7, n_disp, seed=n_disp)
    eng = BPEngine(BPConfig(scheduler="lbp", eps=1e-6, max_rounds=200,
                            backend="pallas_max"))
    res = eng.run(inst.pgm, jax.random.key(0))
    assert bool(res.converged)
    got = np.asarray(M.map_assignment(inst.pgm, res.logm))[:7]
    want = brute_force_map(*inst.raw())
    # Compare energies: ties between labelings are legitimate.
    assert inst.energy(got) == pytest.approx(inst.energy(want), abs=1e-5)


def test_generator_is_deterministic_by_seed():
    a = stereo_pair_mrf(6, 8, 5, seed=7)
    b = stereo_pair_mrf(6, 8, 5, seed=7)
    c = stereo_pair_mrf(6, 8, 5, seed=8)
    for f in ("truth", "left", "right", "unary", "smooth"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    for la, lb in zip(jax.tree_util.tree_leaves(a.pgm),
                      jax.tree_util.tree_leaves(b.pgm)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert not np.array_equal(a.left, c.left)
    assert not np.array_equal(a.unary, c.unary)


def test_pair_is_the_left_image_shifted_by_the_truth():
    inst = stereo_pair_mrf(12, 20, 6, seed=3, noise=0.0)
    rows, cols = np.nonzero(inst.truth >= 0)
    d = inst.truth[rows, cols]
    seen = cols >= d
    matched = (inst.left[rows[seen], cols[seen]]
               == inst.right[rows[seen], cols[seen] - d[seen]])
    # Every seen pixel matches unless it is occluded: a nearer one (larger
    # disparity) lands on the same right pixel.
    nearest = np.full(inst.truth.shape, -1)
    np.maximum.at(nearest, (rows[seen], cols[seen] - d[seen]), d[seen])
    occluded = nearest[rows[seen], cols[seen] - d[seen]] > d[seen]
    assert np.all(matched[~occluded]) and occluded.mean() < 0.5
    # With no noise the truth costs nothing where it is seen: the data term
    # is 0 at the truth wherever the pixel lands unoccluded.
    cost = -np.log(inst.unary[np.arange(rows.size), d])
    assert np.all(cost[seen][matched] == 0.0)
    assert inst.accuracy(inst.truth) == 1.0
