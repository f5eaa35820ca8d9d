"""Stereo matching pairs (Felzenszwalb & Huttenlocher, Efficient Belief
Propagation for Early Vision, IJCV 70(1), 2006, section 4).

An H x W grid of pixels with ``n_disp`` disparity labels, 4-neighbour.
The truth is a slanted plane with a raised rectangle. The left image is a
band-limited 8-bit texture; the right one is the left shifted by the truth
(nearer pixels win where two land on one), with a second texture where no
left pixel lands; both get Gaussian noise. The data cost is
``lam_data * min(|I_L(x, y) - I_R(x - d, y)|, trunc_data)`` (the truncation
where ``x - d < 0``), the smoothness cost ``min(|d_p - d_q|,
trunc_smooth)``; the log potentials are their negatives. The distribution
is the one of ``stereo_pair_mrf`` in the program's dataset module, copied
here so that a change there cannot move the benchmark's inputs.

Config keys: ``height``, ``width``, ``n_disp``, ``lam_data``,
``trunc_data``, ``trunc_smooth``, ``noise`` (grey levels).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import Graph

#: Gaussian low-pass of the texture, in pixels (the program's default).
TEXTURE_SIGMA = 1.5


@dataclasses.dataclass(frozen=True)
class PairGraph(Graph):
    """A ``Graph`` that also carries its truth disparity, row-major (V,)."""

    truth: np.ndarray | None = None


def _grid_edges(height: int, width: int) -> np.ndarray:
    idx = np.arange(height * width).reshape(height, width)
    horiz = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    vert = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    return np.concatenate([horiz, vert], axis=0)


def _texture(rng, height, width, sigma):
    r = max(1, int(np.ceil(3 * sigma)))
    k = np.exp(-0.5 * (np.arange(-r, r + 1) / sigma) ** 2)
    k /= k.sum()
    img = rng.standard_normal((height, width))
    img = np.pad(img, r, mode="symmetric")
    img = sum(w * img[:, j:j + width] for j, w in enumerate(k))
    img = sum(w * img[j:j + height, :] for j, w in enumerate(k))
    img = 128.0 + 50.0 * (img - img.mean()) / max(img.std(), 1e-12)
    return np.clip(np.round(img), 0, 255)


def make(cfg: dict, rng: np.random.Generator) -> PairGraph:
    """One pair drawn from ``rng``. ``log_pair`` is a read-only broadcast
    view of the one smoothness table."""
    h, w, nd = int(cfg["height"]), int(cfg["width"]), int(cfg["n_disp"])
    lam, tau = float(cfg["lam_data"]), float(cfg["trunc_data"])
    _, cc = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    truth = np.clip(np.round((cc / max(w - 1, 1)) * (nd // 2)),
                    0, nd - 1).astype(int)
    fh, fw = max(1, h // 3), max(1, w // 3)
    r0, c0 = h // 4, w // 4
    truth[r0:r0 + fh, c0:c0 + fw] = max(nd - 2, 0)

    left = _texture(rng, h, w, TEXTURE_SIGMA)
    right = _texture(rng, h, w, TEXTURE_SIGMA)
    rows, cols = np.nonzero(np.ones((h, w), bool))
    for d in range(nd):
        at = (truth[rows, cols] == d) & (cols >= d)
        right[rows[at], cols[at] - d] = left[rows[at], cols[at]]
    noise = float(cfg["noise"])
    left = np.clip(left + rng.normal(0.0, noise, left.shape), 0, 255)
    right = np.clip(right + rng.normal(0.0, noise, right.shape), 0, 255)

    cost = np.full((h, w, nd), lam * tau)
    for d in range(min(nd, w)):
        diff = np.abs(left[:, d:] - right[:, :w - d])
        cost[:, d:, d] = lam * np.minimum(diff, tau)
    lbl = np.arange(nd)
    smooth = -np.minimum(np.abs(lbl[:, None] - lbl[None, :]),
                         float(cfg["trunc_smooth"]))
    edges = _grid_edges(h, w)
    return PairGraph(n_states=np.full(h * w, nd), edges=edges,
                     log_unary=-cost.reshape(h * w, nd),
                     log_pair=np.broadcast_to(smooth,
                                              (len(edges), nd, nd)),
                     truth=truth.reshape(-1))
