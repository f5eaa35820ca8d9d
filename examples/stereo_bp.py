"""Stereo-vision MRF: disparity decoding on a synthetic scene.

The paper motivates BP with vision workloads; this driver decodes a
truncated-linear stereo MRF (``repro.pgm.stereo_mrf``: a slanted disparity
plane with a raised foreground rectangle, noisy observations, the classic
grid energy) with max-product BP through the unchanged engine
(``BPConfig(backend="maxprod")``) and scores the labeling two ways:

- **accuracy**: fraction of pixels within +-1 disparity of ground truth
  (the complement of the standard bad-pixel metric) -- must beat the raw
  rounded observation, i.e. the smoothness term must actually denoise;
- **energy**: the MAP objective. BP's labeling should reach at-or-below
  the *ground truth's* energy (noise makes truth slightly suboptimal
  under its own posterior -- matching it is the decoding win).

Run:  PYTHONPATH=src python examples/stereo_bp.py [--height 12] \
          [--width 16] [--disp 8] [--scheduler rbp]
"""

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import BPConfig, BPEngine, list_schedulers
from repro.core.messages import map_assignment
from repro.pgm import stereo_mrf


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--height", type=int, default=12)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--disp", type=int, default=8,
                    help="disparity levels (states per pixel)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheduler", default="rbp", choices=list_schedulers())
    ap.add_argument("--max-rounds", type=int, default=2000)
    args = ap.parse_args()

    inst = stereo_mrf(args.height, args.width, args.disp, seed=args.seed)
    engine = BPEngine(BPConfig(scheduler=args.scheduler, backend="maxprod",
                               eps=1e-4, max_rounds=args.max_rounds,
                               history=False))
    t0 = time.perf_counter()
    res = engine.run(inst.pgm, jax.random.key(args.seed))
    n_pix = args.height * args.width
    labels = np.asarray(map_assignment(inst.pgm, res.logm))[:n_pix]
    wall = time.perf_counter() - t0

    obs_labels = np.clip(np.round(inst.obs), 0, args.disp - 1).astype(int)
    print(f"stereo {args.height}x{args.width}x{args.disp} "
          f"scheduler={args.scheduler}: converged={bool(res.converged)} "
          f"rounds={int(res.rounds)} wall={wall:.2f}s")
    print(f"accuracy(+-1): observation={inst.accuracy(obs_labels):.3f} "
          f"BP={inst.accuracy(labels):.3f}")
    print(f"energy: truth={inst.energy(inst.truth):.2f} "
          f"observation={inst.energy(obs_labels):.2f} "
          f"BP={inst.energy(labels):.2f} (lower is better)")
    disp_map = labels.reshape(args.height, args.width)
    print("decoded disparity map (rows top to bottom):")
    for row in disp_map:
        print("  " + "".join(f"{d:x}" for d in row))


if __name__ == "__main__":
    main()
