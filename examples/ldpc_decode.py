"""LDPC decoding over AWGN: BER-vs-SNR for max-product BP vs uncoded.

The paper motivates BP with error-correcting codes; this driver closes the
loop: a regular (n, dv, dc) Gallager code is encoded as a pairwise PGM
(``repro.pgm.ldpc_code`` -- check constraints become auxiliary vertices
with even-parity states), the channel is BPSK over AWGN, and decoding is
the *unchanged* engine with ``BPConfig(backend="maxprod")`` -- scheduling
is semiring-agnostic, so the whole scheduler/serving stack decodes codes
without modification.

For each SNR point the all-zero codeword is transmitted ``--words`` times
with fresh noise; the coded bit-error rate (max-product MAP + argmax
beliefs) is compared against the uncoded hard-decision BER on the same
received samples. The coded curve must drop below uncoded -- that gap is
the decoder doing real work, and ``tests/test_zoo.py`` pins it.

Run:  PYTHONPATH=src python examples/ldpc_decode.py [--words 8] \
          [--snr 1.0,2.0,3.0] [--n 48] [--scheduler rbp]
"""

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import BPConfig, BPEngine, list_schedulers
from repro.core.messages import map_assignment
from repro.pgm import ldpc_code


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=48, help="code length (bits)")
    ap.add_argument("--dv", type=int, default=3, help="bit degree")
    ap.add_argument("--dc", type=int, default=6, help="check degree")
    ap.add_argument("--words", type=int, default=8,
                    help="codewords simulated per SNR point")
    ap.add_argument("--snr", type=str, default="1.0,2.0,3.0",
                    help="comma-separated SNR points (dB)")
    ap.add_argument("--scheduler", default="lbp", choices=list_schedulers())
    ap.add_argument("--max-rounds", type=int, default=400)
    args = ap.parse_args()

    engine = BPEngine(BPConfig(scheduler=args.scheduler, backend="maxprod",
                               eps=1e-4, max_rounds=args.max_rounds,
                               history=False))
    rate = 1.0 - args.dv / args.dc
    print(f"({args.n},{args.dv},{args.dc}) regular LDPC, rate {rate:.2f}, "
          f"{args.words} words/point, scheduler={args.scheduler}")
    print(f"{'snr_db':>7} {'uncoded_ber':>12} {'coded_ber':>10} "
          f"{'conv':>6} {'rounds':>7} {'wall_s':>7}")
    for snr_db in [float(s) for s in args.snr.split(",")]:
        t0 = time.perf_counter()
        coded = uncoded = bits = conv = 0
        rounds = []
        for w in range(args.words):
            inst = ldpc_code(args.n, dv=args.dv, dc=args.dc, snr_db=snr_db,
                             seed=1000 * w + 7)
            res = engine.run(inst.pgm, jax.random.key(w))
            decoded = np.asarray(map_assignment(inst.pgm, res.logm))
            coded += inst.coded_errors(decoded)
            uncoded += inst.uncoded_errors
            bits += inst.n_bits
            conv += int(bool(res.converged))
            rounds.append(int(res.rounds))
        print(f"{snr_db:7.1f} {uncoded / bits:12.4f} {coded / bits:10.4f} "
              f"{conv:3d}/{args.words:<2d} {np.mean(rounds):7.1f} "
              f"{time.perf_counter() - t0:7.2f}")


if __name__ == "__main__":
    main()
