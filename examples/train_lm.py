"""Train a small LM end-to-end through the production substrate.

Uses the same config/model/optimizer/data/checkpoint stack as the 512-chip
dry-run, scaled to CPU: a reduced qwen3-family model, a few hundred steps,
loss visibly decreasing, checkpoint + exact resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 60]
(Any assigned arch works: --arch granite_moe_3b_a800m trains the MoE.)
"""

import argparse
import dataclasses
import tempfile
import time

import jax

from repro.compile_cache import use_compile_cache
from repro.checkpoint import latest_step, restore_pytree, save_pytree
from repro.configs import get
from repro.configs.base import TRAIN_4K
from repro.data import SyntheticLM
from repro.models import build_model
from repro.train.step import init_train_state, make_train_step


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = get(args.arch).reduced()
    model = build_model(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(
        model.init_params(jax.random.key(0))))
    print(f"{cfg.name}: {n_params / 1e6:.2f}M params (reduced config)")

    shape = dataclasses.replace(TRAIN_4K, seq_len=args.seq,
                                global_batch=args.batch)
    pipe = SyntheticLM(cfg, shape)
    step = jax.jit(make_train_step(model, base_lr=2e-3, warmup=10,
                                   total_steps=args.steps))
    state = init_train_state(model, jax.random.key(0))

    with tempfile.TemporaryDirectory() as ckpt:
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, m = step(state, pipe.batch(i))
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss={float(m['loss']):.4f} "
                      f"lr={float(m['lr']):.2e}", flush=True)
            if i == args.steps // 2:
                save_pytree(ckpt, i + 1, state, extra={"data_step": i + 1})
        print(f"trained {args.steps} steps in "
              f"{time.perf_counter() - t0:.1f}s")

        # crash-resume drill: restore mid-run checkpoint, replay, compare
        s = latest_step(ckpt)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            state)
        restored, extra = restore_pytree(ckpt, s, like)
        for i in range(extra["data_step"], args.steps):
            restored, m2 = step(restored, pipe.batch(i))
        drift = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(abs(a.astype("float32")
                                   - b.astype("float32")).max()),
            state.params, restored.params)))
        print(f"checkpoint-resume replay drift: {drift:.2e} (exact = 0)")


if __name__ == "__main__":
    main()
