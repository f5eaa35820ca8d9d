"""Kernel microbenchmark: fused message update, roofline-verified.

Four sections, written to ``benchmarks/out/BENCH_kernel.json`` (and a
committed root copy, since ``benchmarks/out/`` is gitignored):

- **kernel**: predicted vs measured cost of one fused GPU-layout update
  (``repro.kernels.triton_update.fused_update_e``) per workload shape and
  semiring. "Predicted" is the hand 3-read/2-write model
  (``repro.roofline.kernel_model``); "measured" is the jaxpr-walk of the
  actual launch (``repro.roofline.trace_cost``), padded shapes and all.
  ``prediction_within_tolerance`` is the acceptance column: the two
  intensities must agree within ``_RTOL``.
- **schedulers**: the same predicted-vs-measured kernel intensity recorded
  per registered scheduler, plus the *round* intensity from tracing one
  full engine round (update + residual gate + frontier select + commit)
  with that scheduler -- i.e. how much each scheduler's selection machinery
  dilutes the kernel's arithmetic intensity.
- **autotune**: ``autotune_blk_e`` wall-time sweep vs the analytic
  ``pick_block_edges_gpu`` choice. On CPU (interpret mode) wall time is
  not the GPU story, so the recorded claim is only that the model pick is
  admissible (a swept candidate); on a real GPU the sweep re-runs there.
- **walltime**: microseconds for one update call through ``ref`` and
  through each fused kernel that runs on this platform: compiled on its
  own platform, interpreted on the CPU (``_interp`` keys: CPU sanity
  numbers, not the accelerator story), left out elsewhere.

Usage: python -m benchmarks.bench_kernel [--tiny | --full]
"""

from __future__ import annotations

import json
import pathlib
import platform
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, out_path
from repro.core import messages as M
from repro.core.schedulers import get_scheduler, list_schedulers
from repro.kernels.ops import make_triton_update, pallas_update, triton_update
from repro.kernels.triton_update import autotune_blk_e, fused_update_e
from repro.pgm import ising_grid, protein_like_graph
from repro.roofline import (fused_update_cost, gpu_padded_shape,
                            predicted_intensity, round_cost, trace_cost)

_RTOL = 0.10   # predicted-vs-measured intensity agreement (acceptance)


def _operands(e, s, dtype=jnp.float32):
    return (jax.ShapeDtypeStruct((e, s, s), dtype),
            jax.ShapeDtypeStruct((e, s), dtype),
            jax.ShapeDtypeStruct((e, s), dtype),
            jax.ShapeDtypeStruct((e, s), jnp.bool_))


def _kernel_row(e, s, *, dtype=jnp.float32, semiring="sum"):
    """Predicted (hand model) vs measured (jaxpr walk) for one launch.

    The trace runs at the *launch* shapes (states to the next power of two,
    edges to a block multiple) -- the kernel the GPU executes -- so the
    host-side pad/slice glue XLA fuses around it is not billed to the
    kernel. The model predicts the same padded launch (``padded=True``).
    """
    db = jnp.dtype(dtype).itemsize
    e_pad, s_pad, blk = gpu_padded_shape(e, s, db)
    meas = trace_cost(lambda *o: fused_update_e(
        *o, semiring=semiring, interpret=True), *_operands(e_pad, s_pad, dtype))
    pred = fused_update_cost(e, s, dtype_bytes=db, semiring=semiring,
                             padded=True)
    mi, pi = meas.flops / meas.bytes, pred.flops / pred.bytes
    rel = abs(mi - pi) / pi
    return dict(n_edges=e, n_states=s, e_pad=e_pad, s_pad=s_pad, blk_e=blk,
                dtype=str(jnp.dtype(dtype)), semiring=semiring,
                predicted_flops=pred.flops, predicted_bytes=pred.bytes,
                measured_flops=meas.flops, measured_bytes=meas.bytes,
                predicted_intensity=pi, measured_intensity=mi,
                intensity_rel_err=rel,
                prediction_within_tolerance=bool(rel <= _RTOL))


def _update_paths() -> dict:
    """Wall-timed update paths on this platform. The kernels resolve
    ``interpret`` themselves (interpreter on the CPU only); a kernel meant
    for another accelerator is left out rather than interpreted on it."""
    here = jax.default_backend()
    paths = {"ref": M.ref_update}
    for name, fn, platform in (("pallas", pallas_update, "tpu"),
                               ("triton", triton_update, "gpu")):
        if here in ("cpu", platform):
            paths[name + ("_interp" if here == "cpu" else "")] = fn
    return paths


def _time_update(fn, pgm, logm, iters=5):
    out = fn(pgm, logm)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(pgm, logm)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(full: bool = False, n_graphs: int = 1, tiny: bool = False) -> None:
    if tiny:
        cases = [("ising6_S2", ising_grid(6, 2.0))]
    elif full:
        cases = [("ising40_S2", ising_grid(40, 2.5)),
                 ("protein100_S~64", protein_like_graph(100, seed=1))]
    else:
        cases = [("ising16_S2", ising_grid(16, 2.0)),
                 ("protein40_S~64", protein_like_graph(40, seed=1))]

    record = {"meta": dict(mode="tiny" if tiny else ("full" if full
                                                     else "default"),
                           jax=jax.__version__,
                           machine=platform.machine(),
                           backend=jax.default_backend(),
                           interpret=jax.default_backend() == "cpu",
                           rtol=_RTOL),
              "kernel": {}, "schedulers": {}, "autotune": {},
              "walltime": {}}

    # -- kernel: predicted vs measured per shape x semiring (+ one bf16) --
    for name, pgm in cases:
        e, s = pgm.n_edges, pgm.n_states_max
        for semiring in ("sum", "max"):
            row = _kernel_row(e, s, semiring=semiring)
            record["kernel"][f"{name}/{semiring}"] = row
            emit(f"kernel/{name}/{semiring}", 0.0,
                 f"pred_ai={row['predicted_intensity']:.3f};"
                 f"meas_ai={row['measured_intensity']:.3f};"
                 f"ok={row['prediction_within_tolerance']}")
    bf = _kernel_row(cases[0][1].n_edges, cases[0][1].n_states_max,
                     dtype=jnp.bfloat16)
    record["kernel"][f"{cases[0][0]}/sum/bf16"] = bf

    # -- schedulers: kernel prediction + round-level dilution ------------
    sched_pgm = cases[0][1]
    e, s = sched_pgm.n_edges, sched_pgm.n_states_max
    kernel_row = record["kernel"][f"{cases[0][0]}/sum"]
    pred_ai = kernel_row["predicted_intensity"]
    meas_ai = kernel_row["measured_intensity"]
    update_fn = make_triton_update(True)
    for sname in list_schedulers():
        rc = round_cost(sched_pgm, get_scheduler(sname), update_fn)
        round_ai = rc.flops / rc.bytes
        rel = abs(meas_ai - pred_ai) / pred_ai
        record["schedulers"][sname] = dict(
            n_edges=e, n_states=s,
            predicted_intensity=pred_ai,
            measured_kernel_intensity=meas_ai,
            measured_round_intensity=round_ai,
            round_flops=rc.flops, round_bytes=rc.bytes,
            kernel_byte_fraction=kernel_row["measured_bytes"] / rc.bytes,
            intensity_rel_err=rel,
            prediction_within_tolerance=bool(rel <= _RTOL))
        emit(f"kernel/sched/{sname}", 0.0,
             f"pred_ai={pred_ai:.3f};meas_ai={meas_ai:.3f};"
             f"round_ai={round_ai:.3f};ok={rel <= _RTOL}")

    # -- autotune: model pick vs wall-time sweep, where the GPU kernel runs
    paths = _update_paths()
    triton_path = [p for p in paths if p.startswith("triton")]
    if triton_path:
        key = jax.random.key(0)
        _, s_pad, model_blk = gpu_padded_shape(e, s)   # model pick, clamped
        logpsi = jax.random.normal(key, (e, s, s))
        pre = jax.random.normal(jax.random.fold_in(key, 1), (e, s))
        logm = jnp.zeros((e, s))
        dmask = jnp.ones((e, s), dtype=bool)
        best_blk, timings = autotune_blk_e(
            logpsi, pre, logm, dmask, iters=1 if tiny else 3,
            interpret=triton_path == ["triton_interp"])
        record["autotune"] = dict(
            case=cases[0][0], n_edges=e, n_states=s,
            model_blk=model_blk, best_blk=best_blk,
            model_pick_swept=bool(model_blk in timings),
            target_intensity=predicted_intensity(s, padded=True),
            timings_us={str(k): v for k, v in sorted(timings.items())})
        emit(f"kernel/autotune/{cases[0][0]}", min(timings.values()),
             f"model_blk={model_blk};best_blk={best_blk}")

    # -- walltime: the update paths that run on this platform ------------
    wt_cases = cases if not tiny else cases[:1]
    for name, pgm in wt_cases:
        logm_g = M.init_messages(pgm)
        for path, fn in paths.items():
            us = _time_update(fn, pgm, logm_g, iters=2 if tiny else 5)
            record["walltime"][f"{name}/{path}"] = us
            emit(f"kernel/{name}/{path}", us,
                 f"E={pgm.n_edges};S={pgm.n_states_max}")

    payload = json.dumps(record, indent=2, sort_keys=True)
    out = out_path("BENCH_kernel.json")
    out.write_text(payload)
    # Committed root copy: benchmarks/out/ is gitignored, and the
    # predicted-vs-measured table is a repo-level claim, not a CI artifact.
    root = pathlib.Path(__file__).resolve().parent.parent / "BENCH_kernel.json"
    root.write_text(payload)
    print(f"# wrote {out} and {root}")


if __name__ == "__main__":
    import sys
    run(full="--full" in sys.argv, tiny="--tiny" in sys.argv)
