"""Run one cell of the benchmark on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``. The harness is
driven by data: the cell names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); the mix names the driver
(``bench/drivers/<driver>.py``) that runs its loop, and every metric the
manifest gives the cell is read by ``bench/metrics/<metric>.py`` (or by the
reader of its quantity, ``bench/metrics/<name before the first dot>.py``,
where the metric has no file of its own). With
``--trace 0`` the cell's end-to-end metrics are printed, with ``--trace 1``
its per-layer metrics, from a profiler trace of part of the window.

The run exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the checkout's ``src/`` is missing.
The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error and the last key of
that object. ``--control`` runs the program's own lower-precision path
(bfloat16 tables) in the program's place, for setting the limits; its
``correct`` is expected to come out false.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def require_tpu(chips: int):
    """The devices of the cell; exit before any work without a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found {devices[0].platform!r}", 3)
    if len(devices) < chips:
        fail(f"needs {chips} TPU chips; JAX found {len(devices)}", 3)
    return devices[:chips]


def prepare(chips: int):
    """Check for the program and the chips, place the compile cache, and
    return the cell's devices; exits before any work where one is missing."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no program under {os.path.join(ROOT, 'src')}")
    # A fixed directory inside the checkout, also for the program's own
    # cache placement, which reads this variable. Every program is kept,
    # however fast it compiled, so that a warm run compiles nothing.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    devices = require_tpu(chips)
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    return devices


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_metrics(manifest: dict, cell: str, trace: bool) -> list:
    """The manifest's metric entries that ``cell`` reports in this mode."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in moved
                             else [])]


def metric_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, else the
    file of its quantity, ``<name up to the first dot>.py`` (names may hold
    dots, so it is loaded by path)."""
    import importlib.util

    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "metrics", f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def execute(cell: dict, cfg: dict, mix: dict, metrics: list, *, seed: int,
            seconds: float, trace: bool, control: bool = False,
            devices=None) -> dict:
    """Run the cell and return its result object (the printed line)."""
    import jax

    from bench import work
    from bench.drivers import common

    devices = devices or jax.devices()[:int(cell["chips"])]
    ctx = types.SimpleNamespace(cfg=cfg, mix=mix, seed=seed, seconds=seconds,
                                trace=trace, control=control,
                                t_start=T_START)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    out: common.Outcome = driver.run(ctx)
    n, hits, misses = out.setup_programs
    print(f"setup: {n} programs, {hits} from the compile cache, {misses} "
          f"compiled; compiles_in_window={out.compiles_in_window}",
          flush=True)
    walls = sorted(s["wall_s"] for s in out.solves)
    if walls:
        print(f"solve wall ms: min {1e3 * walls[0]:.2f} median "
              f"{1e3 * walls[len(walls) // 2]:.2f} max {1e3 * walls[-1]:.2f}",
              flush=True)
    kind = devices[0].device_kind
    out.peaks = work.peaks(kind) if devices[0].platform == "tpu" else None

    values = {}
    for m in metrics:
        v = metric_reader(m["name"]).read(out)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {name: {"value": v, "op": op, "limit": lim}
              for name, v, op, lim in out.checks}
    correct = all(c["value"] <= c["limit"] if c["op"] == "<="
                  else c["value"] >= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": values, "device": device}
    if trace and out.trace is not None:
        device["busy_s"] = out.trace.busy_s()
        device["window_s"] = out.trace.window_s
        result["breakdown"] = {"device_ops": out.trace.top_ops(10),
                               "idle_gaps": out.trace.idle_gaps(10)}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="the program's bfloat16-table path in its place")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        fail(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    devices = prepare(int(cell["chips"]))

    configs = {c["name"]: c for c in manifest["configs"]}
    cfg = load_json(ROOT, configs[cell["config"]]["file"])
    mix = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    result = execute(cell, cfg, mix,
                     cell_metrics(manifest, cell["name"], bool(args.trace)),
                     seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), control=args.control,
                     devices=devices)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['op']} "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
