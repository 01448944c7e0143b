"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the cell's chips.
The cell is the ``BENCHMARK.json`` entry named ``<cell>``; its configuration,
traffic mix, driver and per-layer metrics are found by name under ``bench/``
(see ``bench/registry.py``).  The run

* keeps jax's persistent compilation cache in ``$JAX_COMPILATION_CACHE_DIR``,
  else at ``<checkout>/.jax_cache`` (``repro.core.cache``);
* refuses any platform but the TPU, and fewer chips than the cell asks for,
  with a non-zero exit and no result;
* uses exactly the cell's ``chips`` devices, the first ones of the host;
* runs at the configuration's matmul precision;
* warms up, measures for ``--seconds`` (``--trace 1``: profiles a short
  window instead and reports the per-layer metrics), checks what the timed
  path produced against the plain reference, and prints each number compared
  beside its limit on stderr, then the JSON result as the last line of stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def result_line(cell_name: str, outcome, devices, traced: bool, root: str = _ROOT) -> dict:
    """The contract's JSON object for one run."""
    from bench import registry

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": outcome.memory_peak_bytes}
    metrics = {}
    if not traced:
        for m in registry.metrics_for(cell_name, "end_to_end", root):
            metrics[m["name"]] = {"value": outcome.metrics[m["name"]], "unit": m["unit"]}
        out = {"correct": outcome.correct, "attempted": outcome.attempted,
               "failed": outcome.failed, "metrics": metrics, "device": device}
    else:
        bench_dir = os.path.join(root, "bench")
        ctx = {"outcome": outcome, "trace": outcome.trace, "layer": outcome.layer,
               "peaks": registry.peaks(dev.device_kind, bench_dir)}
        for m in registry.metrics_for(cell_name, "per_layer", root):
            v = registry.metric(m["name"], bench_dir).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = outcome.trace.busy_s
        device["window_s"] = outcome.trace.window_s
        out = {"correct": outcome.correct, "attempted": outcome.attempted,
               "failed": outcome.failed, "metrics": metrics, "device": device,
               "breakdown": outcome.trace.breakdown()}
    out["checks"] = {k: {"value": c.value, "limit": c.limit}
                     for k, c in outcome.checks.items()}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, devices,
             root: str = _ROOT) -> dict:
    """Run cell ``name`` (of ``<root>/BENCHMARK.json``, its files under
    ``<root>/bench``) on ``devices`` and return its result line.  Prints each
    number compared beside its limit on stderr."""
    import jax

    from bench import common, registry

    start = common.process_start_wall()
    bench_dir = os.path.join(root, "bench")
    cell = registry.cell(name, root)
    cfg = registry.config(cell["config"], bench_dir)
    tr = registry.traffic(cell["traffic"], bench_dir)
    drv = registry.driver(tr["entry"], bench_dir)
    if len(devices) < cell["chips"]:
        raise RuntimeError(f"cell {name} needs {cell['chips']} chips, found {len(devices)}")
    devices = devices[:cell["chips"]]
    peaks = registry.peaks(devices[0].device_kind, bench_dir)
    trace_dir = os.path.join(root, ".bench_trace", name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = common.CellRun(
        name=name, cell=cell, config=cfg, traffic=tr, seed=seed, seconds=seconds,
        trace=trace, devices=devices, peaks=peaks, start_wall=start,
        trace_dir=trace_dir)
    common.phase(run, f"devices ready: {len(devices)} x {devices[0].device_kind}")
    try:
        with jax.default_matmul_precision(cfg["precision"]["matmul"]):
            with jax.default_device(devices[0]):
                outcome = drv.run(run)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    line = result_line(name, outcome, devices, trace, root)
    for check, c in line["checks"].items():
        print(f"check {check}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr,
              flush=True)
    return line


def main(argv=None) -> int:
    args = parse(argv)
    src = os.path.join(_ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: no program next to {_BENCH} (missing {src}/repro)", file=sys.stderr)
        return 2
    sys.path[:0] = [src, _ROOT]

    from bench import registry

    cell = registry.cell(args.workload)  # an unknown cell fails before jax starts

    from repro.core import cache

    cache.setup_compilation_cache()  # before the first compile
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (jax sees {devices[0].platform}); nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: cell {args.workload} needs {cell['chips']} chips, "
              f"found {len(devices)}; nothing was run", file=sys.stderr)
        return 1
    line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
