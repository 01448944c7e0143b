"""Read the numbers that the correctness limits are set from, in one process.

    python bench/calibrate.py --workload <cell> --seeds 1-12 --control-seeds 1-3 \
        [--fault-seeds 1-3]

For every seed: one run of the cell with a zero-second window (set-up, one
unit of work, the comparison with the reference); its compared numbers are
the program's readings, whose largest is a limit's lower reading.  For each
control seed: the control (the reference one precision step below the
configuration's, in the program's place) against the reference; its smallest
reading is the upper one.  For each fault seed: one run under each fault
the cell can have (``bench/faults.py``), planted in the program.  Prints
one JSON line per reading.  Needs the cell's chips, like ``bench/run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)

CONTROL_PRECISION = {"highest": "high", "default": "fp8"}


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(_ROOT, "src"), _ROOT]

    from bench import common, faults, registry, run

    from repro.core import cache

    cache.setup_compilation_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: no TPU; nothing was run", file=sys.stderr)
        return 1
    cell = registry.cell(args.workload)
    cfg = registry.config(cell["config"])
    tr = registry.traffic(cell["traffic"])
    drv = registry.driver(tr["entry"])
    for s in seeds(args.seeds) if args.seeds else []:
        line = run.run_cell(args.workload, s, 0.0, False, devices)
        print(json.dumps({"who": "program", "seed": s, "correct": line["correct"],
                          "readings": {k: v["value"] for k, v in line["checks"].items()}}),
              flush=True)
    prec = CONTROL_PRECISION[cfg["precision"]["matmul"]]
    for s in seeds(args.control_seeds) if args.control_seeds else []:
        c = common.CellRun(
            name=args.workload, cell=cell, config=cfg, traffic=tr, seed=s, seconds=0.0,
            trace=False, devices=devices[:cell["chips"]], peaks={},
            start_wall=common.process_start_wall(), trace_dir="")
        with jax.default_matmul_precision(cfg["precision"]["matmul"]):
            readings = drv.control(c, prec)
        print(json.dumps({"who": f"control:{prec}", "seed": s, "readings": readings}),
              flush=True)
    for name, plant in faults.for_cell(tr["entry"], cell["chips"]).items():
        for s in seeds(args.fault_seeds) if args.fault_seeds else []:
            with plant():
                line = run.run_cell(args.workload, s, 0.0, False, devices)
            print(json.dumps({"who": f"fault:{name}", "seed": s, "correct": line["correct"],
                              "readings": {k: v["value"] for k, v in line["checks"].items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
