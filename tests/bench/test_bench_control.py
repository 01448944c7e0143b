"""The controls come out as not correct.

The control is the plain reference computed one precision step below the
configuration's, put in the program's place: three bfloat16 passes
(``high``) for the float32-at-highest simulation, float8 for the bfloat16
model.  At these small sizes on the CPU it must read above the limits that
the program's runs keep under (``benchsmall.SMALL_LIMITS``), on three seeds.
The chip readings at each cell's own size are in PERF.md.
"""

from __future__ import annotations

import jax
import pytest

from bench import common, registry
from bench.calibrate import CONTROL_PRECISION
from benchsmall import small_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench_ctl"))


@pytest.mark.parametrize("cell", ["fig2-sync", "fig2-modes", "qwen05b-train-sync"])
@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
def test_the_control_fails_a_compared_number(root, cell, seed):
    bench_dir = root + "/bench"
    w = registry.cell(cell, root)
    cfg = registry.config(w["config"], bench_dir)
    tr = registry.traffic(w["traffic"], bench_dir)
    c = common.CellRun(name=cell, cell=w, config=cfg, traffic=tr, seed=seed, seconds=0.0,
                       trace=False, devices=jax.devices()[:1], peaks={},
                       start_wall=common.process_start_wall(), trace_dir="")
    prec = CONTROL_PRECISION[cfg["precision"]["matmul"]]
    readings = registry.driver(tr["entry"], bench_dir).control(c, prec)
    limits = tr["check"]["limits"]
    failed = {k: v for k, v in readings.items() if k in limits and v > limits[k]}
    assert failed, (readings, limits)
