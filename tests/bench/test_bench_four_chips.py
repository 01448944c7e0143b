"""The four-chip cell on four virtual CPU devices: it runs on a mesh over
all four and proves correct, and with the exchange between chips left out
(``bench/faults.py``: the lanes held by the other chips never reach the
host) it comes out as not correct.  Each case runs in a process of its own,
because jax fixes the device count when it starts."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCRIPT = r"""
import contextlib, json, sys, tempfile
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1], sys.argv[1] + "/tests/bench"]
import jax
from benchsmall import small_root
from bench import faults, run

assert len(jax.devices()) == 4
plant = (faults.sweep_exchange_left_out() if sys.argv[2] == "exchange_left_out"
         else contextlib.nullcontext())
root = small_root(tempfile.mkdtemp())
with plant:
    line = run.run_cell("fig2-sync-x4", 2 ** 31 + 7, 0.0, False, jax.devices(), root=root)
print(json.dumps(line))
"""


@pytest.mark.parametrize("fault,correct", [("none", True), ("exchange_left_out", False)])
def test_four_chip_cell_on_four_virtual_devices(fault, correct):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, ROOT, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["device"]["count"] == 4
    assert line["correct"] is correct, line["checks"]
