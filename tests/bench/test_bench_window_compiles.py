"""Nothing compiles inside a cell's timed window: a small copy of each cell
(``benchsmall``) runs through the harness with ``repro.core.cache``'s
compile counter read on both sides of the window (``common.settled`` wraps
exactly the window in every driver).  Each run is a fresh process, as the
benchmark's are, with its own compilation cache directory."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from benchsmall import ROOT

_SCRIPT = """
import contextlib, json, sys
from repro.core import cache
cache.setup_compilation_cache()
import jax
from bench import common, run
from benchsmall import small_root

window = {}
settled = common.settled


@contextlib.contextmanager
def counted():
    before = cache.compile_stats()
    with settled():
        yield
        after = cache.compile_stats()
    window.update({k: after[k] - before[k] for k in after})


common.settled = counted
line = run.run_cell(sys.argv[2], 2 ** 31 + 11, 0.0, False, jax.devices(),
                    root=small_root(sys.argv[1]))
print(json.dumps({"window": window, "correct": line["correct"],
                  "attempted": line["attempted"]}))
"""


@pytest.mark.parametrize("cell", ["fig2-sync", "fig2-modes", "qwen05b-train-sync"])
def test_the_timed_window_compiles_nothing(cell, tmp_path):
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, here] + [p for p in [env.get("PYTHONPATH")] if p])
    (tmp_path / "root").mkdir()
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, str(tmp_path / "root"), cell],
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] >= 1
    assert out["window"] == {"traces": 0, "compiles": 0, "cache_hits": 0}
