"""Pod-scale dispatch tests: the 2-D ``("cells", "replicas")`` mesh and the
persistent compilation cache.

The bitwise contract extends across mesh SHAPES: the sweep engine pads each
grid axis up to its mesh extent (cells with inert empty rows, replicas by
repeating a key), shards both axes, and slices the padding off — so a
forced-8-device host must produce results bitwise-equal to the 1-device
looped engine under every (cells, replicas) factorization of the device
count, in both ``auto`` and ``shard_map`` partitions.  The persistent
compilation cache must make a FRESH PROCESS re-dispatching an identical
grid skip XLA compilation entirely (zero new disk entries), while a changed
GridSignature misses exactly once.

Both subprocess tests are ``slow`` (they compile full mixed-mode programs /
launch multiple interpreters); the mesh-shape heuristic, shardctx plumbing,
and check_bench schema rules are pinned inline.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType

from repro import shardctx
from repro.launch import mesh as mesh_lib

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
_BENCH = os.path.join(os.path.dirname(_SRC), "benchmarks")


def _sub_env(n_devices=None):
    env = dict(os.environ)
    if n_devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    return env


# ------------------------------------------------- mesh-shape heuristic


def test_sweep_mesh_shape_pod_slice_fills_every_device():
    # the paper-baseline 15-cell x 32-replica grid on a 480-device slice
    assert mesh_lib.sweep_mesh_shape(480, 15, 32) == (15, 32)


def test_sweep_mesh_shape_divisor_heuristic():
    assert mesh_lib.sweep_mesh_shape(4, 3, 9) == (2, 2)  # largest divisor <= 3
    assert mesh_lib.sweep_mesh_shape(8, 15, 2) == (8, 1)  # more cells than devices
    assert mesh_lib.sweep_mesh_shape(1, 7, 7) == (1, 1)
    assert mesh_lib.sweep_mesh_shape(8, 8, 1) == (8, 1)
    assert mesh_lib.sweep_mesh_shape(6, 4, 4) == (3, 2)


def test_sweep_mesh_shape_validates():
    with pytest.raises(ValueError, match="n_devices"):
        mesh_lib.sweep_mesh_shape(0, 3, 3)
    with pytest.raises(ValueError, match="non-empty"):
        mesh_lib.sweep_mesh_shape(4, 0, 3)
    with pytest.raises(ValueError, match="non-empty"):
        mesh_lib.sweep_mesh_shape(4, 3, 0)


def test_make_sweep_mesh_single_device_axes():
    mesh = mesh_lib.make_sweep_mesh(3, 5)
    assert tuple(mesh.axis_names) == ("cells", "replicas")
    assert (mesh.shape["cells"], mesh.shape["replicas"]) == (1, 1)


# ------------------------------------------------- shardctx plumbing


def test_sweep_mesh_context_install_and_restore():
    assert shardctx.current_sweep_mesh() is None
    mesh = mesh_lib.make_sweep_mesh(2, 2)
    with shardctx.sweep_mesh(mesh) as m:
        assert m is mesh and shardctx.current_sweep_mesh() is mesh
        inner = mesh_lib.make_sweep_mesh(1, 1)
        with shardctx.sweep_mesh(inner):
            assert shardctx.current_sweep_mesh() is inner
        assert shardctx.current_sweep_mesh() is mesh
    assert shardctx.current_sweep_mesh() is None


def test_sweep_mesh_context_rejects_wrong_axes():
    bad = jax.make_mesh((1, 1), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    with pytest.raises(ValueError, match="cells"):
        with shardctx.sweep_mesh(bad):
            pass


# ------------------------------------------------- check_bench schema rules


def _check_bench():
    spec = importlib.util.spec_from_file_location(
        "check_bench", os.path.join(_BENCH, "check_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_bench_mesh_shape_rules():
    cb = _check_bench()
    assert cb.mesh_shape_error({"n_devices": 1}) is None
    assert cb.mesh_shape_error({"mesh_shape": [15, 32], "n_devices": 480}) is None
    err = cb.mesh_shape_error({"n_devices": 8})
    assert err and "no mesh_shape" in err
    for bad in ([8], [2, 2, 2], [0, 8], [True, 8], ["2", 4], "2x4"):
        assert cb.mesh_shape_error({"mesh_shape": bad}), bad


def test_check_bench_cold_cache_rules():
    cb = _check_bench()
    cc = {"cold_uncached_s": 4.0, "cold_cached_s": 1.0,
          "uncached_added_entries": 3, "cached_added_entries": 0,
          "cache_dir_prewarmed": False}
    ok = {"smoke": True, "cold_cache": dict(cc)}
    assert cb.cold_cache_error(ok) is None
    assert cb.cold_cache_error(ok, min_cold_cache_speedup=2.0) is None

    # absent section: fine at zero floor, required at a positive floor
    assert cb.cold_cache_error({"smoke": True}) is None
    assert "required" in cb.cold_cache_error({"smoke": True},
                                             min_cold_cache_speedup=1.05)

    # the cached probe compiling ANYTHING is a hard error at any floor
    miss = {"smoke": True, "cold_cache": dict(cc, cached_added_entries=2)}
    assert "COMPILED" in cb.cold_cache_error(miss)

    # ratio floor enforced only when the uncached probe really compiled
    slow = {"smoke": True, "cold_cache": dict(cc, cold_cached_s=3.9)}
    assert "floor" in cb.cold_cache_error(slow, min_cold_cache_speedup=2.0)
    prewarmed = {"smoke": True,
                 "cold_cache": dict(cc, cold_cached_s=3.9,
                                    uncached_added_entries=0,
                                    cache_dir_prewarmed=True)}
    assert cb.cold_cache_error(prewarmed, min_cold_cache_speedup=2.0) is None

    # non-smoke records must beat the in-process cold dispatch
    full = {"smoke": False, "sweep_s": {"cold": 10.0, "warm": 0.1},
            "cold_cache": dict(cc)}
    assert cb.cold_cache_error(full) is None
    full_slow = {"smoke": False, "sweep_s": {"cold": 0.5, "warm": 0.1},
                 "cold_cache": dict(cc)}
    assert cb.cold_cache_error(full_slow)


# ------------------------------------------------- forced-8-device bitwise pin

_PODSCALE_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
AUTO2 = (AxisType.Auto,) * 2
assert jax.local_device_count() == 8, jax.local_device_count()
from repro import shardctx
from repro.core.faults import byzantine_plan
from repro.core.montecarlo import run_monte_carlo
from repro.core.sweep import SweepCase, run_sweep
from repro.core.controller import FixedKController, PflugController
from repro.core.straggler import Exponential, RateSchedule, WorkerFleet
from repro.data import make_linreg_data

N, M, D = 8, 160, 4
data = make_linreg_data(jax.random.PRNGKey(0), m=M, d=D)
loss = lambda w, X, y: (X @ w - y) ** 2
L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / M).max())
eta = 0.05 / L
w0 = jnp.zeros((D,))
keys = jax.random.split(jax.random.PRNGKey(7), 4)
fleet = WorkerFleet(
    models=(Exponential(rate=1.0),) * 4 + (Exponential(rate=0.25),) * 2,
    schedule=RateSchedule(times=(5.0,), scales=(0.5,)),
)
# mixed execution modes, a Byzantine fault cell, and a hetero fleet cell —
# the same cell families the 1-device tier-1 battery pins bitwise
cases = [
    SweepCase(PflugController(n_workers=N, k0=2, step=2, thresh=5, burnin=10),
              Exponential(rate=1.0), eta, label="sync_pflug"),
    SweepCase(FixedKController(n_workers=N, k=2), Exponential(rate=1.0), eta,
              label="kasync_k2", mode="kasync"),
    SweepCase(FixedKController(n_workers=N, k=3), Exponential(rate=1.0), eta,
              label="kbatch_k3", mode="kbatch"),
    SweepCase(FixedKController(n_workers=N, k=3), Exponential(rate=1.0), eta,
              label="flip", fault=byzantine_plan(N, 0.25, "sign_flip")),
    SweepCase(FixedKController(n_workers=6, k=2), fleet, eta,
              label="kasync_hetero_n6", mode="kasync"),
]
refs = [run_monte_carlo(loss, w0, data.X, data.y, n_workers=N,
                        controller=c.controller, straggler=c.straggler,
                        eta=c.eta, fault=c.fault, num_iters=120, keys=keys,
                        eval_every=40, mode=c.mode)
        for c in cases]

def check(res, tag):
    for g, (c, ref) in enumerate(zip(cases, refs)):
        for field in ("time", "loss", "k"):
            a = np.asarray(getattr(res, field)[g])
            b = np.asarray(getattr(ref, field))
            assert np.array_equal(a, b), (tag, c.label, field)

kw = dict(n_workers=N, num_iters=120, keys=keys, eval_every=40,
          specialize=False)

# default mesh (heuristic picks (4, 2) for 5 cells on 8 devices): both
# partition paths must match the looped 1-device ground truth bitwise
for part in ("auto", "shard_map"):
    check(run_sweep(loss, w0, data.X, data.y, cases=cases, partition=part,
                    **kw), f"default/{part}")

# every factorization of the 8 devices: bitwise-invariant.  (1, 8) pads
# replicas 4 -> 8, (8, 1) pads cells 5 -> 8, (2, 4) pads cells 5 -> 6 —
# all three padding regimes are exercised.  Shapes alternate between the
# shardctx context and the explicit mesh= argument to pin both plumbings.
for i, shape in enumerate([(1, 8), (2, 4), (8, 1)]):
    mesh = jax.make_mesh(shape, ("cells", "replicas"), axis_types=AUTO2)
    if i % 2 == 0:
        with shardctx.sweep_mesh(mesh):
            res = run_sweep(loss, w0, data.X, data.y, cases=cases, **kw)
    else:
        res = run_sweep(loss, w0, data.X, data.y, cases=cases, mesh=mesh, **kw)
    check(res, f"mesh{shape}")

# shard_map on a genuinely 2-D decomposition
mesh = jax.make_mesh((2, 4), ("cells", "replicas"), axis_types=AUTO2)
check(run_sweep(loss, w0, data.X, data.y, cases=cases, mesh=mesh,
                partition="shard_map", **kw), "mesh(2, 4)/shard_map")
print("PODSCALE_OK")
"""


@pytest.mark.slow
def test_sweep_2d_mesh_bitwise_across_shapes_forced_8_devices():
    """Mixed-mode mixed-fault grid on a forced 8-device host: bitwise vs the
    1-device looped engine under auto + shard_map at the heuristic mesh
    shape AND at every (cells, replicas) factorization (1x8, 2x4, 8x1)."""
    proc = subprocess.run([sys.executable, "-c", _PODSCALE_SCRIPT],
                          env=_sub_env(8), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PODSCALE_OK" in proc.stdout


# ------------------------------------------------- persistent compilation cache

_CACHE_SCRIPT = """
import json, sys, time
iters = int(sys.argv[1])
from repro.core import cache as cache_lib
cache_dir = cache_lib.setup_compilation_cache()
import jax, jax.numpy as jnp
from repro.core.controller import FixedKController
from repro.core.straggler import Exponential
from repro.core.sweep import SweepCase, run_sweep
from repro.data import make_linreg_data

data = make_linreg_data(jax.random.PRNGKey(0), m=8, d=2)
before = cache_lib.cache_entries(cache_dir)
t0 = time.perf_counter()
run_sweep(lambda w, X, y: (X @ w - y) ** 2, jnp.zeros((2,)), data.X, data.y,
          n_workers=2,
          cases=[SweepCase(FixedKController(n_workers=2, k=1),
                           Exponential(rate=1.0), 0.01)],
          num_iters=iters, key=jax.random.PRNGKey(0), n_replicas=1,
          eval_every=2)
print(json.dumps({"added": cache_lib.cache_entries(cache_dir) - before,
                  "cold_s": time.perf_counter() - t0}))
"""


def _cache_probe(cache_dir, iters):
    env = _sub_env()
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT, str(iters)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_persistent_cache_fresh_process_skips_compile(tmp_path):
    """Same grid, same cache dir, two fresh interpreters: the first pays for
    XLA compilation (new disk entries), the second is a full disk hit (zero
    new entries).  A changed GridSignature (different iteration count, so a
    different traced HLO) misses exactly once, then hits."""
    cache_dir = str(tmp_path / "xla-cache")
    first = _cache_probe(cache_dir, iters=4)
    assert first["added"] > 0, first
    second = _cache_probe(cache_dir, iters=4)
    assert second["added"] == 0, second

    changed = _cache_probe(cache_dir, iters=6)
    assert changed["added"] > 0, changed
    changed_again = _cache_probe(cache_dir, iters=6)
    assert changed_again["added"] == 0, changed_again
