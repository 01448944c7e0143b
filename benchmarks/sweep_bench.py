"""Old-vs-new sweep benchmark: looped per-cell `run_monte_carlo` dispatches
versus ONE grid-vmapped `run_sweep` dispatch, on a fixed controller x
straggler grid at 4k iterations.  Writes ``results/BENCH_sweep.json`` — the
scratch output whose full-grid variant is promoted to the repo-root
committed baseline (see benchmarks/README.md for the schema and the
root-vs-results convention).

The *old* engine rebuilt ``jax.jit(jax.vmap(run_one))`` on every call, so a
G-cell grid paid G traces + G compiles + G dispatches; that is the ``cold``
looped number (measured by clearing the module-level program cache first).
The ``warm`` looped number is the post-PR cached loop (compiles amortized,
still G dispatches); the sweep engine replaces both with a single program.
``speedup`` refers to old-vs-new, i.e. cold-vs-cold; ``speedup_warm``
(cache-hot loop vs cache-hot sweep) is the branch-signature-specialization
headline — ``check_bench.py`` gates it at >= ``--min-warm-speedup``.

``sweep_s`` times the engine's DEFAULT dispatch (``specialize=True``: the
grid's branch signature prunes absent ``lax.switch`` branches); the
``specialized`` section records the signature plus the ``specialize=False``
(fully grid-agnostic, all-branch) warm time for comparison.  Pass
``--no-specialize`` to benchmark the grid-agnostic program as the main
dispatch instead (CI runs both so the gate catches regressions on either
path).

The record also carries an ``async`` section: warm per-update throughput of
the jitted fully-async engine (``run_monte_carlo(mode="kasync")`` at K=1)
against the event-driven host-loop reference (``async_sim``) on the same
problem — the number ``check_bench.py`` gates at >= 5x alongside the warm
sweep-time rules.

The ``cold_cache`` section measures what the persistent compilation cache
(repro.core.cache) buys a production cold start: two FRESH subprocesses run
the same cold sweep dispatch against one cache directory — the first
populates it (``cold_uncached_s``), the second loads compiled executables
from disk (``cold_cached_s``; ``cached_added_entries == 0`` is the
compile-count-zero witness).  ``check_bench.py`` gates the ratio via
``--min-cold-cache-speedup`` and requires ``cold_cached_s < sweep_s.cold``
on full-grid records.  ``--cache-dir`` pins the directory (the CI
cache-persistence lane restores it across workflow runs via actions/cache);
the default is a throwaway temp dir so committed baselines always measure a
true first-ever cold start.  ``--skip-cold-probe`` omits the section.

    PYTHONPATH=src python benchmarks/sweep_bench.py [--smoke] [--out PATH]
                                                    [--no-specialize]
                                                    [--cache-dir DIR]
                                                    [--skip-cold-probe]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.async_sim import simulate_async_sgd
from repro.core.controller import (
    FixedKController,
    PflugController,
    ScheduleController,
    VarianceRatioController,
)
from repro.core.montecarlo import clear_program_cache, run_monte_carlo
from repro.core.straggler import Bimodal, Exponential, Pareto
from repro.core.sweep import SweepCase, clear_sweep_cache, grid_signature, run_sweep
from repro.core.theory import SGDSystem, switching_times
from repro.data import make_linreg_data
from repro.launch import mesh as mesh_lib

# Quickstart-scale cells (examples/quickstart.py): the sweep engine's target
# workload is *many scenarios of moderate size*, where per-cell trace +
# compile + dispatch overhead — not gemm flops — dominates the looped path.
D, M, N = 20, 400, 20
ITERS = 4000
REPLICAS = 32
EVAL_EVERY = 500


def _loss(params, X, y):
    r = X @ params - y
    return r * r


def _build_grid(data, eta, smoke: bool):
    k0, step, k_cap = 4, 4, 16
    stragglers = {
        "exp": Exponential(rate=1.0),
        "pareto": Pareto(x_m=0.5, alpha=1.5),
    }
    if not smoke:
        stragglers["bimodal"] = Bimodal(fast_mean=0.5, slow_mean=10.0, p_slow=0.1)
    controllers = {
        "pflug": PflugController(n_workers=N, k0=k0, step=step, thresh=10,
                                 burnin=40, k_max=k_cap),
        "fixed_k4": FixedKController(n_workers=N, k=k0),
    }
    if not smoke:
        controllers["fixed_k16"] = FixedKController(n_workers=N, k=k_cap)
        controllers["variance_ratio"] = VarianceRatioController(
            n_workers=N, k0=k0, step=step, burnin=40, k_max=k_cap)
        sysm = SGDSystem(eta=eta, L=1.0, c=0.1, sigma2=1.0, s=M // N,
                         F0_gap=10.0, n=N, straggler=stragglers["exp"])
        controllers["schedule"] = ScheduleController(
            n_workers=N, k0=k0, step=step,
            switch_times=switching_times(sysm, list(range(k0, k_cap, step)), step=step))
    return [
        SweepCase(ctrl, strag, eta=eta, label=f"{cname}|{sname}")
        for sname, strag in stragglers.items()
        for cname, ctrl in controllers.items()
    ]


def async_engine_vs_host(iters: int, replicas: int, seed: int = 0) -> dict:
    """Warm per-update throughput: jitted fully-async engine vs host loop.

    Runs ``run_monte_carlo(mode="kasync")`` at K=1 (cold to compile, then
    warm timed) for ``iters`` master updates x ``replicas`` replicas, then
    the event-driven ``simulate_async_sgd`` host loop for one seed over the
    same simulated horizon — the *same* stochastic process, so the host
    performs ~``iters`` updates.  The reported speedup is per *update*
    (host seconds/update over warm engine seconds/update/replica): the
    host's two device syncs per event are the floor the in-graph renewal
    formulation removes."""
    data = make_linreg_data(jax.random.PRNGKey(seed), m=M, d=D)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / M).max())
    eta = 0.05 / L  # async-stable at K=1 (see fig3's divergence note)
    w0 = jnp.zeros((D,))
    s = M // N
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), replicas)
    strag = Exponential(rate=1.0)
    ctrl = FixedKController(n_workers=N, k=1)
    eval_every = max(1, iters // 8)

    def engine():
        r = run_monte_carlo(
            _loss, w0, data.X, data.y, n_workers=N, controller=ctrl,
            straggler=strag, eta=eta, num_iters=iters, keys=keys,
            eval_every=eval_every, mode="kasync")
        jax.block_until_ready(r.loss)
        return r

    res = engine()  # cold: compile charged here, not to the warm number
    t0 = time.perf_counter()
    res = engine()
    engine_warm = time.perf_counter() - t0
    total_time = float(np.mean(np.asarray(res.time)[:, -1]))

    def grad_fn(params, worker):
        Xi = jax.lax.dynamic_slice_in_dim(data.X, worker * s, s, 0)
        yi = jax.lax.dynamic_slice_in_dim(data.y, worker * s, s, 0)
        return jax.grad(lambda p: jnp.mean((Xi @ p - yi) ** 2))(params)

    eval_fn = lambda p: jnp.mean(_loss(p, data.X, data.y))  # noqa: E731
    # Untimed warmup: grad_fn is jitted per worker index (static_argnums),
    # so the first pass pays n_workers compiles + the eval compile — charge
    # neither side's compile to the per-update comparison.
    simulate_async_sgd(
        grad_fn, eval_fn, w0, n_workers=N, eta=eta, straggler=strag,
        total_time=total_time / 10.0, key=jax.random.PRNGKey(seed + 3),
        eval_every=eval_every)
    t0 = time.perf_counter()
    h = simulate_async_sgd(
        grad_fn, eval_fn, w0, n_workers=N, eta=eta, straggler=strag,
        total_time=total_time, key=jax.random.PRNGKey(seed + 2),
        eval_every=eval_every)
    host_s = time.perf_counter() - t0
    host_updates = int(h["updates"][-1]) if h["updates"] else 1
    speedup = (host_s / host_updates) / (engine_warm / (iters * replicas))
    return {
        "engine_warm_s": round(engine_warm, 3),
        "host_s": round(host_s, 3),
        "updates": iters,
        "replicas": replicas,
        "host_updates": host_updates,
        "speedup_per_update": round(speedup, 1),
    }


def cold_probe(smoke: bool, specialize: bool) -> None:
    """``--cold-probe`` entry: ONE cold sweep dispatch of the bench grid in
    THIS (expected fresh) process, with the persistent compilation cache
    where ``JAX_COMPILATION_CACHE_DIR`` puts it.  Prints a one-line JSON
    record — wall seconds plus the cache-entry delta (the observable XLA
    compile count: 0 means every executable loaded from disk) — and exits.
    ``run()`` spawns this twice against one directory to measure
    uncached-vs-cached cold start."""
    from repro.core.cache import cache_entries, setup_compilation_cache

    cache_dir = setup_compilation_cache()
    entries_before = cache_entries(cache_dir)
    iters = 200 if smoke else ITERS
    replicas = 8 if smoke else REPLICAS
    data = make_linreg_data(jax.random.PRNGKey(0), m=M, d=D)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / M).max())
    eta = 0.5 / L
    w0 = jnp.zeros((D,))
    keys = jax.random.split(jax.random.PRNGKey(1), replicas)
    cases = _build_grid(data, eta, smoke)
    t0 = time.perf_counter()
    res = run_sweep(_loss, w0, data.X, data.y, n_workers=N, cases=cases,
                    num_iters=iters, keys=keys, eval_every=EVAL_EVERY,
                    specialize=specialize)
    jax.block_until_ready(res.loss)
    cold_s = time.perf_counter() - t0
    print(json.dumps({
        "cold_s": round(cold_s, 3),
        "entries_before": entries_before,
        "added_entries": cache_entries(cache_dir) - entries_before,
    }))


def _run_cold_probe(smoke: bool, specialize: bool, cache_dir: str) -> dict:
    """Spawn ``--cold-probe`` as a FRESH python process (a true cold start:
    no in-memory program cache, no jit cache, only the disk cache survives)
    and parse its JSON line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--cold-probe"]
    if smoke:
        cmd.append("--smoke")
    if not specialize:
        cmd.append("--no-specialize")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    out = subprocess.run(cmd, capture_output=True, text=True, env=env, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def measure_cold_cache(smoke: bool, specialize: bool, cache_dir: str | None) -> dict:
    """The ``cold_cache`` record section: cold-start wall time without and
    with a warmed persistent cache, via two fresh subprocesses sharing one
    cache directory.  With ``cache_dir`` pinned (CI's actions/cache lane)
    the directory may arrive pre-warmed — then the first probe already hits
    (``uncached_added_entries == 0``) and the uncached-vs-cached ratio is
    meaningless; ``check_bench.py`` skips the ratio gate in that case but
    still enforces ``cached_added_entries == 0``.

    The probes are child processes that need the device, so this refuses to
    run from a parent that holds an accelerator (the parent has touched jax
    and keeps the chip): pass ``--skip-cold-probe`` there."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "the cold-cache probe starts child processes that need the "
            f"device, but this process holds the {jax.default_backend()} "
            "backend; rerun with --skip-cold-probe"
        )
    if cache_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="repro-xla-cache-")
        cache_dir, ctx = tmp.name, tmp
    else:
        os.makedirs(cache_dir, exist_ok=True)
        ctx = None
    try:
        first = _run_cold_probe(smoke, specialize, cache_dir)
        second = _run_cold_probe(smoke, specialize, cache_dir)
    finally:
        if ctx is not None:
            ctx.cleanup()
    return {
        "cache_dir_prewarmed": first["entries_before"] > 0,
        "cold_uncached_s": first["cold_s"],
        "cold_cached_s": second["cold_s"],
        "uncached_added_entries": first["added_entries"],
        "cached_added_entries": second["added_entries"],
    }


def run(
    out_path: str = "results/BENCH_sweep.json",
    smoke: bool = False,
    specialize: bool = True,
    cache_dir: str | None = None,
    skip_cold_probe: bool = False,
):
    iters = 200 if smoke else ITERS
    replicas = 8 if smoke else REPLICAS
    data = make_linreg_data(jax.random.PRNGKey(0), m=M, d=D)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / M).max())
    eta = 0.5 / L
    w0 = jnp.zeros((D,))
    keys = jax.random.split(jax.random.PRNGKey(1), replicas)
    cases = _build_grid(data, eta, smoke)
    sig = grid_signature(cases, N)

    def looped():
        outs = []
        for c in cases:
            outs.append(run_monte_carlo(
                _loss, w0, data.X, data.y, n_workers=N, controller=c.controller,
                straggler=c.straggler, eta=c.eta, num_iters=iters, keys=keys,
                eval_every=EVAL_EVERY))
        jax.block_until_ready([o.loss for o in outs])
        return outs

    def sweep(spec):
        res = run_sweep(_loss, w0, data.X, data.y, n_workers=N, cases=cases,
                        num_iters=iters, keys=keys, eval_every=EVAL_EVERY,
                        specialize=spec)
        jax.block_until_ready(res.loss)
        return res

    clear_program_cache()
    t0 = time.perf_counter(); refs = looped(); looped_cold = time.perf_counter() - t0
    clear_sweep_cache()
    t0 = time.perf_counter(); res = sweep(specialize); sweep_cold = time.perf_counter() - t0
    sweep(not specialize)  # compile the other dispatch mode untimed
    # Warm numbers are best-of-two cache-hot runs, INTERLEAVED across the
    # three paths: back-to-back runs of one path systematically favor
    # whichever ran in the quieter window on the 2-core reference host, and
    # the warm gates police ~5% effects.  Interleaving gives every path the
    # same thermal/contention exposure, so the ratios stay unbiased.
    paths = {
        "looped": looped,
        "main": lambda: sweep(specialize),
        "other": lambda: sweep(not specialize),
    }
    warm = {name: [] for name in paths}
    for _ in range(2):
        for name, fn in paths.items():
            t0 = time.perf_counter(); fn(); warm[name].append(time.perf_counter() - t0)
    looped_warm = min(warm["looped"])
    sweep_warm = min(warm["main"])
    other_warm = min(warm["other"])
    spec_warm = sweep_warm if specialize else other_warm
    unspec_warm = other_warm if specialize else sweep_warm
    async_rec = async_engine_vs_host(
        iters=200 if smoke else 2000, replicas=replicas)
    cold_cache = (
        None if skip_cold_probe
        else measure_cold_cache(smoke, specialize, cache_dir)
    )

    bitwise = all(
        np.array_equal(np.asarray(res.time[g]), np.asarray(r.time))
        and np.array_equal(np.asarray(res.loss[g]), np.asarray(r.loss))
        and np.array_equal(np.asarray(res.k[g]), np.asarray(r.k))
        for g, r in enumerate(refs)
    )

    record = {
        "name": "sweep_bench",
        "smoke": smoke,
        "grid": {
            "labels": [c.name() for c in cases],
            "n_cells": len(cases),
            "n_workers": N,
            "m": M,
            "d": D,
        },
        "n_replicas": replicas,
        "num_iters": iters,
        "eval_every": EVAL_EVERY,
        "looped_s": {"cold": round(looped_cold, 3), "warm": round(looped_warm, 3)},
        # the engine's benchmarked dispatch: specialize=True unless
        # --no-specialize was passed (see the "specialized" section).
        "sweep_s": {"cold": round(sweep_cold, 3), "warm": round(sweep_warm, 3)},
        # old-vs-new: the pre-cache engine re-traced every call, so the old
        # grid loop is the cold looped path; the sweep's one-time compile is
        # charged to it symmetrically.
        "speedup": round(looped_cold / sweep_cold, 3),
        "speedup_warm": round(looped_warm / sweep_warm, 3),
        "bitwise_equal": bitwise,
        # branch-signature specialization: what the benchmarked grid's
        # signature is, and how the pruned program compares warm against the
        # fully-grid-agnostic (specialize=False, all-branch) program.
        "specialized": {
            "enabled": specialize,
            "signature": {
                "ctrl_kinds": list(sig.ctrl_kinds),
                "modes": list(sig.modes),
                "with_schedule": sig.with_schedule,
                "with_comm": sig.with_comm,
            },
            "warm_s": round(spec_warm, 3),
            "unspecialized_warm_s": round(unspec_warm, 3),
            "specialization_speedup": round(unspec_warm / spec_warm, 3),
        },
        # jitted K-async engine vs the event-driven host loop (per update);
        # check_bench gates speedup_per_update >= 5x.
        "async": async_rec,
        "backend": jax.default_backend(),
        "n_devices": jax.local_device_count(),
        # 2-D dispatch topology: the (cells, replicas) mesh shape the sweep
        # resolves for this grid, and the process count it spans (1 unless
        # jax.distributed is initialized).  check_bench rejects records
        # with n_devices > 1 but no mesh_shape (partial migration).
        "mesh_shape": list(mesh_lib.sweep_mesh_shape(
            jax.device_count(), len(cases), replicas)),
        "n_processes": jax.process_count(),
        "jax_version": jax.__version__,
    }
    if cold_cache is not None:
        # fresh-subprocess cold start, uncached vs warmed persistent cache
        # (see module docstring); gated by check_bench --min-cold-cache-speedup.
        record["cold_cache"] = cold_cache
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    cold_tag = (
        f"cold_cached={cold_cache['cold_cached_s']:.2f}s;"
        if cold_cache is not None else ""
    )
    return {
        "name": "sweep_bench",
        "us_per_call": sweep_cold * 1e6,
        "derived": f"cells={len(cases)};replicas={replicas};iters={iters};"
                   f"specialize={specialize};"
                   f"speedup={record['speedup']:.2f}x;"
                   f"speedup_warm={record['speedup_warm']:.2f}x;"
                   f"spec_vs_unspec={record['specialized']['specialization_speedup']:.2f}x;"
                   f"async_speedup={async_rec['speedup_per_update']:.0f}x;"
                   f"{cold_tag}"
                   f"bitwise_equal={bitwise}",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid + short runs (CI-friendly)")
    ap.add_argument("--no-specialize", action="store_true",
                    help="benchmark the fully-grid-agnostic (all-branch) "
                         "program as the main dispatch")
    ap.add_argument("--out", default="results/BENCH_sweep.json")
    ap.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="persistent-cache directory for the cold-cache "
                         "probes (default: a throwaway temp dir; CI pins "
                         "this to an actions/cache-restored path)")
    ap.add_argument("--skip-cold-probe", action="store_true",
                    help="omit the cold_cache section (no subprocesses)")
    ap.add_argument("--cold-probe", action="store_true",
                    help="internal: run ONE cold dispatch in this process "
                         "against $JAX_COMPILATION_CACHE_DIR and print its "
                         "JSON line")
    args = ap.parse_args()
    if args.cold_probe:
        cold_probe(smoke=args.smoke, specialize=not args.no_specialize)
        return
    from repro.core.cache import setup_compilation_cache

    setup_compilation_cache()
    print(json.dumps(
        run(args.out, smoke=args.smoke, specialize=not args.no_specialize,
            cache_dir=args.cache_dir, skip_cold_probe=args.skip_cold_probe),
        indent=2,
    ))


if __name__ == "__main__":
    main()
