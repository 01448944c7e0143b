"""Chip smoke test: drive the main paths once on one TPU and check what comes out.

    python chip_smoke.py               # one chip: sweep and train phases
    python chip_smoke.py --four-chips  # baseline grid on a 4-chip mesh vs 1 chip

Phases (one process; nothing else may hold the chip):

* sweep — one ``run_sweep`` dispatch of the committed baseline grid
  (benchmarks/sweep_bench.py: 5 controllers x 3 straggler families, n=20,
  m=400, d=20, R=32, 4000 iterations, eval every 500), then a small grid with
  a sync, a kasync, a kbatch and a geometric-median cell so every mode branch
  compiles.  One cell per mode is checked bit for bit against the looped
  ``run_monte_carlo``; losses must be finite and the small grid's Pflug cell
  must raise k above k0.
* train — ``repro.launch.train.main`` on the full-width, full-depth
  qwen1.5-0.5b config (sync fastest-k, Pflug, 4 workers, batch 8 x seq 512,
  6 steps, random weights from a seed).  Every ce is finite, step 0's ce is
  within 10% of ln(vocab), the last ce is below the first, and k stays in
  [1, 4].  Compile time, the median warm step time, tokens/s and the device's
  peak_bytes_in_use are printed for the record.
* --four-chips — only the baseline grid, on a ("cells", "replicas") mesh
  over 4 chips and on a mesh over 1 chip; the two must be equal bit for bit.

The compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``; the entry counts before and after are printed.
Every result line goes to stdout before the last one, which is the JSON
verdict ``{"ok": true, "device": {...}}``.  Off the TPU, or outside a
checkout of the repository, the script exits non-zero and prints no verdict.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
import traceback

_ROOT = os.path.dirname(os.path.abspath(__file__))


def _log(msg: str, **fields) -> None:
    print(json.dumps({"phase": msg, **fields}), flush=True)


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _diff(a, b) -> dict:
    """{} when the two results are equal bit for bit, else, per field that
    differs, how many entries differ, the first, and the largest gap."""
    import numpy as np

    out = {}
    for f in ("time", "loss", "k"):
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        bad = x != y
        if bad.any():
            d = {"entries": int(bad.sum()), "of": int(bad.size),
                 "first": np.argwhere(bad)[0].tolist(),
                 "max_abs": float(np.max(np.abs(x.astype(np.float64) - y)))}
            if x.dtype == np.float32:
                xi, yi = (v.view(np.int32).astype(np.int64) for v in (x, y))
                d["max_ulp"] = int(np.max(np.abs(xi - yi)))
            out[f] = d
    return out


def _baseline_problem():
    import jax
    import jax.numpy as jnp

    from benchmarks import sweep_bench as sb
    from repro.data import make_linreg_data

    data = make_linreg_data(jax.random.PRNGKey(0), m=sb.M, d=sb.D)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / sb.M).max())
    eta = 0.5 / L
    keys = jax.random.split(jax.random.PRNGKey(1), sb.REPLICAS)
    return sb, data, eta, keys, sb._build_grid(data, eta, smoke=False)


def _run_baseline(sb, data, keys, cases, mesh=None):
    import jax
    import jax.numpy as jnp

    from repro.core.sweep import run_sweep

    t0 = time.perf_counter()
    res = run_sweep(sb._loss, jnp.zeros((sb.D,)), data.X, data.y,
                    n_workers=sb.N, cases=cases, num_iters=sb.ITERS, keys=keys,
                    eval_every=sb.EVAL_EVERY, mesh=mesh)
    jax.block_until_ready(res.loss)
    return res, time.perf_counter() - t0


def _looped(loss, data, n, case, num_iters, keys, eval_every):
    import jax.numpy as jnp

    from repro.core.montecarlo import run_monte_carlo

    return run_monte_carlo(
        loss, jnp.zeros((data.X.shape[1],)), data.X, data.y, n_workers=n,
        controller=case.controller, straggler=case.straggler, eta=case.eta,
        comm=case.comm, num_iters=num_iters, keys=keys, eval_every=eval_every,
        mode=case.mode, fault=case.fault, agg=case.agg, agg_param=case.agg_param,
    )


def phase_sweep() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.controller import FixedKController, PflugController
    from repro.core.faults import byzantine_plan
    from repro.core.montecarlo import MonteCarloResult
    from repro.core.straggler import Exponential, Pareto
    from repro.core.sweep import SweepCase, run_sweep

    sb, data, eta, keys, cases = _baseline_problem()
    res, cold_s = _run_baseline(sb, data, keys, cases)
    _, warm_s = _run_baseline(sb, data, keys, cases)
    cell_iters = len(cases) * keys.shape[0] * sb.ITERS
    _log("sweep_baseline", cells=len(cases), replicas=int(keys.shape[0]),
         iters=sb.ITERS, first_dispatch_s=cold_s, warm_dispatch_s=warm_s,
         warm_cell_iters_per_s=cell_iters / warm_s)
    _check(bool(np.isfinite(np.asarray(res.loss)).all()), "baseline losses not finite")
    _log("sweep_baseline_k_final", **{
        c.label: float(np.asarray(res.k[g])[:, -1].mean()) for g, c in enumerate(cases)
    })
    g = next(i for i, c in enumerate(cases) if c.label == "pflug|exp")
    ref = _looped(sb._loss, data, sb.N, cases[g], sb.ITERS, keys, sb.EVAL_EVERY)
    cell = MonteCarloResult(time=res.time[g], loss=res.loss[g], k=res.k[g],
                            iteration=res.iteration)
    diffs = {"pflug|exp": _diff(cell, ref)}
    _log("sweep_baseline_vs_looped", cell="pflug|exp", diff=diffs["pflug|exp"])

    # Every execution mode and the in-graph Weiszfeld aggregator in one grid.
    # The baseline's Pflug cells hold k0 for all 4000 iterations: their long
    # transient drives the sign counter far below the threshold.  At three
    # times the step size the transient ends within a few hundred iterations
    # and the test must raise k; that is the adaptation check.
    n, iters, every = sb.N, 400, 100
    mkeys = keys[:8]
    pflug = PflugController(n_workers=n, k0=4, step=4, thresh=10, burnin=40)
    mode_cases = [
        SweepCase(pflug, Exponential(rate=1.0), 3 * eta, label="sync_pflug_3eta"),
        SweepCase(FixedKController(n_workers=n, k=4), Pareto(x_m=0.5, alpha=1.5),
                  eta, label="kasync_k4", mode="kasync"),
        SweepCase(FixedKController(n_workers=n, k=4), Exponential(rate=1.0), eta,
                  label="kbatch_k4", mode="kbatch"),
        SweepCase(FixedKController(n_workers=n, k=8), Exponential(rate=1.0), eta,
                  label="flip_geomedian", agg="geomedian",
                  fault=byzantine_plan(n, 0.2, "sign_flip")),
    ]
    t0 = time.perf_counter()
    mres = run_sweep(sb._loss, jnp.zeros((sb.D,)), data.X, data.y,
                     n_workers=n, cases=mode_cases, num_iters=iters, keys=mkeys,
                     eval_every=every)
    modes_s = time.perf_counter() - t0
    _log("sweep_modes", cells=[c.label for c in mode_cases], first_dispatch_s=modes_s,
         k_max=int(np.asarray(mres.k[0]).max()))
    for g, c in enumerate(mode_cases):
        ref = _looped(sb._loss, data, n, c, iters, mkeys, every)
        cell = MonteCarloResult(time=mres.time[g], loss=mres.loss[g], k=mres.k[g],
                                iteration=mres.iteration)
        diffs[c.label] = _diff(cell, ref)
        _log("sweep_modes_vs_looped", cell=c.label, diff=diffs[c.label])
    _check(bool(np.isfinite(np.asarray(mres.loss)).all()), "mode-grid losses not finite")
    _check(int(np.asarray(mres.k[0]).max()) > pflug.k0,
           f"sync_pflug_3eta: k never rose above k0={pflug.k0}")
    bad = {label: d for label, d in diffs.items() if d}
    _check(not bad, f"sweep cells differ from the looped engine: {bad}")


def phase_train() -> None:
    import jax

    from repro.configs import get_config
    from repro.launch import train

    arch, batch, seq, workers = "qwen1.5-0.5b", 8, 512, 4
    out = train.main([
        "--arch", arch, "--batch", str(batch), "--seq", str(seq),
        "--n-workers", str(workers), "--controller", "pflug", "--mode", "sync",
        "--steps", "6", "--log-every", "1", "--seed", "0",
    ])
    steps = out["steps"]
    ces = [s["ce"] for s in steps]
    ks = [s["k"] for s in steps]
    _check(len(steps) == 6, f"expected 6 steps, got {len(steps)}")
    warm = statistics.median(s["step_s"] for s in steps[1:])
    stats = jax.devices()[0].memory_stats() or {}
    _log("train", arch=arch, batch=batch, seq=seq, ce=ces, k=ks,
         compile_s=out["compile_s"], median_warm_step_s=warm,
         tokens_per_s=batch * seq / warm,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"))
    _check(all(math.isfinite(c) for c in ces), f"non-finite ce: {ces}")
    ln_v = math.log(get_config(arch).vocab_size)
    _check(abs(ces[0] - ln_v) <= 0.1 * ln_v,
           f"step-0 ce {ces[0]} not within 10% of ln(vocab)={ln_v}")
    _check(ces[-1] < ces[0], f"ce did not fall: {ces}")
    _check(all(1 <= k <= workers for k in ks), f"k outside [1, {workers}]: {ks}")


def phase_four_chips() -> None:
    import jax

    from repro.launch import mesh as mesh_lib

    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    sb, data, _, keys, cases = _baseline_problem()
    mesh4 = mesh_lib.make_sweep_mesh(len(cases), keys.shape[0], devices=devices)
    mesh1 = mesh_lib.make_sweep_mesh(len(cases), keys.shape[0], devices=devices[:1])
    res4, cold4 = _run_baseline(sb, data, keys, cases, mesh=mesh4)
    _, warm4 = _run_baseline(sb, data, keys, cases, mesh=mesh4)
    res1, cold1 = _run_baseline(sb, data, keys, cases, mesh=mesh1)
    _, warm1 = _run_baseline(sb, data, keys, cases, mesh=mesh1)
    diff = _diff(res4, res1)
    _log("sweep_four_vs_one_chip", mesh4=dict(mesh4.shape), mesh1=dict(mesh1.shape),
         first_dispatch_s={"4": cold4, "1": cold1},
         warm_dispatch_s={"4": warm4, "1": warm1}, diff=diff)
    _check(not diff, f"4-chip and 1-chip baseline results differ: {diff}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the baseline grid on 4 chips vs 1 chip")
    args = ap.parse_args(argv)

    src = os.path.join(_ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repository checkout next to {__file__}", file=sys.stderr)
        return 2
    sys.path[:0] = [src, _ROOT]

    from repro.core import cache

    cache_dir = cache.setup_compilation_cache()  # before the first compile
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax sees {dev.platform}); nothing was run",
              file=sys.stderr)
        return 1
    entries_before = cache.cache_entries(cache_dir)
    _log("start", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), jax=jax.__version__, cache_dir=cache_dir,
         cache_entries=entries_before)
    phases = [phase_four_chips] if args.four_chips else [phase_sweep, phase_train]
    failed = []
    for phase in phases:  # every phase runs, so one failure hides no other
        t0 = time.perf_counter()
        try:
            phase()
        except SmokeFailure as e:
            print(f"chip_smoke: {phase.__name__} failed: {e}", file=sys.stderr)
            failed.append(phase.__name__)
        except Exception:
            traceback.print_exc()
            print(f"chip_smoke: {phase.__name__} raised", file=sys.stderr)
            failed.append(phase.__name__)
        _log(phase.__name__ + "_done", seconds=time.perf_counter() - t0)
    if failed:
        return 1
    _log("cache", cache_dir=cache_dir, entries_before=entries_before,
         added_entries=cache.cache_entries(cache_dir) - entries_before)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
