"""End-to-end training driver: adaptive fastest-k SGD on any registered arch.

Runs the same train_step program the dry-run lowers, on whatever devices are
available (a CPU host mesh for the runnable examples; the production mesh on
a real pod).  Logs loss / k / simulated wall-clock, checkpoints periodically.

The LM loop and the simulation engines share ONE step implementation: the
train step is traced from ``repro.core.execmode.make_mode_steps`` (the same
per-mode builders ``run_monte_carlo``/``run_sweep`` trace), so ``--mode
kasync``/``--mode kbatch`` run the async execution modes around the real LM
loss with no duplicated fastest-k/staleness logic.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
        --steps 200 --batch 16 --seq 128 --controller pflug

``--simulate`` switches to the paper-scale simulation entry instead of LM
training: a controller x straggler grid of Monte-Carlo replicas on the
synthetic linear-regression task, run as ONE compiled dispatch through the
sweep engine (`repro.core.sweep`) and sharded across local devices:

    PYTHONPATH=src python -m repro.launch.train --simulate \
        --sim-controllers pflug,fixed --sim-stragglers exponential,pareto \
        --steps 4000 --replicas 16 --n-workers 20
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import checkpoint
from repro.configs import get_config, get_smoke_config, list_archs
from repro.core import theory
from repro.core.aggregation import CommModel
from repro.core.controller import get_controller
from repro.core.straggler import get_straggler_model
from repro.data import TokenStream
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.launch import steps as steps_lib
from repro.models import build_model
from repro.optim import get_optimizer
from repro.shardctx import activation_sharding


def _parse_pair(spec, flag, cast=float):
    try:
        a, b = spec.split(":")
        return cast(a), cast(b)
    except ValueError:
        raise SystemExit(f"{flag} expects 'A:B', got {spec!r}")


def _run_simulation(args):
    """The train CLI's simulation entry: a grid sweep as one dispatch.

    ``--sim-n-grid`` makes the worker count an ordinary grid axis (cells are
    padded to the largest n; smaller-n cells hold the extra slots inactive).
    ``--sim-mode`` picks the execution mode (sync fastest-k, K-async,
    K-batch-async; a comma list makes mode a grid axis — every arm still
    runs in the same single dispatch).
    ``--sim-hetero FRAC:FACTOR`` swaps the straggler axis for a two-speed
    exponential fleet — a FRAC fraction of each cell's workers is FACTOR x
    slower — and ``--sim-drift T:SCALE`` adds a fleet-wide mid-run rate
    drift (every rate is multiplied by SCALE at simulated time T).
    ``--sim-fault FAMILY:FRAC:ONSET[:PARAM]`` injects a per-worker fault
    plan — a FRAC fraction of each cell's workers turns faulty (sign_flip /
    rescale / random_gauss / crash) once simulated time reaches ONSET — and
    ``--sim-agg`` picks the gradient aggregator (eq.-(2) weighted mean or a
    robust alternative).  Comma lists sweep either as grid axes (labels get
    ``|{fault}`` / ``|{agg}``), still in the same single dispatch.
    """
    from repro.core.aggregation import AGG_KINDS
    from repro.core.execmode import MODES
    from repro.core.faults import byzantine_plan
    from repro.core.straggler import Exponential, RateSchedule, WorkerFleet
    from repro.core.sweep import SweepCase, run_sweep, summarize_cells
    from repro.data import make_linreg_data

    m, d = args.sim_m, args.sim_d
    if args.sim_n_grid:
        n_values = sorted({int(v) for v in args.sim_n_grid.split(",") if v})
    else:
        n_values = [args.n_workers]
    n_slots = max(n_values)
    if m % n_slots:
        raise SystemExit(f"--sim-m {m} must be divisible by the largest n "
                         f"({n_slots})")
    data = make_linreg_data(jax.random.PRNGKey(args.seed), m=m, d=d)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / m).max())
    eta = 0.5 / L
    ctrl_names = [c for c in args.sim_controllers.split(",") if c]

    drift = None
    if args.sim_drift:
        t_drift, scale = _parse_pair(args.sim_drift, "--sim-drift")
        drift = RateSchedule(times=(t_drift,), scales=(scale,))

    def stragglers_for(n):
        """{label: straggler spec} for an n-active-worker cell."""
        if args.sim_hetero:
            frac, factor = _parse_pair(args.sim_hetero, "--sim-hetero")
            if not 0.0 <= frac <= 1.0 or factor <= 0:
                raise SystemExit(f"--sim-hetero: bad FRAC:FACTOR {args.sim_hetero!r}")
            n_slow = int(round(frac * n))
            fleet = WorkerFleet(
                models=(Exponential(rate=1.0),) * (n - n_slow)
                + (Exponential(rate=1.0 / factor),) * n_slow,
                schedule=drift,
            )
            return {f"two_speed{args.sim_hetero}": fleet}
        out = {}
        for sname in (s for s in args.sim_stragglers.split(",") if s):
            model = get_straggler_model(sname)
            if drift is not None:
                out[sname] = WorkerFleet(models=(model,) * n, schedule=drift)
            else:
                out[sname] = model
        return out

    def make_controller(name, straggler, n):
        if name == "pflug":
            return get_controller("pflug", n, k0=args.k0, step=args.k_step,
                                  thresh=args.thresh, burnin=args.burnin)
        if name == "sketched_pflug":
            return get_controller("sketched_pflug", n, k0=args.k0,
                                  step=args.k_step, thresh=args.thresh,
                                  burnin=args.burnin, sketch_dim=args.sketch_dim)
        if name == "fixed":
            if args.fixed_k > n:
                raise SystemExit(f"--fixed-k {args.fixed_k} > n={n}")
            return get_controller("fixed", n, k=args.fixed_k)
        if name == "variance_ratio":
            return get_controller("variance_ratio", n, k0=args.k0,
                                  step=args.k_step, burnin=args.burnin)
        if name == "schedule":
            sysm = theory.SGDSystem(
                eta=eta, L=args.schedule_smoothness,
                c=args.schedule_strong_convexity, sigma2=args.schedule_sigma2,
                s=m // n_slots, F0_gap=args.schedule_f0_gap, n=n,
                straggler=straggler,
            )
            times = theory.switching_times(
                sysm, list(range(args.k0, n, args.k_step)), step=args.k_step)
            return get_controller("schedule", n, switch_times=times,
                                  k0=args.k0, step=args.k_step)
        raise SystemExit(f"--sim-controllers: unknown controller {name!r}")

    comm = CommModel(alpha=args.comm_alpha, beta=args.comm_beta)
    modes = [mm for mm in args.sim_mode.split(",") if mm]
    for mm in modes:
        if mm not in MODES:
            raise SystemExit(f"--sim-mode: unknown mode {mm!r}; "
                             f"options {sorted(MODES)}")
    if not modes:
        raise SystemExit("--sim-mode: need at least one mode")

    # --sim-fault: each spec is FAMILY:FRAC:ONSET[:PARAM] or the literal
    # "none" (the fault-free arm of a Byzantine sweep).
    fault_specs = ([s for s in args.sim_fault.split(",") if s]
                   if args.sim_fault else ["none"])
    parsed_faults = []
    for spec in fault_specs:
        if spec == "none":
            parsed_faults.append((spec, None))
            continue
        parts = spec.split(":")
        if len(parts) not in (3, 4):
            raise SystemExit(f"--sim-fault expects FAMILY:FRAC:ONSET[:PARAM] "
                             f"or 'none', got {spec!r}")
        try:
            cfg = (parts[0], float(parts[1]), float(parts[2]),
                   float(parts[3]) if len(parts) == 4 else 1.0)
        except ValueError:
            raise SystemExit(f"--sim-fault: bad numbers in {spec!r}")
        parsed_faults.append((spec, cfg))

    def make_plan(cfg, n):
        if cfg is None:
            return None
        family, frac, onset, param = cfg
        try:
            return byzantine_plan(n, frac, family, onset=onset, param=param)
        except ValueError as e:
            raise SystemExit(f"--sim-fault: {e}")

    aggs = [a for a in args.sim_agg.split(",") if a]
    for a in aggs:
        if a not in AGG_KINDS:
            raise SystemExit(f"--sim-agg: unknown aggregator {a!r}; "
                             f"options {sorted(AGG_KINDS)}")
    if not aggs:
        raise SystemExit("--sim-agg: need at least one aggregator")
    if "kbatch" in modes and any(a != "mean" for a in aggs):
        raise SystemExit("--sim-agg: robust aggregation is not supported in "
                         "kbatch mode (drop kbatch from --sim-mode)")

    n_tag = lambda n: f"|n{n}" if len(n_values) > 1 else ""
    mode_tag = lambda mm: f"|{mm}" if len(modes) > 1 else ""
    fault_tag = lambda ft: f"|{ft}" if len(parsed_faults) > 1 else ""
    agg_tag = lambda a: f"|{a}" if len(aggs) > 1 else ""
    cases = [
        SweepCase(make_controller(cname, strag, n), strag, eta=eta, comm=comm,
                  label=(f"{cname}|{sname}{n_tag(n)}{mode_tag(mm)}"
                         f"{fault_tag(ftag)}{agg_tag(agg)}"),
                  mode=mm, fault=make_plan(fcfg, n), agg=agg)
        for mm in modes
        for n in n_values
        for sname, strag in stragglers_for(n).items()
        for cname in ctrl_names
        for ftag, fcfg in parsed_faults
        for agg in aggs
    ]
    t0 = time.time()
    stats = summarize_cells(run_sweep(
        (lambda w, X, y: (X @ w - y) ** 2),
        jnp.zeros((d,)), data.X, data.y, n_workers=n_slots, cases=cases,
        num_iters=args.steps, key=jax.random.PRNGKey(args.seed + 1),
        n_replicas=args.replicas, eval_every=args.sim_eval_every,
    ))
    wall = time.time() - t0
    print(json.dumps({
        "grid_cells": len(cases), "replicas": args.replicas,
        "iters": args.steps, "dispatches": 1,
        "devices": jax.device_count(),
        "processes": jax.process_count(),
        "mesh_shape": list(mesh_lib.sweep_mesh_shape(
            jax.device_count(), len(cases), args.replicas)),
        "wall_s": round(wall, 2),
    }))
    for label, s in stats.items():
        print(json.dumps({
            "cell": label,
            "final_excess": float(s["loss_mean"][-1] - data.f_star),
            "final_excess_ci95": float(s["loss_ci95"][-1]),
            "sim_time": round(float(s["time_mean"][-1]), 2),
            "k_final": round(float(s["k_mean"][-1]), 2),
        }, ), flush=True)
    if args.sim_csv:
        with open(args.sim_csv, "w") as f:
            f.write("cell,iteration,time_mean,time_ci95,loss_mean,loss_ci95,k_mean\n")
            for label, s in stats.items():
                for i in range(len(s["iteration"])):
                    f.write(f"{label},{s['iteration'][i]},{s['time_mean'][i]:.3f},"
                            f"{s['time_ci95'][i]:.4f},{s['loss_mean'][i]:.6g},"
                            f"{s['loss_ci95'][i]:.6g},{s['k_mean'][i]:.2f}\n")
        print(f"wrote {args.sim_csv}")


def main(argv=None):
    """Parse ``argv`` and train (or ``--simulate``).  An LM run returns
    ``{"compile_s": ..., "steps": [per-step metric dicts]}``: each step's
    ``ce``, ``k``, simulated ``sim_time``/``iter_time``, ``step_s``, the
    step's wall time to ``block_until_ready``, and ``traces``/``compiles``,
    what ``cache.compile_stats()`` rose by over the whole step, batch draw
    included (the step that recompiled shows a non-zero count)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-workers", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--controller", default="pflug",
                    choices=["pflug", "sketched_pflug", "fixed", "schedule",
                             "variance_ratio"])
    ap.add_argument("--k0", type=int, default=1)
    ap.add_argument("--k-step", type=int, default=1)
    ap.add_argument("--thresh", type=int, default=10)
    ap.add_argument("--burnin", type=int, default=20)
    ap.add_argument("--fixed-k", type=int, default=2)
    ap.add_argument("--sketch-dim", type=int, default=64,
                    help="sketched_pflug: dimension of the gradient sketch")
    # --controller schedule: Theorem-1 switch times need the SGD system
    # constants, which are not identifiable from an LM run — supply estimates.
    ap.add_argument("--schedule-smoothness", type=float, default=1.0,
                    help="schedule: L (Lipschitz-smoothness estimate)")
    ap.add_argument("--schedule-strong-convexity", type=float, default=0.1,
                    help="schedule: c (strong-convexity estimate)")
    ap.add_argument("--schedule-sigma2", type=float, default=1.0,
                    help="schedule: per-sample gradient variance estimate")
    ap.add_argument("--schedule-f0-gap", type=float, default=10.0,
                    help="schedule: F(w0) - F* estimate")
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "kasync", "kbatch"],
                    help="LM training execution mode (same per-mode step "
                         "builders the sim engines trace)")
    ap.add_argument("--straggler", default="exponential",
                    choices=["exponential", "shifted_exponential", "pareto",
                             "bimodal", "deterministic"])
    ap.add_argument("--comm-alpha", type=float, default=0.0)
    ap.add_argument("--comm-beta", type=float, default=0.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 production mesh (requires 256 devices)")
    # --- simulation entry (paper-scale linreg sweep instead of LM training)
    ap.add_argument("--simulate", action="store_true",
                    help="run a controller x straggler Monte-Carlo sweep on the "
                         "paper's synthetic linreg task (one compiled dispatch "
                         "via repro.core.sweep) instead of LM training")
    ap.add_argument("--sim-controllers", default="pflug,fixed",
                    help="comma list from {pflug,sketched_pflug,fixed,"
                         "schedule,variance_ratio}")
    ap.add_argument("--sim-stragglers", default="exponential,pareto",
                    help="comma list of registered straggler models")
    ap.add_argument("--sim-hetero", default=None, metavar="FRAC:FACTOR",
                    help="simulate: replace the straggler axis with a "
                         "two-speed exponential fleet — FRAC of each cell's "
                         "workers run FACTOR x slower (e.g. 0.3:4)")
    ap.add_argument("--sim-drift", default=None, metavar="T:SCALE",
                    help="simulate: fleet-wide rate drift — multiply every "
                         "worker's rate by SCALE at simulated time T "
                         "(e.g. 500:0.4)")
    ap.add_argument("--sim-mode", default="sync", metavar="MODE[,MODE..]",
                    help="simulate: execution mode(s) from {sync,kasync,"
                         "kbatch}; a comma list sweeps mode as a grid axis "
                         "(async modes apply stale gradients, k = arrivals "
                         "per master update)")
    ap.add_argument("--sim-fault", default=None,
                    metavar="FAMILY:FRAC:ONSET[:PARAM]",
                    help="simulate: per-worker fault plan — FRAC of each "
                         "cell's workers turns faulty (family from "
                         "{sign_flip,rescale,random_gauss,crash}) once "
                         "sim time reaches ONSET; PARAM is the rescale "
                         "factor / gauss scale (e.g. sign_flip:0.3:0). A "
                         "comma list (entries may be 'none') sweeps the "
                         "fault plan as a grid axis")
    ap.add_argument("--sim-agg", default="mean", metavar="AGG[,AGG..]",
                    help="simulate: gradient aggregator from {mean,trimmed,"
                         "median,geomedian}; a comma list sweeps the "
                         "aggregator as a grid axis (robust options "
                         "aggregate per-worker gradient rows; not available "
                         "with kbatch mode)")
    ap.add_argument("--sim-n-grid", default=None, metavar="N1,N2,...",
                    help="simulate: sweep the worker count as a grid axis; "
                         "cells are padded to the largest n (overrides "
                         "--n-workers)")
    ap.add_argument("--replicas", type=int, default=16,
                    help="simulate: Monte-Carlo replicas per grid cell")
    ap.add_argument("--sim-m", type=int, default=400,
                    help="simulate: number of examples")
    ap.add_argument("--sim-d", type=int, default=20,
                    help="simulate: problem dimension")
    ap.add_argument("--sim-eval-every", type=int, default=500)
    ap.add_argument("--sim-csv", default=None,
                    help="simulate: write per-cell trajectories to this CSV")
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed (multi-process SPMD): "
                         "meshes — the production LM mesh and the sweep "
                         "engine's (cells, replicas) mesh alike — then span "
                         "every process's devices; coordinator/rank come "
                         "from the cluster environment")
    args = ap.parse_args(argv)

    # Both must happen before anything touches jax device state or compiles:
    # distributed init defines the global device set every mesh spans, and
    # the cache config must be live before the first jit dispatch persists.
    if args.distributed:
        jax.distributed.initialize()
    from repro.core import cache as cache_lib

    cache_lib.setup_compilation_cache()

    if args.simulate:
        return _run_simulation(args)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = (mesh_lib.make_production_mesh() if args.production_mesh
            else mesh_lib.make_host_mesh())
    n_workers = args.n_workers
    if args.batch % n_workers:
        raise SystemExit(f"--batch {args.batch} must be divisible by --n-workers {n_workers}")

    opt = get_optimizer(args.optimizer, args.lr)
    straggler = get_straggler_model(args.straggler)
    ckw = {}
    if args.controller == "pflug":
        ckw = dict(k0=args.k0, step=args.k_step, thresh=args.thresh, burnin=args.burnin)
    elif args.controller == "sketched_pflug":
        ckw = dict(k0=args.k0, step=args.k_step, thresh=args.thresh,
                   burnin=args.burnin, sketch_dim=args.sketch_dim)
    elif args.controller == "fixed":
        ckw = dict(k=args.fixed_k)
    elif args.controller == "schedule":
        # Theorem-1 bound-optimal switch times, computed from the chosen
        # straggler model's order statistics and the supplied SGD constants.
        sysm = theory.SGDSystem(
            eta=args.lr, L=args.schedule_smoothness,
            c=args.schedule_strong_convexity, sigma2=args.schedule_sigma2,
            s=args.batch // n_workers, F0_gap=args.schedule_f0_gap,
            n=n_workers, straggler=straggler,
        )
        times = theory.switching_times(
            sysm, list(range(args.k0, n_workers, args.k_step)), step=args.k_step)
        print(f"schedule: Theorem-1 switch times {[round(t, 2) for t in times]}")
        ckw = dict(switch_times=times, k0=args.k0, step=args.k_step)
    elif args.controller == "variance_ratio":
        ckw = dict(k0=args.k0, step=args.k_step, burnin=args.burnin)
    controller = get_controller(args.controller, n_workers, **ckw)
    comm = CommModel(alpha=args.comm_alpha, beta=args.comm_beta)

    train_step = steps_lib.make_train_step(model, opt, controller, straggler,
                                           n_workers, comm, mode=args.mode)
    data = TokenStream(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    state = steps_lib.init_train_state(model, opt, controller, key)
    start = 0
    if args.ckpt_dir:
        latest = checkpoint.latest_step(args.ckpt_dir)
        if latest is not None:
            state = checkpoint.restore(args.ckpt_dir, latest, state)
            start = latest
            print(f"restored step {latest} from {args.ckpt_dir}")

    # The step is compiled ahead of the loop so compile time is reported as
    # set-up and every timed step is a warm one.  Each step is timed to the
    # moment its outputs exist on the device (block_until_ready).
    steps = []
    compile_s = None
    with mesh, activation_sharding(shard_lib.activation_resolver(mesh)):
        jitted = jax.jit(train_step, donate_argnums=(0,))
        step_fn = None
        t_start = time.perf_counter()
        for step in range(start, args.steps):
            before = cache_lib.compile_stats()
            tokens, targets = data.batch_at(step)
            batch = {"tokens": tokens, "targets": targets}
            if cfg.family == "vlm":
                batch["patches"] = jnp.zeros(
                    (args.batch, cfg.vlm_patches, cfg.d_model), jnp.float32)
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder_frames, cfg.d_model), jnp.float32)
            key, sub = jax.random.split(key)
            if step_fn is None:
                t0 = time.perf_counter()
                step_fn = jitted.lower(state, batch, sub).compile()
                compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            state, metrics = step_fn(state, batch, sub)
            jax.block_until_ready((state, metrics))
            step_s = time.perf_counter() - t0
            after = cache_lib.compile_stats()
            record = {
                "step": step,
                "ce": float(metrics["ce"]),
                "k": int(metrics["k"]),
                "sim_time": float(metrics["sim_time"]),
                "iter_time": float(metrics["iter_time"]),
                "step_s": step_s,
                "traces": after["traces"] - before["traces"],
                "compiles": after["compiles"] - before["compiles"],
            }
            steps.append(record)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(json.dumps({
                    "step": step,
                    "ce": round(record["ce"], 4),
                    "k": record["k"],
                    "sim_time": round(record["sim_time"], 2),
                    "iter_time": round(record["iter_time"], 3),
                    "compiles": record["compiles"],
                    "wall_s": round(time.perf_counter() - t_start, 1),
                }), flush=True)
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt_dir, step + 1, state)
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, args.steps, state)
        print(f"saved final checkpoint at step {args.steps}")
    return {"compile_s": compile_s, "steps": steps}


if __name__ == "__main__":
    main()
