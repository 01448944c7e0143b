"""Entry kind ``sweep``: the program's public grid entry,
``repro.core.sweep.run_sweep``, called again and again with fresh replicas.

Dispatch i draws its R replica keys as ``split(fold_in(key(seed), i), R)``,
so every dispatch is new replicas of the same grid, as a user tightening
confidence intervals would run it.  Dispatch 0 is the warm-up (set-up); the
window holds whole dispatches 1, 2, ... until ``--seconds`` have passed.

The data (the paper's X, y), the step size and the keys are made here from
the seed; the program gets only those.  After the window, lanes drawn from
the seed (at least two per grid cell, across the dispatches the window ran)
are recomputed by the plain reference (``bench/reference/linreg.py``) and
their time, loss and k records compared.
"""

from __future__ import annotations

import time

import numpy as np

from bench import common
from bench.common import Check, CellRun, Outcome, span


def _loss(w, X, y):
    r = X @ w - y
    return r * r


def make_problem(key, m: int, d: int, x_max: int, w_max: int, noise: float,
                 eta_scale: float):
    """The paper's §V-A data (X uniform over {1..x_max}^d, w_bar uniform over
    {1..w_max}^d, y = X w_bar + noise * N(0, 1)) and eta = eta_scale / L with
    L = 2 lambda_max(X^T X / m), in one jitted call."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        X = jax.random.randint(k1, (m, d), 1, x_max + 1).astype(jnp.float32)
        w_bar = jax.random.randint(k2, (d,), 1, w_max + 1).astype(jnp.float32)
        y = X @ w_bar + noise * jax.random.normal(k3, (m,), jnp.float32)
        L = 2.0 * jnp.linalg.eigvalsh(X.T @ X / m)[-1]
        return X, y, eta_scale / L

    X, y, eta = make(key)
    return X, y, float(eta)


def grid(config: dict, traffic: dict, eta: float):
    """The traffic's cases as ``SweepCase``s, with the configuration's
    controller settings and fleet."""
    from repro.core.controller import FixedKController, PflugController
    from repro.core.faults import byzantine_plan
    from repro.core.straggler import Exponential

    n = config["fleet"]["n_workers"]
    strag = Exponential(rate=config["fleet"]["rate"])
    from repro.core.sweep import SweepCase

    cases = []
    for c in traffic["cases"]:
        if c["controller"] == "pflug":
            p = config["controllers"]["pflug"]
            ctrl = PflugController(n_workers=n, k0=p["k0"], step=p["step"],
                                   thresh=p["thresh"], burnin=p["burnin"],
                                   k_max=p["k_max"])
        elif c["controller"] == "fixed":
            ctrl = FixedKController(n_workers=n, k=c["k"])
        else:
            raise ValueError(f"unknown controller {c['controller']!r}")
        fault = None
        if "fault" in c:
            f = c["fault"]
            fault = byzantine_plan(n, f["frac"], f["family"])
        cases.append(SweepCase(ctrl, strag, eta=eta, label=c["label"],
                               mode=c.get("mode", "sync"), fault=fault,
                               agg=c.get("agg", "mean")))
    return cases


def lanes_to_check(seed: int, n_dispatches: int, n_cells: int, n_replicas: int,
                   per_cell: int):
    """(dispatch, cell, replica) triples drawn from the seed: ``per_cell``
    lanes of every cell, from the window's dispatches 1..n_dispatches."""
    from bench.registry import seed32

    rng = np.random.default_rng(seed32(seed, "sweep-check"))
    out = []
    for g in range(n_cells):
        for _ in range(per_cell):
            out.append((int(rng.integers(1, n_dispatches + 1)), g,
                        int(rng.integers(0, n_replicas))))
    return out


def reference_lanes(config, traffic, X, y, eta, base_key, picks, precision):
    """The reference's (time, loss, k) for the picked lanes, one program per
    (mode, aggregator) present."""
    import jax
    import jax.numpy as jnp

    from bench.reference import linreg

    n = config["fleet"]["n_workers"]
    R = traffic["replicas"]
    p = config["controllers"]["pflug"]
    E = config["iterations"] // config["eval_every"]
    out = np.zeros((3, len(picks), E), np.float64)
    groups: dict = {}
    for j, (d, g, r) in enumerate(picks):
        c = traffic["cases"][g]
        groups.setdefault((c.get("mode", "sync"), c.get("agg", "mean")), []).append(j)
    for (mode, agg), idx in sorted(groups.items()):
        rows = []
        for j in idx:
            d, g, r = picks[j]
            c = traffic["cases"][g]
            key = jax.random.split(jax.random.fold_in(base_key, d), R)[r]
            if c["controller"] == "pflug":
                ctrl = (linreg.PFLUG, p["k0"], p["step"], p["thresh"], p["burnin"],
                        p["k_max"])
            else:
                ctrl = (linreg.FIXED, c["k"], 0, 0, 0, c["k"])
            fault = np.ones((n,), np.float32)
            if "fault" in c:
                if c["fault"]["family"] != "sign_flip":
                    raise ValueError(f"no reference for fault {c['fault']['family']!r}")
                fault[n - int(round(c["fault"]["frac"] * n)):] = -1.0
            rows.append((key,) + ctrl + (fault,))
        lanes = linreg.Lane(
            key=jnp.stack([r[0] for r in rows]),
            **{f: jnp.asarray([r[i + 1] for r in rows], jnp.int32)
               for i, f in enumerate(("ctrl", "k0", "step", "thresh", "burnin", "k_max"))},
            eta=jnp.full((len(rows),), eta, jnp.float32),
            fault=jnp.asarray(np.stack([r[7] for r in rows])),
        )
        with jax.default_matmul_precision("highest"):
            rec = linreg.simulate(X, y, lanes, n, config["iterations"],
                                  config["eval_every"], precision, mode, agg)
        for f in range(3):
            out[f, idx] = np.asarray(rec[f])
    return out[0], out[1], out[2]


def compare(prog, ref) -> dict:
    """The numbers compared: the widest relative gap of the loss and of the
    simulated time over every record of every picked lane, and the share of
    records whose k differs."""
    (pt, pl, pk), (rt, rl, rk) = prog, ref
    return {
        "loss_gap": common.rel_gap(pl, rl),
        "time_gap": common.rel_gap(pt, rt),
        "k_mismatch": float(np.mean(np.asarray(pk) != np.asarray(rk))),
    }


def run(c: CellRun) -> Outcome:
    import jax
    import jax.numpy as jnp

    from repro.core.sweep import run_sweep
    from repro.launch import mesh as mesh_lib

    cfg, tr = c.config, c.traffic
    prob, fleet = cfg["problem"], cfg["fleet"]
    n, R, iters = fleet["n_workers"], tr["replicas"], cfg["iterations"]
    key = common.seed_key(c.seed)
    data_key, base_key = jax.random.split(key)
    X, y, eta = make_problem(data_key, prob["m"], prob["d"], prob["x_max"],
                             prob["w_max"], prob["noise_std"], cfg["step_size"]["c"])
    cases = grid(cfg, tr, eta)
    G = len(cases)
    mesh = mesh_lib.make_sweep_mesh(G, R, devices=c.devices)
    keys_of = jax.jit(lambda i: jax.random.split(jax.random.fold_in(base_key, i), R))
    w0 = jnp.zeros((prob["d"],), jnp.float32)

    def dispatch(i):
        with span("bench.draw"):
            keys = keys_of(i)
        with span("bench.dispatch"):
            res = run_sweep(_loss, w0, X, y, n_workers=n, cases=cases,
                            num_iters=iters, keys=keys,
                            eval_every=cfg["eval_every"], mesh=mesh)
        with span("bench.block"):
            jax.block_until_ready((res.time, res.loss, res.k))
        with span("bench.read"):
            return tuple(np.asarray(a) for a in (res.time, res.loss, res.k))

    common.phase(c, "inputs made")
    t0 = time.perf_counter()
    dispatch(0)  # warm-up: compiles or loads every program the window runs
    first_call_s = time.perf_counter() - t0
    common.phase(c, "first dispatch done")

    records = []
    with common.settled():
        setup_s = time.time() - c.start_wall
        t_win = time.perf_counter()
        with common.traced(c):
            while True:
                records.append(dispatch(len(records) + 1))
                window_s = time.perf_counter() - t_win
                if (c.trace and len(records) >= tr["trace_dispatches"]) or (
                        not c.trace and window_s >= c.seconds):
                    break
    trace = None
    if c.trace:
        from bench import trace as trace_lib

        common.phase(c, "traced window done")
        trace = trace_lib.reduce_dir(c.trace_dir)
        common.phase(c, "trace reduced")
    mem = common.peak_bytes(c.devices)

    nd = len(records)
    failed = sum(not all(np.isfinite(a).all() for a in rec) for rec in records)
    picks = lanes_to_check(c.seed, nd, G, R, tr["check"]["lanes_per_cell"])
    prog = tuple(np.stack([records[d - 1][f][g, r] for d, g, r in picks])
                 for f in range(3))
    ref = reference_lanes(cfg, tr, X, y, eta, base_key, picks, "highest")
    common.phase(c, "reference compared")
    limits = tr["check"]["limits"]
    checks = {name: Check(v, limits[name]) for name, v in compare(prog, ref).items()
              if name in limits}
    cell_iters = nd * G * R * iters
    return Outcome(
        metrics={"cell_iters_per_s": cell_iters / window_s, "setup_s": setup_s},
        attempted=nd, failed=failed, checks=checks, memory_peak_bytes=mem,
        layer={"first_call_s": first_call_s, "window_s": window_s,
               "serial_iters": nd * iters,
               "eval_every": cfg["eval_every"], "m": prob["m"], "d": prob["d"],
               "n_workers": n, "k_records": [rec[2] for rec in records],
               "chips": len(c.devices)},
        trace=trace)


def control(c: CellRun, precision: str) -> dict:
    """The control's readings: the reference computed at ``precision`` (one
    step below the configuration's) put in the program's place, on the lanes
    a run of one dispatch would check."""
    import jax

    cfg, tr = c.config, c.traffic
    prob = cfg["problem"]
    data_key, base_key = jax.random.split(common.seed_key(c.seed))
    X, y, eta = make_problem(data_key, prob["m"], prob["d"], prob["x_max"],
                             prob["w_max"], prob["noise_std"], cfg["step_size"]["c"])
    picks = lanes_to_check(c.seed, 1, len(tr["cases"]), tr["replicas"],
                           tr["check"]["lanes_per_cell"])
    ref = reference_lanes(cfg, tr, X, y, eta, base_key, picks, "highest")
    low = reference_lanes(cfg, tr, X, y, eta, base_key, picks, precision)
    return compare(low, ref)
