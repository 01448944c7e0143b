"""Single-dispatch sweep engine: grid-vmapped, device-sharded Monte-Carlo.

The paper's artifacts (Figs. 2-3, the ablation) are *grids* — controller x
straggler model x (n, k-policy) — of many-seed error-vs-wall-clock
distributions.  ``run_monte_carlo`` runs one grid cell per dispatch; this
module runs the **whole grid as one jitted program** by stacking every
cell's configuration as pytree leaves and vmapping a grid axis on top of
the replica axis:

  * straggler parameters are **per-worker** packed matrices
    (``straggler.pack_params_per_worker``: an (n_slots, P) float32 row per
    worker slot plus an (n_slots,) family-index vector) realized as cheap
    per-family transforms of ONE shared base uniform, selected per slot
    (``straggler.sample_times_per_worker``) — the iid paper model is the
    broadcast-row special case, mixed fleets (``straggler.WorkerFleet``)
    are first-class, and an optional ``RateSchedule`` drifts a parameter
    leaf in-graph as a function of the carried sim_time;
  * ``n`` is an ordinary grid axis: every cell is padded to a common
    ``n_slots``; slots past the cell's ``n_active`` sample +inf, rank
    strictly after every active worker, and their data shards are held out
    of both the gradient and the eval loss;
  * controller hyperparameters (k0, step, thresh, burnin, k_max, decay,
    ratio threshold, schedule switch times, sketch sign constants) are
    traced leaves interpreted by a ``lax.switch`` over a unified
    controller-state superset;
  * the comm model's (alpha, beta) and the step size eta are leaves too.

Because *kinds* are traced int32 leaves, cell assignment never forces a
retrace — what is compiled against is the grid's **branch signature**
(``GridSignature``): the sets of controller kinds and execution modes,
plus schedule/comm feature flags, actually present.  By default
(``specialize=True``) the program prunes every switch branch the
signature excludes — under vmap a switch computes all branches for all
cells on every iteration, so fixed-composition grids (every figure script)
otherwise pay a multiplicative all-branches tax — and programs are cached
per signature, so repopulating a same-signature grid never retraces.
``specialize=False`` keeps the fully-grid-agnostic program: any same-shape
grid repopulates with zero retraces, at the all-branches cost.  (The
straggler family set is deliberately never specialized — see
``GridSignature``.)

The grid is dispatched over a 2-D ``("cells", "replicas")`` device mesh:
each axis pads to its mesh-axis multiple (cells with inert empty rows,
replicas by repeating a key), the padded grid flattens cell-major into ONE
lane axis, and that axis is sharded over both mesh axes — so a grid
smaller than the device count still occupies every device (a 15-cell x
32-replica grid fills a 480-device slice: the replica axis shards too),
and the mesh spans *processes* whenever ``jax.distributed`` is initialized
(``launch.mesh.make_sweep_mesh`` builds it over global devices;
``shardctx.sweep_mesh`` or the ``mesh=`` argument override it).  The
traced program stays the historical single-vmap flat program — the mesh
decides placement, never arithmetic.  Inputs are placed with
``jax.sharding.NamedSharding`` and XLA propagation partitions the program
(with a ``shard_map`` fallback path); on a single device both paths
degenerate to the plain vmap.

Bitwise fidelity: every cell's trajectories are bitwise-equal to what a
looped ``run_monte_carlo`` call produces for the same PRNG keys.  The
per-iteration arithmetic (RNG split order, packed-parameter samplers, rank/
mask/order-statistic path, segment-sum weighted gradient, controller update
formulas including float32 constant rounding) deliberately mirrors the
class-based engine op for op — tests/test_sweep.py pins this.

API sketch::

    cases = [
        SweepCase(PflugController(n_workers=50, k0=10, step=10, thresh=10),
                  Exponential(rate=1.0), eta=1e-2, label="pflug/exp"),
        SweepCase(FixedKController(n_workers=50, k=40),
                  Pareto(x_m=0.5, alpha=1.5), eta=1e-2, label="k40/pareto"),
    ]
    result = run_sweep(loss_fn, w0, X, y, n_workers=50, cases=cases,
                       num_iters=40_000, keys=keys, eval_every=500)
    stats = summarize_cells(result)     # one summarize() dict per cell
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import aggregation, execmode, faults
from repro.core.controller import (
    FixedKController,
    PflugController,
    ScheduleController,
    SketchedPflugController,
    VarianceRatioController,
    _tree_dot,
    _tree_zeros_like,
)
from repro.core.gradsource import GradSource, PerExampleSource, lane_data
from repro.core.montecarlo import (
    MonteCarloResult,
    _LRUProgramCache,
    _default_program_cache_size,
    summarize,
)
from repro.core.straggler import (
    StragglerModel,
    WorkerFleet,
    apply_rate_schedule,
    family_select_masks,
    pack_params_per_worker,
    pack_schedule,
    sample_times_selected,
)

__all__ = [
    "GridSignature",
    "SweepCase",
    "SweepResult",
    "grid_signature",
    "run_sweep",
    "run_sweep_source",
    "summarize_cells",
    "product_cases",
    "sweep_cache_stats",
    "clear_sweep_cache",
    "dispatch_donation",
]

# Controller kinds — lax.switch branch indices for the unified update.
_FIXED, _PFLUG, _SCHEDULE, _VARIANCE_RATIO, _SKETCHED_PFLUG = range(5)

_CTRL_KINDS = {
    FixedKController: _FIXED,
    PflugController: _PFLUG,
    ScheduleController: _SCHEDULE,
    VarianceRatioController: _VARIANCE_RATIO,
    SketchedPflugController: _SKETCHED_PFLUG,
}
_N_CTRL_KINDS = len(_CTRL_KINDS)


class GridSignature(NamedTuple):
    """The static *shape of the work* a grid can ask of a compiled program.

    Under vmap every ``lax.switch`` computes ALL of its branches for every
    lane and selects — so a grid-agnostic program pays for every controller
    kind, feature flag, and execution mode on every iteration whether or
    not the grid contains them.  The signature records which branches can
    actually be selected (as *sets* — the per-cell assignment stays a traced
    leaf), letting ``run_sweep`` compile a program with the absent branches
    pruned.  Two grids with the same signature (and static shapes) share one
    compiled program: repopulating a same-signature grid never retraces.

    Fields are sorted tuples of branch indices plus feature flags:

    * ``ctrl_kinds`` — controller branch indices present,
    * ``modes`` — ``execmode.MODES`` indices present,
    * ``with_schedule`` — any cell carries a live ``RateSchedule``,
    * ``with_comm`` — any cell carries a non-zero ``CommModel``,
    * ``fault_kinds`` — non-``none`` fault families any cell's ``FaultPlan``
      can activate (``faults.FAULT_FAMILIES`` indices),
    * ``agg_kinds`` — aggregator kinds present (``aggregation.AGG_KINDS``
      indices; ``(AGG_MEAN,)`` for an all-mean grid).

    The fault and aggregator axes are specialized even under
    ``specialize=False`` (``_full_signature`` derives them from the actual
    cases): unconditionally tracing per-slot fault transforms, gauss noise
    draws, and three robust aggregators would tax every unspecialized
    dispatch — including the committed warm-ceiling benchmark gate — for
    axes almost no grid populates.  A fault-free, mean-aggregation grid
    therefore compiles today's exact program under BOTH dispatch modes (the
    bitwise pin in tests/test_faults.py); same-shape *fault-grid*
    repopulation still never retraces, because the packed per-slot fault
    rows and the per-cell aggregator assignment are traced leaves — only
    changing which *families/aggregators exist anywhere in the grid* can
    compile a new program.

    The straggler *family* set is deliberately NOT part of the signature:
    under the shared-base-uniform protocol every family is a couple of
    cheap elementwise ops, and pruning them would make the sampler
    subgraph's structure vary between programs — which XLA CPU compiles
    with last-ulp differences in the response-time chain (measured: a
    family-restricted looped program vs a full-sampler sweep drifted one
    ulp of sim_time per ~100 kasync events).  Keeping the sampler
    structurally identical in every program is what makes the bitwise
    sweep-vs-looped contract robust.  The pruned axes (controllers, modes,
    schedule, comm) live outside the response-time-generating subgraph.

    Specialization changes which branches are *traced*, never the
    arithmetic of the branches that run: every pruned program stays
    bitwise-equal per cell to looped ``run_monte_carlo``.
    """

    ctrl_kinds: tuple
    modes: tuple
    with_schedule: bool
    with_comm: bool
    fault_kinds: tuple
    agg_kinds: tuple


def _robustness_axes(cases: Sequence["SweepCase"]) -> tuple[tuple, tuple]:
    """The (fault_kinds, agg_kinds) signature components of a grid."""
    fault_kinds, agg_kinds = set(), set()
    for c in cases:
        fault_kinds.update(faults.plan_kinds_present(c.fault))
        ak = aggregation.AGG_KINDS.get(c.agg)
        if ak is not None:  # unknown aggregators error later, in _cell_of
            agg_kinds.add(ak)
    return (
        tuple(sorted(fault_kinds)),
        tuple(sorted(agg_kinds)) if agg_kinds else (aggregation.AGG_MEAN,),
    )


def grid_signature(cases: Sequence["SweepCase"], n_slots: int) -> GridSignature:
    """Derive the branch signature of a populated grid (see GridSignature)."""
    del n_slots  # families (which padding would affect) are not in the signature
    kinds, modes = set(), set()
    with_schedule = with_comm = False
    for c in cases:
        kind = _CTRL_KINDS.get(type(c.controller))
        if kind is not None:  # unknown controllers error later, in _cell_of
            kinds.add(kind)
        if c.mode in execmode.MODES:
            modes.add(execmode.MODES[c.mode])
        if isinstance(c.straggler, WorkerFleet):
            sched = c.straggler.schedule
            if sched is not None and len(sched.times):
                with_schedule = True
        if c.comm is not None and (c.comm.alpha != 0.0 or c.comm.beta != 0.0):
            with_comm = True
    fault_kinds, agg_kinds = _robustness_axes(cases)
    return GridSignature(
        ctrl_kinds=tuple(sorted(kinds)),
        modes=tuple(sorted(modes)),
        with_schedule=with_schedule,
        with_comm=with_comm,
        fault_kinds=fault_kinds,
        agg_kinds=agg_kinds,
    )


def _full_signature(cases: Sequence["SweepCase"]) -> GridSignature:
    """``specialize=False``: the fully-grid-agnostic program family.

    Every controller kind and feature flag is kept, so ANY same-shape grid
    repopulates without retracing.  The one static split retained is the
    historical all-sync flag: a grid with no async cell compiles the lean
    pre-mode program (no ExecCarry), any async cell selects the full
    three-mode program.  The fault/aggregator axes are derived from the
    actual cases even here — they are always specialized (see
    GridSignature) — so a faulty grid under ``specialize=False`` keeps
    zero-retrace repopulation only within its fault/aggregator family sets.
    """
    all_sync = all(c.mode == "sync" for c in cases)
    fault_kinds, agg_kinds = _robustness_axes(cases)
    return GridSignature(
        ctrl_kinds=tuple(range(_N_CTRL_KINDS)),
        modes=(execmode.MODE_SYNC,) if all_sync
        else tuple(sorted(execmode.MODES.values())),
        with_schedule=True,
        with_comm=True,
        fault_kinds=fault_kinds,
        agg_kinds=agg_kinds,
    )


def _static_remap(present: tuple, total: int):
    """int32 lookup table mapping global branch indices to pruned-local ones."""
    remap = np.zeros((total,), np.int32)
    for j, g in enumerate(present):
        remap[g] = j
    return remap


def _auto_unroll(sig: GridSignature) -> int:
    """Scan-unroll heuristic for ``unroll=None``, from measurements on the
    2-core reference host (benchmarks/README.md):

    * async in the signature -> 4: the ExecCarry body (and kbatch's inner
      n_slots-event scan when present) is large, and compile time scales
      with the unrolled body while deeper unroll bought no warm time;
    * sync-only, multiple controller kinds -> 6 (the 15-cell baseline
      grid's shape: ~5% warmer-than-4 throughput at moderate compile);
    * sync-only, single controller kind -> 8: the maximally pruned body is
      small enough that deeper unrolling keeps amortizing scan-trip
      overhead.

    Unroll never affects the arithmetic — trajectories are
    bitwise-identical across unroll values (pinned by
    tests/test_specialize.py).

    Fault or robust-aggregation axes in the signature take the async
    setting: the step body grows the per-slot fault transforms (and the
    robust path an n_slots row stack of shard gradients), so the
    compile-time reasoning is the big-body one.
    """
    if sig.modes != (execmode.MODE_SYNC,):
        return 4
    if sig.fault_kinds or sig.agg_kinds != (aggregation.AGG_MEAN,):
        return 4
    return 8 if len(sig.ctrl_kinds) == 1 else 6


@dataclasses.dataclass(frozen=True)
class SweepCase:
    """One grid cell: a controller/straggler/step-size/comm configuration.

    ``straggler`` may be a ``WorkerFleet`` (heterogeneous per-worker models,
    optionally with a time-varying ``RateSchedule``).  The cell's *active*
    worker count is ``controller.n_workers``; when it is smaller than the
    engine's ``n_workers`` slot count the remaining slots are inactive
    (+inf response times, data held out) — this is how n varies per cell.

    ``mode`` is the cell's execution mode (``repro.core.execmode.MODES``):
    ``"sync"`` fastest-k lock step (default), ``"kasync"`` K-async SGD,
    ``"kbatch"`` K-batch-async SGD.  In the async modes the controller's k
    is K — the number of (stale) gradient arrivals per master update.  Mode
    is a traced grid leaf: sync and async arms run in ONE compiled program,
    and repopulating an equally-shaped mixed grid never retraces.

    ``fault`` is the cell's ``faults.FaultPlan`` (``None`` = healthy fleet;
    ``faults.byzantine_plan`` builds the standard fraction-faulty plan) —
    packed into per-slot ``(family, onset, param)`` leaf vectors.  ``agg``
    names the cell's gradient aggregator (``aggregation.AGG_KINDS``): the
    eq.-(2) weighted ``"mean"`` (default) or the robust ``"trimmed"`` /
    ``"median"`` / ``"geomedian"`` alternatives over the per-worker row
    stack, with ``agg_param`` the trimmed mean's trim fraction (ignored by
    the others).  Robust aggregation is rejected for ``kbatch`` cells —
    kbatch arrivals are sequential, there is no row stack to aggregate.
    """

    controller: Any
    straggler: StragglerModel | WorkerFleet
    eta: float
    comm: aggregation.CommModel | None = None
    label: str = ""
    mode: str = "sync"
    fault: faults.FaultPlan | None = None
    agg: str = "mean"
    agg_param: float = 0.1

    def name(self) -> str:
        if self.label:
            return self.label
        return f"{type(self.controller).__name__}/{type(self.straggler).__name__}"


def product_cases(
    controllers: dict, stragglers: dict, eta: float,
    comm: aggregation.CommModel | None = None,
) -> list[SweepCase]:
    """The full controller x straggler grid, labeled ``"<ctrl>|<strag>"``."""
    return [
        SweepCase(ctrl, strag, eta=eta, comm=comm, label=f"{cname}|{sname}")
        for sname, strag in stragglers.items()
        for cname, ctrl in controllers.items()
    ]


class _CellParams(NamedTuple):
    """One grid cell as traced leaves (stacked to (G, ...) across the grid)."""

    ctrl_kind: jax.Array  # int32 — index into the controller lax.switch
    mode: jax.Array  # int32 — execution mode (execmode.MODES lax.switch)
    k0: jax.Array  # int32
    step: jax.Array  # int32
    thresh: jax.Array  # int32
    burnin: jax.Array  # int32
    k_max: jax.Array  # int32 — k cap (n_active when the class left it None)
    decay: jax.Array  # f32 — variance_ratio EMA decay d
    one_minus_decay: jax.Array  # f32 — f32(1 - d) rounded exactly as the class does
    ratio_thresh: jax.Array  # f32
    switch_times: jax.Array  # f32 (S,) — schedule times, +inf padded
    n_active: jax.Array  # int32 — active worker slots (n as a grid axis)
    strag_kinds: jax.Array  # int32 (n_slots,) — per-slot SWEEP_FAMILIES indices
    strag_p: jax.Array  # f32 (n_slots, N_STRAGGLER_PARAMS) — per-worker params
    sched_mode: jax.Array  # int32 — straggler.SCHEDULE_MODES
    sched_leaf: jax.Array  # int32 — which parameter column drifts
    sched_times: jax.Array  # f32 (K,) — rate-schedule knots, +inf padded
    sched_scales: jax.Array  # f32 (K,) — knot multipliers, last-value padded
    sketch_signs: Any  # params-shaped pytree — sketched_pflug Rademacher signs
    comm_alpha: jax.Array  # f32
    comm_beta: jax.Array  # f32
    eta: jax.Array  # f32
    fault_kinds: jax.Array  # int32 (n_slots,) — faults.FAULT_FAMILIES per slot
    fault_onset: jax.Array  # f32 (n_slots,) — per-slot fault onset sim time
    fault_param: jax.Array  # f32 (n_slots,) — rescale factor / gauss scale
    agg_kind: jax.Array  # int32 — aggregation.AGG_KINDS select index
    agg_param: jax.Array  # f32 — trimmed mean's trim fraction


class _CtrlState(NamedTuple):
    """Superset of every supported controller's state (policy-agnostic carry)."""

    k: jax.Array
    count_negative: jax.Array
    count_iter: jax.Array
    prev_grad: Any  # pytree — Pflug's g_{j-1}
    prev_sketch: jax.Array  # f32 (sketch_dim,) — sketched Pflug's z_{j-1}
    ema_mean: Any  # pytree — variance_ratio's EMA(g)
    ema_sq: jax.Array
    have_prev: jax.Array
    n_switches: jax.Array


class SweepResult(NamedTuple):
    """Grid of eval-point trajectories: ``time``/``loss``/``k`` are (G, R, E)."""

    time: jax.Array
    loss: jax.Array
    k: jax.Array
    iteration: np.ndarray
    labels: tuple

    def cell(self, g: int) -> MonteCarloResult:
        """Cell g's trajectories as a MonteCarloResult (R, E)."""
        return MonteCarloResult(
            time=self.time[g], loss=self.loss[g], k=self.k[g], iteration=self.iteration
        )


def summarize_cells(result: SweepResult) -> dict:
    """``{label: summarize(cell)}`` for every grid cell."""
    return {
        label: summarize(result.cell(g)) for g, label in enumerate(result.labels)
    }


def _sketch_signs_of(params_like, seed: int, sketch_dim: int):
    """Host-side precompute of SketchedPflugController._sketch's Rademacher
    signs: the same crc32(key-path)-derived leaf seeds and the same
    ``jax.random.rademacher`` draw, materialized once per cell as a
    params-shaped pytree of f32 constants (the grid's static sketch
    layout).  The in-graph branch multiplies these exactly as the class
    multiplies its on-the-fly signs, so sketched cells stay bitwise-equal
    to the looped engine."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params_like)
    out = []
    for path, g in leaves:
        digest = zlib.crc32(jax.tree_util.keystr(path).encode("utf-8"))
        key = jax.random.PRNGKey(seed + (digest % (2**30)))
        out.append(np.asarray(
            jax.random.rademacher(key, np.shape(g), dtype=jnp.float32)
        ))
    return jax.tree_util.tree_unflatten(treedef, out)


def _zero_signs_of(params_like):
    return jax.tree.map(lambda g: np.zeros(np.shape(g), np.float32), params_like)


def _cell_of(
    case: SweepCase,
    n_slots: int,
    n_switch_slots: int,
    n_sched_slots: int,
    sketch_dim: int,
    params_like,
) -> _CellParams:
    c = case.controller
    kind = _CTRL_KINDS.get(type(c))
    if kind is None:
        raise ValueError(
            f"{type(c).__name__} is not sweepable; supported: "
            f"{[t.__name__ for t in _CTRL_KINDS]}"
        )
    i32, f32 = np.int32, np.float32
    n_active = int(c.n_workers)
    if n_active > n_slots:
        raise ValueError(
            f"cell {case.name()!r}: controller n_workers={n_active} exceeds "
            f"the grid's n_slots={n_slots}"
        )
    if isinstance(case.straggler, WorkerFleet) and case.straggler.n_active != n_active:
        raise ValueError(
            f"cell {case.name()!r}: fleet has {case.straggler.n_active} models "
            f"but controller.n_workers={n_active}"
        )
    if case.mode not in execmode.MODES:
        raise ValueError(
            f"cell {case.name()!r}: unknown mode {case.mode!r}; options "
            f"{sorted(execmode.MODES)}"
        )
    if case.agg not in aggregation.AGG_KINDS:
        raise ValueError(
            f"cell {case.name()!r}: unknown aggregator {case.agg!r}; options "
            f"{sorted(aggregation.AGG_KINDS)}"
        )
    if case.agg != "mean" and case.mode == "kbatch":
        raise ValueError(
            f"cell {case.name()!r}: robust aggregation ({case.agg!r}) is not "
            "supported in kbatch mode — kbatch arrivals are sequential, "
            "there is no per-worker row stack to aggregate"
        )
    if case.fault is not None and not isinstance(case.fault, faults.FaultPlan):
        raise ValueError(
            f"cell {case.name()!r}: fault must be a faults.FaultPlan or None, "
            f"got {case.fault!r}"
        )
    try:
        fkinds, fonset, fparam = faults.pack_faults(case.fault, n_slots, n_active)
    except ValueError as e:
        raise ValueError(f"cell {case.name()!r}: {e}") from None
    k0, step, thresh, burnin = 1, 0, 0, 0
    k_max = n_active
    decay = ratio_thresh = 0.0
    times = np.full((n_switch_slots,), np.inf, f32)
    signs = _zero_signs_of(params_like)
    if kind == _FIXED:
        k0 = c.k
    elif kind in (_PFLUG, _SKETCHED_PFLUG):
        k0, step, thresh, burnin = c.k0, c.step, c.thresh, c.burnin
        k_max = c.k_max if c.k_max is not None else n_active
        if kind == _SKETCHED_PFLUG:
            if c.sketch_dim != sketch_dim:
                raise ValueError(
                    f"cell {case.name()!r}: sketch_dim={c.sketch_dim} but the "
                    f"grid's static sketch layout is {sketch_dim} (every "
                    "sketched cell in one sweep must share sketch_dim)"
                )
            signs = _sketch_signs_of(params_like, c.seed, sketch_dim)
    elif kind == _SCHEDULE:
        k0, step = c.k0, c.step
        st = np.asarray(list(c.switch_times), f32)
        if st.size > n_switch_slots:
            raise ValueError(f"{st.size} switch times > {n_switch_slots} slots")
        times[: st.size] = st
    elif kind == _VARIANCE_RATIO:
        k0, step, burnin = c.k0, c.step, c.burnin
        k_max = c.k_max if c.k_max is not None else n_active
        decay, ratio_thresh = c.decay, c.ratio_thresh
    pmat, kinds, _ = pack_params_per_worker(case.straggler, n_slots, n_active=n_active)
    sched = case.straggler.schedule if isinstance(case.straggler, WorkerFleet) else None
    sched_mode, sched_leaf, sched_times, sched_scales = pack_schedule(sched, n_sched_slots)
    comm = case.comm or aggregation.CommModel()
    return _CellParams(
        ctrl_kind=i32(kind),
        mode=i32(execmode.MODES[case.mode]),
        k0=i32(k0),
        step=i32(step),
        thresh=i32(thresh),
        burnin=i32(burnin),
        k_max=i32(k_max),
        decay=f32(decay),
        # The class computes (1 - d) in Python float64 and lets jax cast at
        # use; rounding here the same way keeps cells bitwise-faithful.
        one_minus_decay=f32(1.0 - decay),
        ratio_thresh=f32(ratio_thresh),
        switch_times=times,
        n_active=i32(n_active),
        strag_kinds=kinds,
        strag_p=pmat,
        sched_mode=sched_mode,
        sched_leaf=sched_leaf,
        sched_times=sched_times,
        sched_scales=sched_scales,
        sketch_signs=signs,
        comm_alpha=f32(comm.alpha),
        comm_beta=f32(comm.beta),
        eta=f32(case.eta),
        fault_kinds=fkinds,
        fault_onset=fonset,
        fault_param=fparam,
        agg_kind=i32(aggregation.AGG_KINDS[case.agg]),
        agg_param=f32(case.agg_param),
    )


# ------------------------------------------------- unified controller update


def _ctrl_init(cp: _CellParams, params_like, sketch_dim: int) -> _CtrlState:
    return _CtrlState(
        k=jnp.asarray(cp.k0, jnp.int32),
        count_negative=jnp.asarray(0, jnp.int32),
        # Pflug starts its iteration counter at 1, variance_ratio at 0.
        count_iter=jnp.where(cp.ctrl_kind == _VARIANCE_RATIO, 0, 1).astype(jnp.int32),
        prev_grad=_tree_zeros_like(params_like),
        prev_sketch=jnp.zeros((sketch_dim,), jnp.float32),
        ema_mean=_tree_zeros_like(params_like),
        ema_sq=jnp.asarray(0.0, jnp.float32),
        have_prev=jnp.asarray(False),
        n_switches=jnp.asarray(0, jnp.int32),
    )


def _sel(pred, a, b):
    """``where`` that folds away when the predicate is statically known."""
    if pred is True:
        return a
    if pred is False:
        return b
    return jnp.where(pred, a, b)


def _sel_tree(pred, a, b):
    if pred is True:
        return a
    if pred is False:
        return b
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _pred_or(a, b):
    if a is True or b is True:
        return True
    if a is False:
        return b
    if b is False:
        return a
    return a | b


class _CtrlPreds(NamedTuple):
    """Per-cell controller-kind predicates, hoisted out of the hot loop.

    Each field is a traced per-lane bool, or a static python bool when the
    grid's signature decides it (absent kind -> False; only kind -> True),
    letting the unified update fold the corresponding selects away."""

    is_pflug: Any
    is_schedule: Any
    is_vr: Any
    is_sketched: Any


def _ctrl_preds(cp: _CellParams, ctrl_kinds: tuple | None) -> _CtrlPreds:
    kinds = tuple(ctrl_kinds) if ctrl_kinds is not None else tuple(
        range(_N_CTRL_KINDS)
    )

    def pred(kind):
        if kind not in kinds:
            return False
        if kinds == (kind,):
            return True
        return cp.ctrl_kind == kind

    return _CtrlPreds(
        is_pflug=pred(_PFLUG),
        is_schedule=pred(_SCHEDULE),
        is_vr=pred(_VARIANCE_RATIO),
        is_sketched=pred(_SKETCHED_PFLUG),
    )


def _apply_sketch(signs, grads, sketch_dim: int) -> jax.Array:
    """Count-sketch of the gradient from precomputed per-cell sign leaves —
    arithmetic-identical to SketchedPflugController._sketch (same leaf
    order, same pad/reshape/bucket-sum, same accumulation order), with the
    on-the-fly Rademacher draw replaced by the cell's traced constants."""
    m = sketch_dim
    z = jnp.zeros((m,), jnp.float32)
    for sl, g in zip(jax.tree.leaves(signs), jax.tree.leaves(grads)):
        t = (sl * g.astype(jnp.float32)).reshape(-1)
        pad = (-t.size) % m
        if pad:
            t = jnp.pad(t, (0, pad))
        z = z + t.reshape(-1, m).sum(axis=0)
    return z


def _ctrl_update(
    cp: _CellParams, state, grads, sim_time, stats, sketch_dim: int,
    ctrl_kinds: tuple | None = None,
    preds: _CtrlPreds | None = None,
):
    """The unified controller update, branch-signature-specialized.

    Under vmap a ``lax.switch`` over per-kind branch functions computes
    every branch for every lane and then select_n's FULL state tuples —
    duplicating the shared k/count bookkeeping per branch and forcing each
    branch to materialize candidate values for leaves it never touches.
    This form instead computes each present kind's *signal* once (the
    Pflug sign test on the dense or sketched gradient dot, the
    variance-ratio EMAs, the schedule's time trigger), emits the shared
    switch/step bookkeeping once, and merges per-kind leaves with single
    two-way selects.  Per selected lane the arithmetic is op-for-op the
    class controller's update (the bitwise sweep-vs-looped contract);
    kinds outside ``ctrl_kinds`` are never traced, and with a single kind
    present every select folds away.

    ``stats`` (execmode.ExecStats) rides through untouched by the current
    policies — the hook staleness-aware controllers plug into.
    """
    del stats
    kinds = tuple(ctrl_kinds) if ctrl_kinds is not None else tuple(
        range(_N_CTRL_KINDS)
    )
    if preds is None:
        preds = _ctrl_preds(cp, kinds)
    has_pflug = _PFLUG in kinds
    has_sketched = _SKETCHED_PFLUG in kinds
    has_schedule = _SCHEDULE in kinds
    has_vr = _VARIANCE_RATIO in kinds
    counting = _pred_or(preds.is_pflug, preds.is_sketched)
    adapting = _pred_or(counting, preds.is_vr)
    i32 = jnp.int32
    k = state.k

    # --- counting signal: sign of consecutive aggregated-gradient dots
    # (Algorithm 1), on the dense gradient (pflug) or its count-sketch.
    dot = z = None
    if has_pflug:
        dot = _tree_dot(grads, state.prev_grad)
    if has_sketched:
        z = _apply_sketch(cp.sketch_signs, grads, sketch_dim)
        dot_s = jnp.dot(z, state.prev_sketch)
        dot = dot_s if dot is None else _sel(preds.is_sketched, dot_s, dot)
    if counting is not False:
        delta = jnp.where(
            state.have_prev, jnp.where(dot < 0, 1, -1), 0
        ).astype(i32)
        count_neg1 = state.count_negative + delta

    # --- variance-ratio signal: ||EMA(g)||^2 / EMA(||g||^2)
    if has_vr:
        d, omd = cp.decay, cp.one_minus_decay
        ema1 = jax.tree.map(
            lambda m, g: d * m + omd * g.astype(jnp.float32),
            state.ema_mean, grads,
        )
        gsq = _tree_dot(grads, grads)
        ema_sq1 = d * state.ema_sq + omd * gsq
        mean_sq = _tree_dot(ema1, ema1)
        ratio = mean_sq / jnp.maximum(ema_sq1, 1e-30)

    # --- shared adaptive bookkeeping: one switch test, one k bump
    new_k = k
    do_switch = False
    if adapting is not False:
        if has_vr and counting is not False:
            cond = jnp.where(
                preds.is_vr, ratio < cp.ratio_thresh, count_neg1 > cp.thresh
            )
        elif has_vr:
            cond = ratio < cp.ratio_thresh
        else:
            cond = count_neg1 > cp.thresh
        gate = (state.count_iter > cp.burnin) & (k + cp.step <= cp.k_max)
        do_switch = (
            cond & gate if adapting is True else adapting & cond & gate
        )
        new_k = jnp.where(do_switch, k + cp.step, k)
        count_iter1 = jnp.where(do_switch, 0, state.count_iter) + 1

    # --- schedule's time-triggered k (capped at the cell's ACTIVE workers —
    # with n as a grid axis the class-side cap is a per-cell value)
    if has_schedule:
        n_passed = jnp.sum(sim_time >= cp.switch_times).astype(i32)
        k_sched = jnp.minimum(cp.k0 + cp.step * n_passed, cp.n_active)
        new_k = _sel(preds.is_schedule, k_sched, new_k)

    new_state = state._replace(
        k=new_k,
        count_negative=(
            state.count_negative if counting is False
            else _sel(counting, jnp.where(do_switch, 0, count_neg1),
                      state.count_negative)
        ),
        count_iter=(
            state.count_iter if adapting is False
            else _sel(adapting, count_iter1, state.count_iter)
        ),
        prev_grad=(
            state.prev_grad if not has_pflug
            else _sel_tree(
                preds.is_pflug,
                jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                state.prev_grad,
            )
        ),
        prev_sketch=(
            state.prev_sketch if not has_sketched
            else _sel(preds.is_sketched, z, state.prev_sketch)
        ),
        ema_mean=(
            state.ema_mean if not has_vr
            else _sel_tree(
                preds.is_vr,
                jax.tree.map(
                    lambda m: jnp.where(do_switch, jnp.zeros_like(m), m), ema1
                ),
                state.ema_mean,
            )
        ),
        ema_sq=(
            state.ema_sq if not has_vr
            else _sel(preds.is_vr, jnp.where(do_switch, 0.0, ema_sq1),
                      state.ema_sq)
        ),
        have_prev=(
            state.have_prev if adapting is False
            else _sel(adapting, jnp.asarray(True), state.have_prev)
        ),
        n_switches=(
            state.n_switches if adapting is False
            # do_switch already carries the adapting mask; int add is exact,
            # so non-adaptive lanes' +0 reproduces their branches' pass-through.
            else state.n_switches + do_switch.astype(i32)
        ),
    )
    return new_state, new_k


# ---------------------------------------------------------------- the engine


class _SweepCarry(NamedTuple):
    params: Any
    ctrl_state: _CtrlState
    sim_time: jax.Array
    key: jax.Array


def _make_run_one_moded(
    source: GradSource,
    n_workers: int,
    params0,
    data,
    grad_fn: Callable,
    mean_loss: Callable,
    sketch_dim: int,
    n_full: int,
    rem: int,
    eval_every: int,
    unroll: int,
    sig: GridSignature,
):
    """Execution-mode-aware run_one: the ``execmode.ExecCarry`` superset
    threaded through the same eval-block scaffolding, with a per-cell
    select over the execution-mode *tails* the signature admits.  Every
    tail is computed and the cell's is selected, so ``mode`` is
    an ordinary traced grid leaf — the signature's modes share ONE compiled
    program and repopulating a same-signature grid never retraces.

    The mode-invariant prelude (key split, per-slot sampling, renewal
    residuals, fastest-K ranking/order statistic, comm) is hoisted OUT of
    the switch (``execmode.make_mode_prelude_and_tails``), so only mode
    bookkeeping — which gradient stack to differentiate, how snapshots /
    staleness / clocks evolve — is selected per cell; in particular
    kbatch's n_slots-event inner scan is traced only when kbatch is in the
    signature.  The sync tail performs the pre-mode arithmetic op for op
    (for sync cells ``pending`` is never set, so the hoisted residuals ARE
    the fresh draw bit for bit), and the async tails are the SAME step code
    the looped ``run_monte_carlo(mode=...)`` traces — sweep cells stay
    bitwise-equal to the looped engine in every mode."""
    modes = sig.modes
    mode_remap = (
        None if len(modes) in (1, len(execmode.MODES))
        else jnp.asarray(_static_remap(modes, len(execmode.MODES)))
    )

    def run_one(cp: _CellParams, replica_key, lane_data):
        # The stale closures read the lane's own copy of the data
        # (gradsource.lane_data); build_stale emits the per-worker shard
        # reshape first, as the looped engine does.
        stale_grad, shard_grad_at = source.build_stale(lane_data, n_workers)
        # Per-cell constants, hoisted out of the iteration scan: the family
        # select masks, controller predicates, and mode index are all pure
        # functions of the cell's kind leaves.
        fam_masks = family_select_masks(cp.strag_kinds)
        ctrl_preds = _ctrl_preds(cp, sig.ctrl_kinds)
        mode_local = cp.mode if mode_remap is None else mode_remap[cp.mode]

        def draw(sub, sim_time):
            pm = (
                apply_rate_schedule(
                    cp.strag_p, cp.sched_mode, cp.sched_leaf,
                    cp.sched_times, cp.sched_scales, sim_time,
                )
                if sig.with_schedule
                else cp.strag_p
            )
            return sample_times_selected(fam_masks, pm, sub)

        comm_time = (
            (lambda k: cp.comm_alpha + cp.comm_beta * k.astype(jnp.float32))
            if sig.with_comm
            else None
        )

        def ctrl_update(state, g, sim_time, stats):
            return _ctrl_update(
                cp, state, g, sim_time, stats, sketch_dim, sig.ctrl_kinds,
                preds=ctrl_preds,
            )

        # The robustness axes: per-cell closures over traced fault/agg
        # leaves, gated on the signature's STATIC family sets — absent
        # families/aggregators trace nothing, fault-free and mean cells in a
        # robust program ride exact-1.0 multiplies and where passthroughs
        # (the bitwise contract; see faults.make_fault_fns).
        fault_fns = faults.make_fault_fns(
            cp.fault_kinds, cp.fault_onset, cp.fault_param,
            sig.fault_kinds, params0, n_workers,
        )
        robust_sel = aggregation.make_robust_select(
            cp.agg_kind, cp.agg_param, sig.agg_kinds
        )

        prelude, tails = execmode.make_mode_prelude_and_tails(
            n_slots=n_workers,
            draw=draw,
            sync_grad=grad_fn,
            stale_grad=stale_grad,
            shard_grad_at=shard_grad_at,
            comm_time=comm_time,
            eta=cp.eta,
            ctrl_update=ctrl_update,
            faults=fault_fns,
            robust_agg=robust_sel,
        )

        if len(modes) == 1:

            def one_step(carry: execmode.ExecCarry, _):
                return tails[modes[0]](carry, prelude(carry))

        else:
            sel_tails = tuple(tails[m] for m in modes)

            def one_step(carry: execmode.ExecCarry, _):
                # What vmap makes of a lax.switch on a per-lane index, minus
                # one thing: the switch would broadcast every operand to the
                # lanes, the closed-over data too, turning the shared
                # ``X @ w`` into a per-lane product that rounds differently
                # from the looped engine's.
                p = prelude(carry)
                outs = [tail(carry, p) for tail in sel_tails]
                with jax.named_scope("repro.async_state"):
                    return jax.tree.map(
                        lambda *xs: jax.lax.select_n(mode_local, *xs), *outs
                    )

        def eval_block(carry: execmode.ExecCarry, length: int):
            carry, ks = jax.lax.scan(
                one_step, carry, None, length=length, unroll=min(unroll, length)
            )
            with jax.named_scope("repro.eval"):
                loss = mean_loss(carry.params, cp.n_active)
            return carry, (carry.sim_time, loss, ks[-1])

        carry = execmode.init_exec_carry(
            params0, n_workers, _ctrl_init(cp, params0, sketch_dim), replica_key
        )
        records = None
        if n_full:
            carry, records = jax.lax.scan(
                lambda c, _: eval_block(c, eval_every), carry, None, length=n_full
            )
        if rem:
            carry, last = eval_block(carry, rem)
            last = jax.tree.map(lambda x: x[None], last)
            records = (
                last
                if records is None
                else jax.tree.map(lambda a, b: jnp.concatenate([a, b]), records, last)
            )
        return records

    return run_one


# (source.cache_token(), n_workers, num_iters, eval_every, unroll,
#  n_switch_slots, n_sched_slots, sketch_dim, partition, (mc, mr, n_proc),
#  GridSignature) -> jitted grid program.  Jit's own cache handles shapes
# (grid size, params/data shapes) under each entry; the signature key is
# what makes same-signature grid repopulation a cache hit and a new
# signature exactly one new trace.  Bounded LRU (shared implementation with
# montecarlo, REPRO_PROGRAM_CACHE_SIZE-sized): eviction + re-entry retraces
# exactly once.  The same key components determine the traced HLO, which is
# what jax's persistent compilation cache fingerprints — see
# repro.core.cache for the on-disk story.
_PROGRAM_CACHE = _LRUProgramCache(maxsize=_default_program_cache_size())
_N_TRACES = 0


def sweep_cache_stats() -> dict:
    return {"programs": len(_PROGRAM_CACHE), "traces": _N_TRACES}


def clear_sweep_cache() -> None:
    global _N_TRACES
    _PROGRAM_CACHE.clear()
    _N_TRACES = 0


def dispatch_donation() -> tuple:
    """The ``donate_argnums`` the sweep dispatch requests for its freshly
    materialized (never caller-owned) cell-leaf and key buffers — argument
    positions 2 and 3 of the grid program, on BOTH the auto and shard_map
    paths.  CPU XLA has no donation support (it would warn and ignore), so
    only accelerator backends request it; the GPU CI lane asserts this is
    non-empty off-CPU."""
    return (2, 3) if jax.default_backend() in ("gpu", "tpu") else ()


def _build_grid_program(
    source: GradSource,
    n_workers: int,
    num_iters: int,
    eval_every: int,
    unroll: int,
    sketch_dim: int,
    partition: str,
    mesh: Mesh | None,
    sig: GridSignature,
):
    n_full, rem = divmod(num_iters, eval_every)
    # A sync-only signature compiles the lean program (no async carry, no
    # mode switch — byte-identical to the historical all-sync engine); any
    # async mode in the signature selects the unified ExecCarry program.
    # Fault or robust-aggregation axes also route through the moded program
    # (even all-sync): the transforms live in the shared execmode tails —
    # ONE integration point for both engines — and the moded sync tail is
    # already pinned bitwise-equal to the lean path, so the lean program
    # stays byte-identical to today's for the grids that can use it.
    with_moded = (
        sig.modes != (execmode.MODE_SYNC,)
        or bool(sig.fault_kinds)
        or sig.agg_kinds != (aggregation.AGG_MEAN,)
    )

    def make_run_one(params0, data):
        """run_one closing over (possibly device-local) data — built inside
        the shard_map body so no tracers are captured across its boundary."""
        fns = source.build(data, n_workers)
        grad_fn = fns.grad

        def mean_loss(params, n_active):
            return fns.eval_loss_active(params, n_active)

        if with_moded:
            return _make_run_one_moded(
                source, n_workers, params0, data,
                grad_fn, mean_loss, sketch_dim, n_full, rem, eval_every, unroll,
                sig,
            )

        def run_one(cp: _CellParams, replica_key, lane_data):
            del lane_data  # the sync program has no stale gradients
            # Per-cell constants, hoisted out of the iteration scan (pure
            # functions of the cell's kind leaves).
            fam_masks = family_select_masks(cp.strag_kinds)
            ctrl_preds = _ctrl_preds(cp, sig.ctrl_kinds)

            def one_step(carry: _SweepCarry, _):
                k = carry.ctrl_state.k
                with jax.named_scope("repro.sampler"):
                    new_key, sub = jax.random.split(carry.key)
                    # Signature pruning: the rate-schedule drift and the
                    # comm-model adds are traced only when some cell can
                    # select them (each is a bitwise no-op for the cells
                    # that don't).
                    pm = (
                        apply_rate_schedule(
                            cp.strag_p, cp.sched_mode, cp.sched_leaf,
                            cp.sched_times, cp.sched_scales, carry.sim_time,
                        )
                        if sig.with_schedule
                        else cp.strag_p
                    )
                    times = sample_times_selected(fam_masks, pm, sub)
                mask, t_iter = aggregation.fastest_k_mask_time(times, k)
                if sig.with_comm:
                    t_iter = t_iter + (
                        cp.comm_alpha + cp.comm_beta * k.astype(jnp.float32)
                    )
                with jax.named_scope("repro.grad"):
                    g = grad_fn(carry.params, mask, k)
                params = execmode.sgd_update(carry.params, g, cp.eta)
                sim_time = carry.sim_time + t_iter
                with jax.named_scope("repro.controller"):
                    ctrl_state, _ = _ctrl_update(
                        cp, carry.ctrl_state, g, sim_time, execmode.zero_stats(k),
                        sketch_dim, sig.ctrl_kinds, preds=ctrl_preds,
                    )
                return _SweepCarry(params, ctrl_state, sim_time, new_key), k

            def eval_block(carry: _SweepCarry, length: int):
                carry, ks = jax.lax.scan(
                    one_step, carry, None, length=length, unroll=min(unroll, length)
                )
                with jax.named_scope("repro.eval"):
                    loss = mean_loss(carry.params, cp.n_active)
                return carry, (carry.sim_time, loss, ks[-1])

            carry = _SweepCarry(
                params=params0,
                ctrl_state=_ctrl_init(cp, params0, sketch_dim),
                sim_time=jnp.asarray(0.0, jnp.float32),
                key=replica_key,
            )
            records = None
            if n_full:
                carry, records = jax.lax.scan(
                    lambda c, _: eval_block(c, eval_every), carry, None, length=n_full
                )
            if rem:
                carry, last = eval_block(carry, rem)
                last = jax.tree.map(lambda x: x[None], last)
                records = (
                    last
                    if records is None
                    else jax.tree.map(lambda a, b: jnp.concatenate([a, b]), records, last)
                )
            return records

        return run_one

    # The traced program vmaps ONCE over the flattened (Gp*Rp,) lane axis —
    # deliberately NOT vmap(vmap(...)) over (cells, replicas): nesting the
    # batch axes changes XLA CPU's fusion choices at last-ulp level in the
    # larger graphs (mixed-mode switch, hetero fleets, LM losses), breaking
    # the sweep-vs-looped bitwise contract.  The 2-D mesh lives entirely in
    # the DATA layout: the flat lane axis is sharded over BOTH mesh axes
    # (cell-major lane order, so a ("cells", "replicas") split assigns each
    # device a contiguous lane block), and the arithmetic per lane is the
    # historical single-vmap program, bit for bit.
    flat_spec = P(("cells", "replicas"))

    def vmap_lanes(params0, data, cells, keys):
        # Shared data for the sync closures, each lane's own copy for the
        # stale ones (gradsource.lane_data), as in run_monte_carlo.
        return jax.vmap(make_run_one(params0, data))(
            cells, keys, lane_data(data, keys.shape[0])
        )

    def run_grid(params0, data, cells: _CellParams, keys):
        global _N_TRACES
        _N_TRACES += 1
        if partition == "shard_map":

            def body(p0, d, c, k):
                return vmap_lanes(p0, d, c, k)

            sharded = jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    jax.tree.map(lambda _: P(), params0),
                    jax.tree.map(lambda _: P(), data),
                    jax.tree.map(lambda _: flat_spec, cells),
                    flat_spec,
                ),
                out_specs=flat_spec,
                check_vma=False,
            )
            return sharded(params0, data, cells, keys)
        return vmap_lanes(params0, data, cells, keys)

    # The cell-leaf and key buffers are freshly materialized inside every
    # run_sweep dispatch (never caller-owned), so donating them lets XLA
    # reuse their allocations for the scan carries/outputs instead of
    # holding both live across the call — on the auto AND shard_map paths
    # (the jit wraps both).
    return jax.jit(run_grid, donate_argnums=dispatch_donation())


def _lane_layout(stacked: _CellParams, G: int, R: int, mc: int, mr: int):
    """The flat lane axis of a G x R grid on an (mc, mr) mesh, built on the host.

    Each grid axis is padded to its mesh-axis multiple; the padded lanes are
    sliced off before results are returned.  Cells pad with EMPTY all-zero
    parameter rows (inert: zero-rate samplers draw +inf, n_active=0 holds
    all data out — and any junk they compute stays confined to their own
    lanes, there is no cross-lane arithmetic — never copies of a real cell,
    so padding can't amplify real compute); replicas pad by repeating key 0.
    The padded (Gp, Rp) grid then flattens CELL-MAJOR into the (Gp*Rp,)
    lane axis the program vmaps over, so sharding that one axis over
    ("cells", "replicas") hands each device a contiguous equal lane block.

    Returns ``(flat_cells, key_index, Gp, Rp)``: ``stacked`` with every leaf
    a NumPy array of Gp*Rp lanes (plain copies, no arithmetic), and the
    index of the replica key each lane draws (``keys[key_index]``).
    """
    Gp, Rp = G + (-G) % mc, R + (-R) % mr
    if Gp * Rp == mc * mr:
        # One lane per device: pad the replicas once more so every device
        # holds at least two (XLA drops size-1 batch dimensions, which
        # would hand a one-lane program differently rounding kernels —
        # run_monte_carlo pads a lone replica the same way).
        Rp += mr
    cell_idx = np.repeat(np.arange(Gp), Rp)
    rep_idx = np.tile(np.arange(Rp), Gp)

    def lanes(a):
        a = np.asarray(a)
        if Gp > G:
            a = np.concatenate([a, np.zeros((Gp - G,) + a.shape[1:], a.dtype)])
        return a[cell_idx]

    return jax.tree.map(lanes, stacked), np.where(rep_idx < R, rep_idx, 0), Gp, Rp


@functools.partial(jax.profiler.annotate_function, name="repro.sweep.run")
def run_sweep_source(
    source: GradSource,
    params0,
    data,
    n_workers: int,
    cases: Sequence[SweepCase],
    num_iters: int,
    keys: jax.Array | None = None,
    key: jax.Array | None = None,
    n_replicas: int | None = None,
    eval_every: int = 10,
    unroll: int | None = None,
    n_switch_slots: int | None = None,
    n_sched_slots: int | None = None,
    partition: str = "auto",
    specialize: bool = True,
    mesh: Mesh | None = None,
) -> SweepResult:
    """Run a G-cell x R-replica grid of fastest-k SGD as ONE jitted dispatch.

    Generic over the gradient source: ``data`` is the source's data pytree
    (``(X, y)`` for ``PerExampleSource`` — ``run_sweep`` is the thin
    per-example wrapper — a token batch dict for ``LMSource``), threaded
    through the compiled program as a traced argument and replicated across
    devices.

    ``n_workers`` is the grid's **slot count**: every cell is padded to it,
    and a cell's *active* worker count is its ``controller.n_workers``
    (slots past it sample +inf response times and their data shards are
    held out of the gradient and the eval loss) — so n itself is an
    ordinary grid axis.  Cells whose controllers all use the full slot
    count reproduce the pre-heterogeneity engine bit for bit.

    ``specialize`` (default True) enables **branch-signature
    specialization**: the grid's ``GridSignature`` — the *sets* of
    controller kinds and execution modes plus feature flags (rate
    schedules, comm models) actually present — is derived at dispatch, and
    the compiled program prunes every switch branch the signature excludes
    (under vmap a switch computes ALL branches for ALL cells every
    iteration, so fixed-composition grids otherwise pay a multiplicative
    all-branches tax).  Programs are cached per signature: repopulating a
    same-signature grid never retraces, and a new signature compiles
    exactly once.  ``specialize=False`` keeps the fully grid-agnostic
    program (all kinds/modes/features traced; any same-shape grid
    repopulates with zero retraces) — use it when the grid composition
    itself varies call to call.  Straggler families are never specialized
    (see ``GridSignature``).  Specialization changes which branches are
    traced, never the arithmetic of the branches that run: cells are
    bitwise-equal to looped ``run_monte_carlo`` either way.

    ``unroll=None`` (the default) picks the scan unroll from the signature:
    4 — the measured sweet spot for all-branch bodies (identical warm
    runtime to 8, ~5x cheaper compile on a 15-cell grid) — rising to 8 for
    pruned sync-only single-controller programs, whose small step bodies
    can afford deeper unrolling.  Unroll never affects the arithmetic —
    trajectories are bitwise-identical across unroll values.

    ``partition`` chooses how the (G, R) grid is laid out across the 2-D
    ``("cells", "replicas")`` device mesh (the padded grid flattens
    cell-major into one lane axis sharded over BOTH mesh axes — the traced
    program stays the historical single-vmap flat program, so the mesh
    affects placement, never arithmetic):

    * ``"auto"`` — inputs are placed with ``NamedSharding`` and XLA's
      sharding propagation partitions the whole program (the default;
      degenerates to plain vmap on one device);
    * ``"shard_map"`` — explicit per-device blocks via
      ``jax.shard_map`` (fallback for backends where automatic
      propagation misbehaves);
    * ``"none"`` — no device placement (single-device debugging).

    ``mesh`` resolution (ignored under ``"none"``): the explicit argument
    wins, else an ambient ``repro.shardctx.sweep_mesh`` context, else
    ``repro.launch.mesh.make_sweep_mesh(G, R)`` — a mesh over **global**
    devices, which spans processes whenever ``jax.distributed`` is
    initialized (every participating process must make the identical call,
    the usual jax SPMD contract; placement materializes only each process's
    addressable shards).  The mesh must carry axes ``("cells",
    "replicas")``.

    Each grid axis is padded to its mesh-axis multiple and the padding is
    dropped before results are returned: the cell axis with *empty*
    all-zero parameter rows (inert lanes — never gathered copies of a real
    cell, so padding cannot amplify real compute) and the replica axis by
    repeating key 0.  Mesh shape never affects values: results are
    bitwise-identical across every mesh shape and both dispatch paths
    (tests/test_podscale.py pins this).

    Every cell (g, r) is bitwise-equal to
    ``run_monte_carlo(..., controller=cases[g].controller, ...)``'s replica r
    with the same key.

    The host work of a call shows in a profile as a ``repro.sweep.run``
    span around the call and one child span per phase:
    ``repro.sweep.cells`` (signature, per-cell leaves, stack),
    ``repro.sweep.layout`` (the padded flat lane leaves, built on the host
    with NumPy, and the one gather of the replica keys),
    ``repro.sweep.place`` (every lane leaf to the device once, on the mesh
    if there is one), ``repro.sweep.program``
    (program-cache lookup, with ``repro.sweep.build`` on a miss),
    ``repro.sweep.call`` (the dispatch; a trace and compile land here) and
    ``repro.sweep.unpad`` (slicing the padding off the outputs).
    """
    if not cases:
        raise ValueError("cases must be non-empty")
    labels = [c.name() for c in cases]
    if len(set(labels)) != len(labels):
        dupes = sorted({l for l in labels if labels.count(l) > 1})
        raise ValueError(
            f"duplicate cell labels {dupes}: give identically-typed cases "
            "distinct SweepCase.label values (summarize_cells keys on them)"
        )
    if keys is None:
        if key is None or n_replicas is None:
            raise ValueError("pass either keys=(R keys) or key= and n_replicas=")
        keys = jax.random.split(key, n_replicas)
    source.check(data, n_workers)
    if eval_every <= 0:
        raise ValueError(f"eval_every must be positive, got {eval_every}")
    if num_iters <= 0:
        raise ValueError(f"num_iters must be positive, got {num_iters}")
    if partition not in ("auto", "shard_map", "none"):
        raise ValueError(f"unknown partition {partition!r}")

    with TraceAnnotation("repro.sweep.cells"):
        if n_switch_slots is None:
            n_switch_slots = max(
                [1]
                + [
                    len(list(c.controller.switch_times))
                    for c in cases
                    if isinstance(c.controller, ScheduleController)
                ]
            )
        if n_sched_slots is None:
            n_sched_slots = max(
                [1]
                + [
                    len(c.straggler.schedule.times)
                    for c in cases
                    if isinstance(c.straggler, WorkerFleet) and c.straggler.schedule
                ]
            )
        # The grid's static sketch layout: every sketched cell must share one
        # sketch_dim (it is the prev_sketch carry shape, baked into the trace).
        sketch_dims = {
            c.controller.sketch_dim
            for c in cases
            if isinstance(c.controller, SketchedPflugController)
        }
        if len(sketch_dims) > 1:
            raise ValueError(
                f"sketched cells disagree on sketch_dim ({sorted(sketch_dims)}); "
                "one sweep supports a single static sketch layout"
            )
        sketch_dim = sketch_dims.pop() if sketch_dims else 1
        # The grid's branch signature selects the program family: specialized
        # programs trace only the branches the signature admits (cached per
        # signature — same-signature repopulation never retraces), while
        # specialize=False collapses every grid onto the fully-grid-agnostic
        # signature (retaining the historical lean-program split for all-sync
        # grids).  Either way `mode`/kind assignments stay traced leaves.
        sig = grid_signature(cases, n_workers) if specialize else _full_signature(cases)
        if unroll is None:
            unroll = _auto_unroll(sig)
        G, R = len(cases), keys.shape[0]
        cells_np = [
            _cell_of(c, n_workers, n_switch_slots, n_sched_slots, sketch_dim, params0)
            for c in cases
        ]
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *cells_np)

    with TraceAnnotation("repro.sweep.layout"):
        if partition == "none":
            mesh = None
            mc = mr = n_proc = 1
        else:
            if mesh is None:
                from repro import shardctx

                mesh = shardctx.current_sweep_mesh()
            if mesh is None:
                from repro.launch import mesh as mesh_lib

                mesh = mesh_lib.make_sweep_mesh(G, R)
            if tuple(mesh.axis_names) != ("cells", "replicas"):
                raise ValueError(
                    "sweep mesh must have axes ('cells', 'replicas'), got "
                    f"{tuple(mesh.axis_names)}"
                )
            mc, mr = mesh.shape["cells"], mesh.shape["replicas"]
            n_proc = jax.process_count()

        flat_cells, key_index, Gp, Rp = _lane_layout(stacked, G, R, mc, mr)
        flat_keys = keys[key_index]

    with TraceAnnotation("repro.sweep.place"):
        if mesh is None:
            flat_cells = jax.device_put(flat_cells)
        else:
            from repro.launch.sharding import place_spanning

            lanes = NamedSharding(mesh, P(("cells", "replicas")))
            flat_cells, flat_keys = place_spanning((flat_cells, flat_keys), lanes)
            params0, data = place_spanning((params0, data), NamedSharding(mesh, P()))

    with TraceAnnotation("repro.sweep.program"):
        cache_key = (
            source.cache_token(),
            n_workers,
            int(num_iters),
            int(eval_every),
            int(unroll),
            int(n_switch_slots),
            int(n_sched_slots),
            int(sketch_dim),
            partition,
            (mc, mr, n_proc),
            sig,
        )
        program = _PROGRAM_CACHE.get(cache_key)
        if program is None:
            with TraceAnnotation("repro.sweep.build"):
                program = _build_grid_program(
                    source, n_workers, num_iters, eval_every, unroll,
                    sketch_dim, partition, mesh, sig,
                )
            _PROGRAM_CACHE[cache_key] = program
    with TraceAnnotation("repro.sweep.call"):
        times, losses, ks = program(params0, data, flat_cells, flat_keys)

    with TraceAnnotation("repro.sweep.unpad"):
        n_evals = times.shape[1]
        times, losses, ks = (
            a.reshape(Gp, Rp, n_evals)[:G, :R] for a in (times, losses, ks)
        )
        iteration = np.minimum(
            np.arange(1, n_evals + 1) * eval_every, num_iters
        ).astype(np.int64)
        return SweepResult(
            time=times,
            loss=losses,
            k=ks,
            iteration=iteration,
            labels=tuple(c.name() for c in cases),
        )


def run_sweep(
    per_example_loss_fn: Callable,  # (params, X, y) -> per-example losses (m,)
    params0,
    X: jax.Array,
    y: jax.Array,
    n_workers: int,
    cases: Sequence[SweepCase],
    num_iters: int,
    keys: jax.Array | None = None,
    key: jax.Array | None = None,
    n_replicas: int | None = None,
    eval_every: int = 10,
    unroll: int | None = None,
    n_switch_slots: int | None = None,
    n_sched_slots: int | None = None,
    partition: str = "auto",
    specialize: bool = True,
    mesh: Mesh | None = None,
) -> SweepResult:
    """The historical per-example entry point: a thin wrapper over
    ``run_sweep_source`` with the reference ``PerExampleSource`` and
    ``data=(X, y)``, pinned bitwise-equal to the pre-GradSource engine.
    See ``run_sweep_source`` for semantics."""
    return run_sweep_source(
        PerExampleSource(per_example_loss_fn),
        params0,
        (X, y),
        n_workers=n_workers,
        cases=cases,
        num_iters=num_iters,
        keys=keys,
        key=key,
        n_replicas=n_replicas,
        eval_every=eval_every,
        unroll=unroll,
        n_switch_slots=n_switch_slots,
        n_sched_slots=n_sched_slots,
        partition=partition,
        specialize=specialize,
        mesh=mesh,
    )
