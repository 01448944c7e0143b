"""Vectorized Monte-Carlo engine for (adaptive) fastest-k SGD.

The paper's headline artifacts (Figs. 2-3) are *distributions* of
error-vs-wall-clock trajectories over many seeds, not single runs.  This
module runs R independent replicas of the fastest-k simulation as **one**
compiled XLA program:

  * ``jax.lax.scan`` over iterations (grouped into eval blocks),
  * ``jax.vmap`` over replica PRNG keys,
  * periodic loss evaluation *inside* the scan — the host sees nothing
    until the whole R-replica trajectory tensor is materialized,
  * any registered controller/straggler-model pair threaded through a
    single policy-agnostic carry (the controller contributes an opaque
    pytree state via its ``init``/``update`` interface).

The gradient source is pluggable (``repro.core.gradsource.GradSource``):
the engine consumes only the closures the source builds — a masked eq.-(2)
aggregate gradient, stale per-worker-shard gradients for the async modes,
and the eval losses.  ``run_monte_carlo`` keeps the historical per-example
``(loss_fn, X, y)`` signature as a thin wrapper over the reference
``PerExampleSource``; ``run_monte_carlo_source`` is the generic entry point
(e.g. ``repro.launch.lm_source.LMSource`` for a real LM train step).

Compiled programs are cached at module level in a bounded LRU (so long-lived
sweep processes don't accumulate executables without limit), keyed on
everything baked into the trace (the source's ``cache_token()``, n_workers,
controller/straggler/comm values, eta, iteration counts, unroll): repeated
calls with the same configuration — a looped grid, a benchmark's warm-up +
timed run — reuse the first trace instead of rebuilding
``jit(vmap(run_one))`` per call.  Data (params0, the source's data pytree,
keys) are traced *arguments*, so jit's own shape cache handles varying
shapes per configuration.

The per-iteration hot path samples and ranks worker times once
(``aggregation.fastest_k_draw``) and computes the eq.-(2) weighted gradient
through a per-worker segment sum (the source's ``weighted_loss``) — no
length-m per-example weight vector is ever materialized.

``repro.core.simulate.simulate_fastest_k`` is a thin R=1 wrapper over this
engine; benchmarks drive it directly with R >= 32, and whole controller x
straggler grids run as a *single* dispatch via ``repro.core.sweep``.

API sketch::

    keys = jax.random.split(jax.random.PRNGKey(0), 32)
    result = run_monte_carlo(
        per_example_loss_fn, w0, X, y, n_workers=50,
        controller=PflugController(n_workers=50), straggler=Exponential(),
        eta=1e-2, num_iters=40_000, keys=keys, eval_every=500,
    )
    stats = summarize(result)   # mean / ci95 arrays over the replica axis
"""

from __future__ import annotations

import collections
import dataclasses
import inspect
import math
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, execmode, faults as faultsmod
from repro.core.gradsource import GradSource, PerExampleSource, lane_data
from repro.core.straggler import (
    StragglerModel,
    WorkerFleet,
    apply_rate_schedule,
    pack_params_per_worker,
    pack_schedule,
    sample_times_per_worker,
)

__all__ = [
    "MonteCarloResult",
    "run_monte_carlo",
    "run_monte_carlo_source",
    "summarize",
    "program_cache_stats",
    "clear_program_cache",
    "set_program_cache_size",
    "program_cache_size",
]

_Z95 = 1.959963984540054  # two-sided 95% normal quantile


class _Carry(NamedTuple):
    params: object
    ctrl_state: object  # opaque controller pytree — policy-agnostic
    sim_time: jax.Array
    key: jax.Array


class MonteCarloResult(NamedTuple):
    """Eval-point trajectories for R replicas.

    ``time``/``loss``/``k`` have shape (R, n_evals); ``iteration`` has shape
    (n_evals,) and gives the iteration count at each eval point (multiples of
    ``eval_every``, with a final partial point at ``num_iters`` when it is
    not a multiple).
    """

    time: jax.Array
    loss: jax.Array
    k: jax.Array
    iteration: np.ndarray


def _hashable(obj):
    """Frozen-dataclass config objects -> hashable cache-key components.

    Handles list-valued fields (e.g. ScheduleController.switch_times) by
    tuple-ifying; falls back to repr for anything exotic."""
    if obj is None:
        return None
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (
            type(obj).__module__,
            type(obj).__qualname__,
            tuple(
                (f.name, _hashable(getattr(obj, f.name)))
                for f in dataclasses.fields(obj)
            ),
        )
    if isinstance(obj, (list, tuple)):
        return tuple(_hashable(x) for x in obj)
    if isinstance(obj, np.ndarray):
        # repr() elides large arrays ('...'), which could collide two
        # different configs onto one cache key — hash the actual contents.
        return ("ndarray", obj.shape, str(obj.dtype), obj.tobytes())
    try:
        hash(obj)
        return obj
    except TypeError:
        return repr(obj)


class _LRUProgramCache:
    """Bounded least-recently-used compiled-program cache.

    Long-lived sweep/benchmark processes touch many configurations; an
    unbounded dict would pin every compiled executable (and its device
    buffers) for the process lifetime.  Eviction just drops the jitted
    callable — re-entering an evicted configuration retraces exactly once
    (pinned by tests/test_program_cache.py).  ``maxsize`` is mutable so
    tests can shrink it.
    """

    def __init__(self, maxsize: int = 32):
        self.maxsize = maxsize
        self._entries: collections.OrderedDict = collections.OrderedDict()

    def get(self, key):
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
        return entry

    def __setitem__(self, key, value):
        self._entries[key] = value
        self._entries.move_to_end(key)
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)

    def __len__(self):
        return len(self._entries)

    def clear(self):
        self._entries.clear()

    def resize(self, maxsize: int):
        """Set ``maxsize``, evicting least-recently-used entries down to it."""
        if maxsize < 1:
            raise ValueError(f"program cache maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)


def _default_program_cache_size() -> int:
    """Default program-cache capacity: ``REPRO_PROGRAM_CACHE_SIZE`` if set
    (read at import, shared by both engines), else 32."""
    raw = os.environ.get("REPRO_PROGRAM_CACHE_SIZE", "")
    if not raw:
        return 32
    try:
        size = int(raw)
    except ValueError:
        raise ValueError(
            f"REPRO_PROGRAM_CACHE_SIZE={raw!r} is not an integer"
        ) from None
    if size < 1:
        raise ValueError(f"REPRO_PROGRAM_CACHE_SIZE must be >= 1, got {size}")
    return size


def set_program_cache_size(maxsize: int) -> None:
    """Resize the compiled-program caches of BOTH engines (this module's and
    repro.core.sweep's), evicting LRU entries past the new capacity.  An
    evicted configuration retraces exactly once on re-entry — arithmetic is
    never affected, only trace count (tests/test_program_cache.py)."""
    import sys

    _PROGRAM_CACHE.resize(maxsize)
    sweep = sys.modules.get("repro.core.sweep")
    if sweep is not None:  # lazy: sweep imports this module, not vice versa
        sweep._PROGRAM_CACHE.resize(maxsize)


def program_cache_size() -> int:
    """Current capacity of the looped engine's program cache."""
    return _PROGRAM_CACHE.maxsize


# config-key -> jitted (params0, data, keys) -> (times, losses, ks).
_PROGRAM_CACHE = _LRUProgramCache(maxsize=_default_program_cache_size())
# Incremented inside the traced function body, i.e. once per actual trace.
# Tests assert a second identical call leaves this unchanged.
_N_TRACES = 0


def program_cache_stats() -> dict:
    """Module-level compiled-program cache introspection (for tests/benchmarks)."""
    return {"programs": len(_PROGRAM_CACHE), "traces": _N_TRACES}


def clear_program_cache() -> None:
    global _N_TRACES
    _PROGRAM_CACHE.clear()
    _N_TRACES = 0


def _vmap_lanes(run_lane):
    """``run_all(params0, data, keys, n_active)``: ``run_lane(params0, data,
    lane_data, key, n_active)`` vmapped over the replica keys.  ``data`` is
    shared by every lane; ``lane_data`` is each lane's own copy
    (``gradsource.lane_data``), which the stale-gradient closures use — the
    sweep engine lays its lanes out the same way."""

    def run_all(params0, data, keys, n_active_arg=None):
        # A lone replica runs as a pair (its key twice) and keeps lane 0:
        # XLA drops size-1 batch dimensions, which would hand a one-lane
        # program different (differently rounding) product kernels.
        n = keys.shape[0]
        lanes = keys if n > 1 else jnp.concatenate([keys, keys])
        out = jax.vmap(run_lane, in_axes=(None, None, 0, 0, None))(
            params0, data, lane_data(data, lanes.shape[0]), lanes, n_active_arg
        )
        return jax.tree.map(lambda a: a[:n], out)

    return run_all


def _build_program(
    source: GradSource,
    n_workers: int,
    controller,
    straggler: StragglerModel,
    comm,
    eta: float,
    num_iters: int,
    eval_every: int,
    unroll: int,
):
    n_full, rem = divmod(num_iters, eval_every)

    # Heterogeneous fleets go through the per-worker packed protocol — the
    # SAME in-graph functions the sweep engine traces, with the packed
    # matrices baked in as constants, so a sweep cell carrying this fleet's
    # rows is bitwise-equal to this program.  Scalar models keep the original
    # class path untouched (homogeneous trajectories stay bit-stable).
    is_fleet = isinstance(straggler, WorkerFleet)
    if is_fleet:
        pmat_np, kinds_np, n_active = pack_params_per_worker(straggler, n_workers)
        n_knots = len(straggler.schedule.times) if straggler.schedule else 0
        sched_np = pack_schedule(straggler.schedule, max(1, n_knots))

    def run_lane(params0, data, lane_data, replica_key, n_active_arg=None):
        global _N_TRACES
        _N_TRACES += 1  # Python side effect: fires once per trace, never per run
        del lane_data  # the sync program has no stale gradients
        fns = source.build(data, n_workers)
        grad_fn = fns.grad

        if is_fleet:
            pmat = jnp.asarray(pmat_np)
            kinds = jnp.asarray(kinds_np)
            sched = tuple(jnp.asarray(a) for a in sched_np)

            def draw(sub, sim_time, k):
                pm = apply_rate_schedule(pmat, *sched, sim_time)
                # Deliberately the FULL family sampler, never restricted to
                # the fleet's own families: the sampler subgraph must be
                # structurally identical to the sweep engine's, because XLA
                # CPU compiles structurally different sampler graphs with
                # last-ulp differences in the response-time chain (see
                # GridSignature's docstring in repro.core.sweep).
                times = sample_times_per_worker(kinds, pm, sub)
                mask, t = aggregation.fastest_k_mask_time(times, k)
                if comm is not None:
                    t = t + comm.time(k)
                return mask, t

            def mean_loss(params):
                # n_active rides in as a traced argument, NOT a baked
                # constant: a constant active mask lets XLA fold the masked
                # eval reduction into a different summation order than the
                # sweep engine's traced-leaf version, breaking bitwise
                # equality in the last ulp.
                return fns.eval_loss_active(params, n_active_arg)

        else:

            def draw(sub, sim_time, k):
                del sim_time
                return aggregation.fastest_k_draw(straggler, sub, n_workers, k, comm)

            mean_loss = fns.eval_loss

        def one_step(carry: _Carry, _):
            # k comes from the *previous* controller state (decided before the step).
            k = carry.ctrl_state.k if hasattr(carry.ctrl_state, "k") else carry.ctrl_state[0]
            with jax.named_scope("repro.sampler"):  # the ranks scope their own
                new_key, sub = jax.random.split(carry.key)
                mask, t_iter = draw(sub, carry.sim_time, k)
            with jax.named_scope("repro.grad"):
                g = grad_fn(carry.params, mask, k)
            params = execmode.sgd_update(carry.params, g, eta)
            sim_time = carry.sim_time + t_iter
            with jax.named_scope("repro.controller"):
                ctrl_state, _ = controller.update(carry.ctrl_state, g, sim_time)
            return _Carry(params, ctrl_state, sim_time, new_key), k

        def eval_block(carry: _Carry, length: int):
            """Advance `length` iterations, then evaluate — all in-graph.

            The per-iteration ops are tiny, so loop-trip overhead is material:
            unrolling lets XLA fuse across consecutive iterations.
            """
            carry, ks = jax.lax.scan(
                one_step, carry, None, length=length, unroll=min(unroll, length)
            )
            with jax.named_scope("repro.eval"):
                loss = mean_loss(carry.params)
            return carry, (carry.sim_time, loss, ks[-1])

        def run_one(replica_key):
            carry = _Carry(
                params=params0,
                ctrl_state=controller.init(params0),
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

        return run_one(replica_key)

    return jax.jit(_vmap_lanes(run_lane))


def _build_async_program(
    source: GradSource,
    n_workers: int,
    controller,
    straggler: StragglerModel,
    comm,
    eta: float,
    num_iters: int,
    eval_every: int,
    unroll: int,
    mode: str,
    fault: faultsmod.FaultPlan | None = None,
    agg: str = "mean",
    agg_param: float = 0.1,
):
    """Moded variant: the renewal-process carry (``execmode.ExecCarry``)
    threaded through the same eval-block scaffolding as the sync program.
    The per-event step functions are the SAME code the sweep engine traces
    (``execmode.make_mode_steps``), so a sweep cell is bitwise-equal to this
    program for identical PRNG keys.  This builder serves every async mode,
    and — since the robustness axes live in the shared mode tails — every
    faulty or robust-aggregation configuration too, including ``sync`` ones
    (the moded sync tail is pinned bitwise-equal to the lean sync program,
    so routing through here never changes a fault-free cell's bits)."""
    n_full, rem = divmod(num_iters, eval_every)
    mode_idx = execmode.MODES[mode]

    is_fleet = isinstance(straggler, WorkerFleet)
    n_active = straggler.n_active if is_fleet else n_workers
    if is_fleet:
        pmat_np, kinds_np, _ = pack_params_per_worker(straggler, n_workers)
        n_knots = len(straggler.schedule.times) if straggler.schedule else 0
        sched_np = pack_schedule(straggler.schedule, max(1, n_knots))

    # Packed per-slot fault rows, baked as program constants (the sweep
    # engine carries the identical vectors as traced leaves; the transforms
    # are selects and multiplies either way, so the arithmetic matches bit
    # for bit).  ``fault_present``/``agg_present`` are the STATIC family
    # sets this program traces — mirroring the sweep's GridSignature axes.
    fault_present = faultsmod.plan_kinds_present(fault)
    fk_np, fo_np, fp_np = faultsmod.pack_faults(fault, n_workers, n_active)
    agg_present = tuple(sorted({aggregation.AGG_MEAN, aggregation.AGG_KINDS[agg]}))

    # Class controllers all take the ExecStats signal; tolerate user-supplied
    # policies that predate it (they see the historical 3-argument call).
    try:
        accepts_stats = len(inspect.signature(controller.update).parameters) >= 4
    except (TypeError, ValueError):  # builtins / exotic callables
        accepts_stats = True

    def run_lane(params0, data, lane_data, replica_key, n_active_arg=None):
        global _N_TRACES
        _N_TRACES += 1
        # build_stale goes FIRST: it emits the per-worker shard reshape at
        # the exact op position the historical inline reshape occupied.
        stale_grad, shard_grad_at = source.build_stale(lane_data, n_workers)
        fns = source.build(data, n_workers)

        if is_fleet:
            pmat = jnp.asarray(pmat_np)
            kinds = jnp.asarray(kinds_np)
            sched = tuple(jnp.asarray(a) for a in sched_np)

            def draw(sub, sim_time):
                pm = apply_rate_schedule(pmat, *sched, sim_time)
                # Full sampler, never family-restricted (see the sync
                # builder's draw note).
                return sample_times_per_worker(kinds, pm, sub)

            def mean_loss(params):
                return fns.eval_loss_active(params, n_active_arg)

        else:

            def draw(sub, sim_time):
                del sim_time
                return straggler.sample(sub, n_workers)

            mean_loss = fns.eval_loss

        # comm=None statically omits the receive-cost adds (a bitwise no-op
        # versus adding a zero CommModel's 0.0 — see make_mode_prelude_and_tails).
        comm_time = comm.time if comm is not None else None

        def ctrl_update(state, g, sim_time, stats):
            if accepts_stats:
                return controller.update(state, g, sim_time, stats)
            return controller.update(state, g, sim_time)

        def ctrl_k(state):
            return state.k if hasattr(state, "k") else state[0]

        fault_fns = faultsmod.make_fault_fns(
            jnp.asarray(fk_np), jnp.asarray(fo_np), jnp.asarray(fp_np),
            fault_present, params0, n_workers,
        )
        robust_sel = aggregation.make_robust_select(
            aggregation.AGG_KINDS[agg], float(agg_param), agg_present
        )

        steps = execmode.make_mode_steps(
            n_slots=n_workers,
            draw=draw,
            sync_grad=fns.grad,
            stale_grad=stale_grad,
            shard_grad_at=shard_grad_at,
            comm_time=comm_time,
            eta=eta,
            ctrl_update=ctrl_update,
            ctrl_k=ctrl_k,
            faults=fault_fns,
            robust_agg=robust_sel,
        )
        one_step = steps[mode_idx]

        def eval_block(carry, length: int):
            carry, ks = jax.lax.scan(
                lambda c, _: one_step(c), carry, None,
                length=length, unroll=min(unroll, length),
            )
            with jax.named_scope("repro.eval"):
                loss = mean_loss(carry.params)
            return carry, (carry.sim_time, loss, ks[-1])

        def run_one(replica_key):
            carry = execmode.init_exec_carry(
                params0, n_workers, controller.init(params0), replica_key
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

        return run_one(replica_key)

    return jax.jit(_vmap_lanes(run_lane))


def run_monte_carlo_source(
    source: GradSource,
    params0,
    data,
    n_workers: int,
    controller,
    straggler: StragglerModel | WorkerFleet,
    eta: float,
    num_iters: int,
    keys: jax.Array | None = None,
    key: jax.Array | None = None,
    n_replicas: int | None = None,
    comm: aggregation.CommModel | None = None,
    eval_every: int = 10,
    unroll: int = 8,
    mode: str = "sync",
    fault: faultsmod.FaultPlan | None = None,
    agg: str = "mean",
    agg_param: float = 0.1,
) -> MonteCarloResult:
    """Run R fastest-k SGD replicas of an arbitrary ``GradSource``.

    ``data`` is the source's data pytree (e.g. ``(X, y)`` for
    ``PerExampleSource``, a token batch dict for ``LMSource``), threaded
    through the compiled program as a traced argument.  Everything else —
    replica semantics, execution modes, controllers, heterogeneous fleets —
    matches ``run_monte_carlo`` (whose docstring carries the details); that
    function is literally a wrapper over this one with the reference
    per-example source.

    ``fault`` injects a per-worker ``faults.FaultPlan`` (Byzantine gradient
    corruption and/or mid-run crashes) and ``agg``/``agg_param`` select the
    gradient aggregator (``aggregation.AGG_KINDS``; the default eq.-(2)
    weighted ``"mean"``, or robust ``"trimmed"``/``"median"``/
    ``"geomedian"`` — rejected in ``kbatch`` mode, whose arrivals are
    sequential).  This engine is the per-cell bitwise ground truth the sweep
    engine's fault/robust cells are pinned against.
    """
    if keys is None:
        if key is None or n_replicas is None:
            raise ValueError("pass either keys=(R keys) or key= and n_replicas=")
        keys = jax.random.split(key, n_replicas)
    source.check(data, n_workers)
    if eval_every <= 0:
        raise ValueError(f"eval_every must be positive, got {eval_every}")
    if num_iters <= 0:
        raise ValueError(f"num_iters must be positive, got {num_iters}")
    if mode not in execmode.MODES:
        raise ValueError(
            f"unknown mode {mode!r}; options {sorted(execmode.MODES)}"
        )
    if agg not in aggregation.AGG_KINDS:
        raise ValueError(
            f"unknown aggregator {agg!r}; options {sorted(aggregation.AGG_KINDS)}"
        )
    if agg != "mean" and mode == "kbatch":
        raise ValueError(
            f"robust aggregation ({agg!r}) is not supported in kbatch mode — "
            "kbatch arrivals are sequential, there is no per-worker row "
            "stack to aggregate"
        )
    if fault is not None and not isinstance(fault, faultsmod.FaultPlan):
        raise ValueError(
            f"fault must be a faults.FaultPlan or None, got {fault!r}"
        )
    if isinstance(straggler, WorkerFleet):
        # Mirror sweep._cell_of: a controller sized to more workers than the
        # fleet has active would wait on +inf inactive slots once k exceeds
        # n_active, silently saturating every trajectory's clock to inf.
        cn = getattr(controller, "n_workers", None)
        if cn is not None and cn != straggler.n_active:
            raise ValueError(
                f"fleet has {straggler.n_active} models but "
                f"controller.n_workers={cn}"
            )

    cache_key = (
        source.cache_token(),
        n_workers,
        _hashable(controller),
        _hashable(straggler),
        _hashable(comm),
        float(eta),
        int(num_iters),
        int(eval_every),
        int(unroll),
        str(mode),
        _hashable(fault),
        str(agg),
        float(agg_param),
    )
    program = _PROGRAM_CACHE.get(cache_key)
    if program is None:
        if mode == "sync" and fault is None and agg == "mean":
            program = _build_program(
                source, n_workers, controller, straggler, comm,
                eta, num_iters, eval_every, unroll,
            )
        else:
            # Any fault or robust-aggregation configuration routes through
            # the moded builder (even mode="sync"): the robustness
            # transforms live in the shared execmode tails.
            program = _build_async_program(
                source, n_workers, controller, straggler, comm,
                eta, num_iters, eval_every, unroll, mode,
                fault=fault, agg=agg, agg_param=agg_param,
            )
        _PROGRAM_CACHE[cache_key] = program
    if isinstance(straggler, WorkerFleet):
        times, losses, ks = program(
            params0, data, keys, jnp.asarray(straggler.n_active, jnp.int32)
        )
    else:
        times, losses, ks = program(params0, data, keys)
    iteration = np.minimum(
        np.arange(1, times.shape[1] + 1) * eval_every, num_iters
    ).astype(np.int64)
    return MonteCarloResult(time=times, loss=losses, k=ks, iteration=iteration)


def run_monte_carlo(
    per_example_loss_fn: Callable,  # (params, X, y) -> per-example losses (m,)
    params0,
    X: jax.Array,
    y: jax.Array,
    n_workers: int,
    controller,
    straggler: StragglerModel | WorkerFleet,
    eta: float,
    num_iters: int,
    keys: jax.Array | None = None,
    key: jax.Array | None = None,
    n_replicas: int | None = None,
    comm: aggregation.CommModel | None = None,
    eval_every: int = 10,
    unroll: int = 8,
    mode: str = "sync",
    fault: faultsmod.FaultPlan | None = None,
    agg: str = "mean",
    agg_param: float = 0.1,
) -> MonteCarloResult:
    """Run R independent fastest-k SGD replicas in one jitted program.

    Thin wrapper over ``run_monte_carlo_source`` with the reference
    ``PerExampleSource`` — the historical per-example quadratic path, pinned
    bitwise-equal to the pre-GradSource engine in every mode.

    Replicas are specified either by ``keys`` (an array of R PRNG keys,
    vmapped over axis 0) or by ``key`` + ``n_replicas`` (split internally).
    Each replica reproduces exactly the trajectory the R=1 path
    (``simulate_fastest_k``) produces for its key: the per-iteration RNG
    split, fastest-k masking, SGD update and controller update are shared
    code paths.

    Every worker owns a contiguous shard of m/n examples (the paper's
    horizontal partition); each participating worker contributes the full
    partial gradient over its shard — eq. (2) — realized through a
    per-worker segment sum of the per-example losses.

    ``mode`` selects the execution mode (see ``repro.core.execmode``):
    ``"sync"`` is the paper's fastest-k lock step (the default; the program
    is byte-identical to the pre-mode engine), ``"kasync"`` waits for the
    next k *completions* and applies their stale partial gradients, and
    ``"kbatch"`` redispatches every completer immediately so fast workers
    can land several gradients per update.  In the async modes the
    controller's k plays the role of K (arrivals per update), its update
    receives arrival/staleness statistics (``ExecStats``), and one
    "iteration" is one master update.  Each async cell here is the bitwise
    ground truth the sweep engine's async cells are pinned against; the
    event-driven host loop (``repro.core.async_sim``) is the independent
    reference the k=1 kasync trajectory is validated on.

    ``straggler`` may be a ``WorkerFleet``: per-worker (heterogeneous)
    response distributions, an optional in-graph rate schedule driven by the
    carried sim_time, and — when the fleet has fewer active models than
    ``n_workers`` slots — +inf-padded inactive slots whose shards are held
    out of both training and the eval loss.  The fleet path is the bitwise
    ground truth the sweep engine's heterogeneous cells are pinned against;
    plain ``StragglerModel`` configurations are untouched by it.
    """
    return run_monte_carlo_source(
        PerExampleSource(per_example_loss_fn),
        params0,
        (X, y),
        n_workers=n_workers,
        controller=controller,
        straggler=straggler,
        eta=eta,
        num_iters=num_iters,
        keys=keys,
        key=key,
        n_replicas=n_replicas,
        comm=comm,
        eval_every=eval_every,
        unroll=unroll,
        mode=mode,
        fault=fault,
        agg=agg,
        agg_param=agg_param,
    )


def summarize(result: MonteCarloResult) -> dict:
    """Replica-axis statistics: mean and 95% CI half-widths, as numpy arrays.

    Returns ``{'iteration', 'n_replicas', 'time_mean', 'time_ci95',
    'loss_mean', 'loss_ci95', 'k_mean', 'k_ci95'}`` where every ``*_mean`` /
    ``*_ci95`` entry has shape (n_evals,).  CI half-widths use the normal
    approximation ``z * s / sqrt(R)`` (zero when R < 2).
    """
    out = {"iteration": np.asarray(result.iteration)}
    r = None
    for name, arr in (("time", result.time), ("loss", result.loss), ("k", result.k)):
        a = np.asarray(arr, dtype=np.float64)
        r = a.shape[0]
        out[f"{name}_mean"] = a.mean(axis=0)
        if r > 1:
            out[f"{name}_ci95"] = _Z95 * a.std(axis=0, ddof=1) / math.sqrt(r)
        else:
            out[f"{name}_ci95"] = np.zeros(a.shape[1])
    out["n_replicas"] = r
    return out
