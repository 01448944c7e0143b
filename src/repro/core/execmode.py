"""Execution modes: k-sync / K-async / K-batch-async SGD as one carry.

The paper studies *synchronous* fastest-k SGD (wait for the fastest k of n
fresh gradients, discard the rest).  Dutta et al. ("Slow and Stale Gradients
Can Win the Race", arXiv:1803.01113) show the interesting comparison class is
the asynchronous family, where stale gradients trade error-per-update for
wall-clock exactly like the k knob does:

* ``sync``   — every iteration all n workers draw fresh response times; the
  master waits for the fastest k, applies their *fresh* partial gradients,
  and restarts everyone.  Iteration time is the order statistic X_(k).
* ``kasync`` — K-async SGD: workers compute continuously against the
  parameter snapshot they were dispatched with.  The master waits for the
  next K *completions*, applies their (stale) partial gradients averaged
  over K, and redispatches exactly those K workers from the new model; the
  other n-K keep computing (their clocks carry over as residuals).
* ``kbatch`` — K-batch-async SGD: every completion redispatches its worker
  immediately, and the master updates once K gradients have arrived — a
  fast worker can contribute several gradients to one update.

All three run **in-graph**: asynchrony is reformulated as a renewal process
carried through the scan — per-worker residual clocks (time left on the
current task), per-worker parameter snapshots (what each in-flight gradient
is being computed against), and per-worker staleness counters.  Staleness is
measured in *master updates*, per Dutta et al.: the counter records how many
updates have been applied since the worker read its snapshot, i.e. the
version gap between the parameters a gradient is applied to and the
parameters it was computed at (0 for every sync-mode gradient).

Residual clocks are exact for every straggler family: a worker's full task
duration is sampled once at dispatch (``straggler.renewal_remaining``) and
ticks down as master events pass — no residual-distribution sampling is ever
needed.  For memoryless families (Exponential rows) redrawing a fresh time
each event would be distributionally identical (the classic shortcut); the
carried clock is what makes the engine exact for shifted/heavy-tailed
families too.

For K = n the ``kasync`` step degenerates to the sync step: every worker
completes in every event (the event time is X_(n)), every snapshot equals
the master's parameters, and every staleness counter stays 0.  The sync
*mode* nevertheless keeps its own branch with the pre-refactor arithmetic,
op for op, so sync-mode cells remain bitwise-equal to the historical engine
(the repo's equality convention; pinned by tests/test_execmode.py).

The step functions here are **shared verbatim** by ``repro.core.montecarlo``
(class-based leaves, the per-cell ground truth) and ``repro.core.sweep``
(traced grid leaves) — the construction that keeps the two engines
bitwise-identical per cell.

Each part of a step runs under a ``jax.named_scope``, so a profile of any
program built from these functions (the looped engine, the sweep's grid
program, the LM train step) attributes device time by part:
``repro.sampler`` (key split, straggler draw, renewal clocks),
``repro.ranks`` (``aggregation.fastest_k_mask_time``), ``repro.grad`` (the
gradient closures, forward and backward), ``repro.aggregate`` (gradient
faults and the robust aggregators), ``repro.update`` (SGD or the plugged
optimizer), ``repro.controller`` (the k update) and ``repro.async_state``
(the kasync/kbatch snapshot, clock and staleness bookkeeping).  The engines
add ``repro.eval`` around their loss evaluations.  A scope changes the
compiled program's metadata only, never its arithmetic.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import aggregation
from repro.core.straggler import renewal_remaining

__all__ = [
    "MODES",
    "MODE_SYNC",
    "MODE_KASYNC",
    "MODE_KBATCH",
    "ExecStats",
    "ExecCarry",
    "ModePrelude",
    "zero_stats",
    "sgd_update",
    "init_exec_carry",
    "make_stale_grad_fns",
    "make_mode_prelude_and_tails",
    "make_mode_steps",
]

# Branch order is load-bearing: repro.core.sweep builds its select over
# modes in this index order and bakes the indices into compiled programs.
MODES = {"sync": 0, "kasync": 1, "kbatch": 2}
MODE_SYNC, MODE_KASYNC, MODE_KBATCH = MODES["sync"], MODES["kasync"], MODES["kbatch"]


class ExecStats(NamedTuple):
    """Per-update arrival/staleness signal handed to controller updates.

    ``arrivals`` is the number of gradients applied (K; k for sync),
    ``mean_staleness``/``max_staleness`` summarize the staleness (in master
    updates) of those gradients — identically zero in sync mode.  Current
    controllers ignore the signal; it is the hook staleness-aware adaptive
    policies plug into.
    """

    arrivals: jax.Array  # int32
    mean_staleness: jax.Array  # f32
    max_staleness: jax.Array  # int32


def zero_stats(k: jax.Array) -> ExecStats:
    return ExecStats(
        arrivals=jnp.asarray(k, jnp.int32),
        mean_staleness=jnp.asarray(0.0, jnp.float32),
        max_staleness=jnp.asarray(0, jnp.int32),
    )


class ExecCarry(NamedTuple):
    """Mode-agnostic scan carry (superset of the sync carry).

    ``worker_params`` stacks each worker's dispatch-time parameter snapshot
    along a leading (n_slots,) axis; ``remaining`` is each in-flight task's
    residual clock; ``pending`` marks slots whose clock was already drawn
    (False ⇒ the slot redispatches with a fresh draw at the next event);
    ``staleness`` counts master updates since each worker read its snapshot.
    Sync-mode steps leave all four untouched.
    """

    params: Any
    worker_params: Any  # pytree with leading (n_slots,) axis
    remaining: jax.Array  # (n_slots,) f32 residual clocks
    staleness: jax.Array  # (n_slots,) int32
    pending: jax.Array  # (n_slots,) bool
    ctrl_state: Any
    sim_time: jax.Array
    key: jax.Array
    # Optimizer state for callers that plug a stateful update rule in via
    # ``apply_update`` (the launch train step).  None — an empty pytree
    # node, zero leaves — for the sim engines' plain SGD, so the carried
    # structure (and every compiled sim program) is unchanged by the field.
    opt_state: Any = None


def init_exec_carry(
    params0, n_slots: int, ctrl_state, key: jax.Array, opt_state: Any = None
) -> ExecCarry:
    """t = 0: every worker is about to be dispatched from params0."""
    worker_params = jax.tree.map(
        lambda p: jnp.broadcast_to(p[None], (n_slots,) + p.shape), params0
    )
    return ExecCarry(
        params=params0,
        worker_params=worker_params,
        remaining=jnp.zeros((n_slots,), jnp.float32),
        staleness=jnp.zeros((n_slots,), jnp.int32),
        pending=jnp.zeros((n_slots,), bool),
        ctrl_state=ctrl_state,
        sim_time=jnp.asarray(0.0, jnp.float32),
        key=key,
        opt_state=opt_state,
    )


def sgd_update(params, g, eta):
    """The sim engines' plain SGD step ``p - eta * g``, on a materialized
    gradient.

    The barrier stops XLA from reassociating ``eta`` into the gradient's own
    trailing scalar factors (``1/k``, a trimmed mean's ``1/count``): it
    folds such chains only when the factors are compile-time constants — a
    looped fixed-k cell — never when they are a sweep cell's traced leaves,
    and the two orders round differently.  With the barrier both engines
    apply the same multiply to the same gradient bits.
    """
    with jax.named_scope("repro.update"):
        g = jax.lax.optimization_barrier(g)
        return jax.tree.map(lambda pa, gi: pa - eta * gi, params, g)


def _in_scope(name: str, fn: Callable | None) -> Callable | None:
    """``fn`` with every operation it traces under the named scope ``name``
    (``None`` stays ``None``)."""
    if fn is None:
        return None

    def scoped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return scoped


def _slot_bcast(mask: jax.Array, like: jax.Array) -> jax.Array:
    """(n_slots,) mask reshaped to broadcast against an (n_slots, ...) leaf."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def make_stale_grad_fns(
    per_example_loss_fn: Callable, Xw, yw, n_slots: int,
    stale_weighted_loss: Callable | None = None,
):
    """The stale-gradient machinery of the async modes, built ONCE here so
    both engines trace identical ops (the bitwise sweep-vs-looped contract).

    ``Xw``/``yw`` are the worker-major data reshaped to a leading
    ``(n_slots, s)`` axis.  ``stale_weighted_loss`` defaults to the eq.-(2)
    aggregate in ``repro.core.aggregation``; gradient sources pass their own
    method (same formula, source-owned).  Returns
    ``(stale_grad, shard_grad_at)``:

    * ``stale_grad(worker_params, mask_f32, k)`` — the master's K-async
      update direction: each slot's per-example losses are evaluated at that
      slot's OWN parameter snapshot (vmap over the stacked snapshots), fed
      through the eq.-(2) segment-sum weighting
      (``aggregation.stale_weighted_loss``), differentiated wrt the stack,
      and row-summed — ``(1/k) * sum_i mask_i * (1/s) sum_shard grad F``.
    * ``shard_grad_at(worker_params, i)`` — one slot's stale partial
      gradient (the K-batch inner-event form; ``i`` may be traced).
    """
    if stale_weighted_loss is None:
        stale_weighted_loss = aggregation.stale_weighted_loss

    def stale_loss(worker_params, mask, k):
        losses = jax.vmap(per_example_loss_fn)(worker_params, Xw, yw)
        return stale_weighted_loss(losses.reshape(n_slots, -1), mask, k)

    stale_grad_stack = jax.grad(stale_loss)

    def stale_grad(worker_params, mask, k):
        gs = stale_grad_stack(worker_params, mask, k)
        # Row i is worker i's eq.-(2)-weighted stale partial gradient;
        # the master applies their sum.
        return jax.tree.map(lambda g: g.sum(axis=0), gs)

    def shard_grad_at(worker_params, i):
        wp_i = jax.tree.map(lambda a: a[i], worker_params)
        Xi, yi = Xw[i], yw[i]
        return jax.grad(lambda w: jnp.mean(per_example_loss_fn(w, Xi, yi)))(wp_i)

    return stale_grad, shard_grad_at


class ModePrelude(NamedTuple):
    """Mode-invariant per-event work, hoisted out of the mode switch.

    Every field is computed identically by each mode that consumes it (the
    sync/kasync pair consumes all of them; kbatch only ``new_key``/``sub``/
    ``k``), so in a mixed-mode grid the per-cell select picks only
    the cheap mode *bookkeeping* tails — per-slot sampling, ranking, and the
    order statistic are traced once per event instead of once per branch.
    For a sync-mode cell ``pending`` is identically False, so ``remaining``
    is the fresh draw bit for bit — which is exactly what keeps hoisting a
    bitwise no-op for sync lanes.
    """

    new_key: jax.Array  # next carry key (first output of the split)
    sub: jax.Array  # this event's subkey (kbatch's key0)
    k: jax.Array  # the controller's current k/K
    remaining: jax.Array  # (n_slots,) residual clocks after renewal
    arrive_f: jax.Array  # f32 mask of the K smallest clocks
    tau: jax.Array  # K-th order statistic of the clocks
    t_iter: jax.Array  # tau + master-side comm


def make_mode_prelude_and_tails(
    *,
    n_slots: int,
    draw: Callable,  # draw(sub, sim_time) -> (n_slots,) fresh task durations
    sync_grad: Callable,  # sync_grad(params, mask, k) -> grad pytree (eq. 2)
    stale_grad: Callable,  # stale_grad(worker_params, mask_f32, k) -> grad pytree
    shard_grad_at: Callable,  # shard_grad_at(worker_params, i) -> worker i's partial grad
    comm_time: Callable | None,  # comm_time(k) -> f32 receive cost; None = no comm
    eta,  # f32 scalar (python float or traced leaf)
    ctrl_update: Callable,  # ctrl_update(state, g, sim_time, stats) -> (state, k)
    ctrl_k: Callable = lambda s: s.k,  # current K from the controller state
    apply_update: Callable | None = None,  # (params, g, opt_state) -> (params, opt_state)
    faults=None,  # Optional[repro.core.faults.FaultFns]
    robust_agg: Callable | None = None,  # aggregation.make_robust_select result
):
    """The execution modes factored as (shared prelude, per-mode tails).

    ``prelude(carry)`` performs the mode-invariant work (key split, fresh
    per-slot draw, renewal residuals, fastest-K ranking/order statistic,
    comm); ``tails[mode](carry, prelude)`` each return ``(new_carry, k)``
    with identical pytree structure, so a per-cell select over the
    tails vmaps cleanly.  ``tails[mode](carry, prelude(carry))`` is exactly
    the historical full step for that mode, op for op — callers that trace a
    single mode (``make_mode_steps``) and callers that switch over tails
    behind one shared prelude (the sweep engine) therefore stay
    bitwise-identical per cell.

    ``comm_time=None`` statically omits the master-side receive cost
    (arithmetically ``+ 0.0`` everywhere it would appear — a bitwise no-op
    versus a zero ``CommModel``).  All leaves the caller closes over
    (straggler rows, eta, comm, controller hyperparameters) may be traced —
    nothing here branches on values in Python.

    ``apply_update`` is the parameter-update hook:
    ``apply_update(params, g, opt_state) -> (new_params, new_opt_state)``.
    The default is the sim engines' plain SGD step — the identical
    ``p - eta * g`` tree map the tails historically inlined, with
    ``opt_state`` passed through untouched (``None`` for sim carries) — so
    omitting it is a bitwise no-op.  The launch train step plugs a real
    optimizer in here, which is what lets training and simulation share
    these step functions.

    ``faults`` (a ``repro.core.faults.FaultFns``) and ``robust_agg`` (an
    ``aggregation.make_robust_select`` result) thread the robustness axes
    through every mode.  Both default to ``None``, in which case NONE of the
    machinery below is traced — the fault-free / mean-aggregation program is
    op-for-op today's program (the bitwise pin in tests/test_faults.py).
    Inside a faulty program, healthy cells ride multiplies by exactly 1.0
    and ``where`` passthroughs, which are bitwise no-ops:

    * crash: ``faults.time`` pins crashed-past-onset response times and
      residual clocks to +inf AFTER sampling/renewal, so the ranking path
      degrades to the surviving fleet and an in-flight dispatch of a crashed
      worker never completes.  Once fewer than k workers survive the k-th
      order statistic saturates, iteration time goes +inf, and (the pinned
      all-crashed edge case) parameters hold via an ``alive`` select.  The
      ``isfinite`` guards below exist only to keep inf-minus-inf NaNs out of
      the carried clocks; for finite clocks they are bitwise passthroughs.
    * gradient faults fold into the eq.-(2) participation mask (the
      weighted loss is linear in it): sign_flip -> -1, rescale -> param,
      random_gauss -> 0 with its replacement noise added separately —
      key-derived by ``fold_in`` from the event subkey so the engines' split
      chain is never advanced.  The noise add is gated per cell on
      ``faults.any_gauss`` (adding literal 0.0 could flip -0.0 bits).
    * ``robust_agg(mean_g, rows, mask, k)`` selects the cell's aggregator
      over the per-worker shard-gradient ROW stack (sync: at the master's
      params; kasync: at each worker's snapshot) with the same fault
      transforms applied row-wise; mean cells take ``mean_g`` through the
      select unchanged.  The kbatch tail ignores ``robust_agg`` — its
      arrivals are sequential, there is no row stack to aggregate — and the
      engines reject kbatch+robust cells up front.
    """
    if apply_update is None:

        def apply_update(params, g, opt_state):
            return sgd_update(params, g, eta), opt_state

    draw = _in_scope("repro.sampler", draw)
    sync_grad = _in_scope("repro.grad", sync_grad)
    stale_grad = _in_scope("repro.grad", stale_grad)
    shard_grad_at = _in_scope("repro.grad", shard_grad_at)
    apply_update = _in_scope("repro.update", apply_update)
    ctrl_update = _in_scope("repro.controller", ctrl_update)

    has_crash = faults is not None and faults.time is not None
    has_grad_fault = faults is not None and faults.weight is not None
    has_gauss = faults is not None and faults.noise_rows is not None

    if robust_agg is not None:
        _slot_idx = jnp.arange(n_slots)

        def grad_rows(wp):
            # Row i = slot i's unweighted shard-mean gradient at its own
            # parameters — the robust aggregators' input cloud.
            return jax.vmap(lambda i: shard_grad_at(wp, i))(_slot_idx)

    def corrupted_grad(mean_grad_fn, rows_wp, arrive_f, k, sub, t0):
        """Mean-path gradient with fault transforms + per-cell robust select.

        ``mean_grad_fn(mask, k)`` is the mode's eq.-(2) gradient closure;
        ``rows_wp`` the (n_slots,)-stacked params the robust rows evaluate
        at; ``t0`` the event-start sim time (fault onsets are judged at the
        event's start, identically in every mode).
        """
        mask_g = arrive_f * faults.weight(t0) if has_grad_fault else arrive_f
        g = mean_grad_fn(mask_g, k)
        z = faults.noise_rows(sub, t0) if has_gauss else None
        if has_gauss:
            kf = k.astype(jnp.float32)
            g = jax.tree.map(
                lambda gl, zl: jnp.where(
                    faults.any_gauss,
                    gl + jnp.tensordot(arrive_f, zl, axes=1) * (1.0 / kf),
                    gl,
                ),
                g,
                z,
            )
        if robust_agg is not None:
            rows = grad_rows(rows_wp)
            if faults is not None and faults.row_faults is not None:
                rows = faults.row_faults(rows, z, t0)
            g = robust_agg(g, rows, arrive_f, k)
        return g

    corrupted_grad = _in_scope("repro.aggregate", corrupted_grad)

    def hold_if_dead(params, old_params, remaining):
        """The zero-survivors pin: parameters hold once every clock is +inf
        (iteration time is already +inf via the saturated order statistic)."""
        if not has_crash:
            return params
        alive = jnp.any(jnp.isfinite(remaining))
        return jax.tree.map(
            lambda a, b: jnp.where(alive, a, b), params, old_params
        )

    def prelude(carry: ExecCarry) -> ModePrelude:
        with jax.named_scope("repro.sampler"):
            new_key, sub = jax.random.split(carry.key)
            k = ctrl_k(carry.ctrl_state)
            remaining = renewal_remaining(
                draw(sub, carry.sim_time), carry.pending, carry.remaining
            )
            if has_crash:
                remaining = faults.time(remaining, carry.sim_time)
        # The sync hot-path primitive, read over residual clocks: arrival
        # set = the K smallest clocks, event duration = the K-th one.  (For
        # sync cells the clocks ARE the fresh draw — pending is never set.)
        arrive_f, tau = aggregation.fastest_k_mask_time(remaining, k)
        t_iter = tau if comm_time is None else tau + comm_time(k)
        return ModePrelude(
            new_key=new_key, sub=sub, k=k, remaining=remaining,
            arrive_f=arrive_f, tau=tau, t_iter=t_iter,
        )

    def sync_tail(carry: ExecCarry, p: ModePrelude):
        # Pre-refactor arithmetic, op for op: fastest-k mask + order
        # statistic -> eq.-(2) gradient at the master's params.  The async
        # carry fields pass through untouched (bitwise identity).
        k = p.k
        if faults is None and robust_agg is None:
            g = sync_grad(carry.params, p.arrive_f, k)
        else:
            rows_wp = (
                jax.tree.map(
                    lambda q: jnp.broadcast_to(q[None], (n_slots,) + q.shape),
                    carry.params,
                )
                if robust_agg is not None
                else None
            )
            g = corrupted_grad(
                lambda m, kk: sync_grad(carry.params, m, kk),
                rows_wp, p.arrive_f, k, p.sub, carry.sim_time,
            )
        params, opt_state = apply_update(carry.params, g, carry.opt_state)
        params = hold_if_dead(params, carry.params, p.remaining)
        sim_time = carry.sim_time + p.t_iter
        ctrl_state, _ = ctrl_update(carry.ctrl_state, g, sim_time, zero_stats(k))
        return (
            carry._replace(
                params=params, ctrl_state=ctrl_state, sim_time=sim_time,
                key=p.new_key, opt_state=opt_state,
            ),
            k,
        )

    def kasync_tail(carry: ExecCarry, p: ModePrelude):
        # One master event: the next K completions arrive, their stale
        # partial gradients (at their dispatch snapshots) are averaged and
        # applied, and exactly those K workers redispatch from the new model.
        new_key, k = p.new_key, p.k
        remaining, arrive_f, t_iter = p.remaining, p.arrive_f, p.t_iter
        arrive = arrive_f.astype(bool)
        if faults is None and robust_agg is None:
            g = stale_grad(carry.worker_params, arrive_f, k)
        else:
            g = corrupted_grad(
                lambda m, kk: stale_grad(carry.worker_params, m, kk),
                carry.worker_params, arrive_f, k, p.sub, carry.sim_time,
            )
        params, opt_state = apply_update(carry.params, g, carry.opt_state)
        params = hold_if_dead(params, carry.params, remaining)
        sim_time = carry.sim_time + t_iter
        kf = k.astype(jnp.float32)
        stats = ExecStats(
            arrivals=jnp.asarray(k, jnp.int32),
            mean_staleness=(
                jnp.dot(arrive_f, carry.staleness.astype(jnp.float32)) * (1.0 / kf)
            ),
            max_staleness=jnp.max(jnp.where(arrive, carry.staleness, 0)),
        )
        ctrl_state, _ = ctrl_update(carry.ctrl_state, g, sim_time, stats)
        # Arrivals redispatch from the fresh model (clock drawn next event);
        # everyone else keeps computing, one update staler.
        worker_params = jax.tree.map(
            lambda wp, pa: jnp.where(_slot_bcast(arrive, wp), pa[None], wp),
            carry.worker_params,
            params,
        )
        staleness = jnp.where(arrive, 0, carry.staleness + 1)
        # In-flight workers compute THROUGH the master's receive window, so
        # their clocks tick down by the full event duration t_iter (not just
        # tau); a task finishing inside that window arrives at the window's
        # end — clamp at zero so it surfaces immediately next event.  With
        # comm = 0 the clamp is a bitwise no-op (non-arrival clocks are
        # >= tau by construction).  Crashed clocks stay +inf (the isfinite
        # guard also keeps inf - inf out when t_iter itself saturates; for
        # finite clocks it selects the historical expression bit for bit).
        if has_crash:
            rem_next = jnp.where(
                jnp.isfinite(remaining),
                jnp.maximum(remaining - t_iter, 0.0),
                jnp.inf,
            )
        else:
            rem_next = jnp.maximum(remaining - t_iter, 0.0)
        return (
            ExecCarry(
                params=params,
                worker_params=worker_params,
                remaining=rem_next,
                staleness=staleness,
                pending=~arrive,
                ctrl_state=ctrl_state,
                sim_time=sim_time,
                key=new_key,
                opt_state=opt_state,
            ),
            k,
        )

    def kbatch_tail(carry: ExecCarry, p: ModePrelude):
        # One master event: K single completions in a row — each completer
        # contributes its stale partial gradient and redispatches IMMEDIATELY
        # (reading the still-pre-update params), so a fast worker can land
        # several gradients in one update.  The inner scan runs a static
        # n_slots events and masks the tail beyond the traced K — including
        # the tail events' shard gradients (multiplied by 0): with K traced
        # per cell the trip count cannot depend on it, so a kbatch update
        # costs n_slots shard gradients (~ one full-batch gradient)
        # regardless of K.  A static K bound could shorten the scan, but
        # only by restructuring key consumption identically in both engines
        # (the bitwise sweep-vs-looped pin).  Only the prelude's key split
        # and k are consumed here: kbatch events draw per completion from a
        # second-level split, so the hoisted draw/ranking belong to the
        # other modes (they fold away in a kbatch-only program).
        new_key, k = p.new_key, p.k
        kf = k.astype(jnp.float32)
        key0, sub0 = jax.random.split(p.sub)
        remaining = renewal_remaining(
            draw(sub0, carry.sim_time), carry.pending, carry.remaining
        )
        if has_crash:
            remaining = faults.time(remaining, carry.sim_time)
        # Fault transforms hoisted per event (onsets are judged at the
        # event's start, like the other modes; a completer landing several
        # gradients this event reuses its one noise row).
        t0 = carry.sim_time
        w_mult = faults.weight(t0) if has_grad_fault else None
        z_rows = faults.noise_rows(p.sub, t0) if has_gauss else None
        g_mask = faults.gauss_mask(t0) if has_gauss else None
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), carry.params)
        i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731

        def inner(state, e):
            (rem, stal, wp, gsum, ssum, smax, tau_sum, key) = state
            active = e < k
            i_star = jnp.argmin(rem)  # ties -> lowest index, like the heapq
            tau_e = rem[i_star]
            g_e = shard_grad_at(wp, i_star)
            if has_grad_fault:
                # Per-arrival corruption: the completer's contribution is
                # scaled (sign_flip/rescale; healthy slots scale by exactly
                # 1.0) and a gauss completer's is REPLACED by its gated,
                # param-scaled noise row (where passthrough otherwise).
                m_i = w_mult[i_star]
                g_e = jax.tree.map(lambda a: m_i * a, g_e)
            if has_gauss:
                gz_i = g_mask[i_star]
                g_e = jax.tree.map(
                    lambda a, zl: jnp.where(gz_i, zl[i_star], a), g_e, z_rows
                )
            w = jnp.where(active, jnp.float32(1.0), jnp.float32(0.0))
            gsum = jax.tree.map(lambda a, b: a + w * b, gsum, g_e)
            ssum = ssum + jnp.where(active, stal[i_star], 0)
            smax = jnp.maximum(smax, jnp.where(active, stal[i_star], 0))
            key, sub = jax.random.split(key)
            # A full (n_slots,) draw per inner event, of which only the
            # completer's entry is kept: O(n) spare samples per arrival, but
            # it reuses the packed per-worker protocol unchanged (and the
            # per-event shard gradient above, O(s*d), dominates the O(n)
            # sampling in this loop anyway).
            redraw = draw(sub, carry.sim_time + tau_sum + tau_e)
            if has_crash:
                # A crashed worker's redispatch never completes either, and
                # inf-clock slots tick by inf-minus-inf otherwise.
                redraw = faults.time(redraw, carry.sim_time + tau_sum + tau_e)
                rem_minus = jnp.where(jnp.isfinite(rem), rem - tau_e, jnp.inf)
            else:
                rem_minus = rem - tau_e
            rem_next = jnp.where(active, rem_minus, rem)
            rem_next = rem_next.at[i_star].set(
                jnp.where(active, redraw[i_star], rem[i_star])
            )
            stal_next = jnp.where(active, stal.at[i_star].set(0), stal)
            wp_next = jax.tree.map(
                lambda a, p: jnp.where(active, a.at[i_star].set(p), a),
                wp,
                carry.params,
            )
            tau_next = tau_sum + jnp.where(active, tau_e, 0.0)
            return (rem_next, stal_next, wp_next, gsum, ssum, smax, tau_next, key), None

        init = (
            remaining,
            carry.staleness,
            carry.worker_params,
            g0,
            i32(0),
            i32(0),
            jnp.asarray(0.0, jnp.float32),
            key0,
        )
        (remaining, staleness, worker_params, gsum, ssum, smax, tau_sum, _), _ = (
            jax.lax.scan(inner, init, jnp.arange(n_slots))
        )
        g = jax.tree.map(lambda x: x * (1.0 / kf), gsum)
        params, opt_state = apply_update(carry.params, g, carry.opt_state)
        params = hold_if_dead(params, carry.params, remaining)
        t_iter = tau_sum if comm_time is None else tau_sum + comm_time(k)
        sim_time = carry.sim_time + t_iter
        stats = ExecStats(
            arrivals=jnp.asarray(k, jnp.int32),
            mean_staleness=ssum.astype(jnp.float32) * (1.0 / kf),
            max_staleness=smax,
        )
        ctrl_state, _ = ctrl_update(carry.ctrl_state, g, sim_time, stats)
        return (
            ExecCarry(
                params=params,
                # Carried clocks also run through the master's receive
                # window (comm = 0, or no comm model at all, keeps this a
                # bitwise no-op; see kasync).
                remaining=(
                    remaining if comm_time is None
                    else jnp.maximum(remaining - comm_time(k), 0.0)
                ),
                worker_params=worker_params,
                # The update just applied ages every in-flight task by one.
                staleness=staleness + 1,
                pending=jnp.ones((n_slots,), bool),
                ctrl_state=ctrl_state,
                sim_time=sim_time,
                key=new_key,
                opt_state=opt_state,
            ),
            k,
        )

    return prelude, (
        sync_tail,
        _in_scope("repro.async_state", kasync_tail),
        _in_scope("repro.async_state", kbatch_tail),
    )


def make_mode_steps(
    *,
    n_slots: int,
    draw: Callable,
    sync_grad: Callable,
    stale_grad: Callable,
    shard_grad_at: Callable,
    comm_time: Callable | None,
    eta,
    ctrl_update: Callable,
    ctrl_k: Callable = lambda s: s.k,
    apply_update: Callable | None = None,
    faults=None,
    robust_agg: Callable | None = None,
):
    """The three full execution-mode step functions over a shared ``ExecCarry``.

    ``step(carry) -> (new_carry, k)`` — each is its mode's tail composed
    with the shared prelude (``make_mode_prelude_and_tails``); tracing one
    of them (the looped per-cell engines) and tracing the tails behind one
    hoisted prelude (the sweep's mixed-mode programs) therefore produce
    bitwise-identical trajectories per cell.  Prelude fields a mode does not
    consume fold away when that mode is traced alone.
    """
    prelude, tails = make_mode_prelude_and_tails(
        n_slots=n_slots, draw=draw, sync_grad=sync_grad, stale_grad=stale_grad,
        shard_grad_at=shard_grad_at, comm_time=comm_time, eta=eta,
        ctrl_update=ctrl_update, ctrl_k=ctrl_k, apply_update=apply_update,
        faults=faults, robust_agg=robust_agg,
    )
    return tuple(
        (lambda carry, _tail=tail: _tail(carry, prelude(carry))) for tail in tails
    )
