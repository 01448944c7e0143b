"""The distributed step functions: adaptive fastest-k train_step, prefill_step,
decode_step — the programs the dry-run lowers and the trainer runs.

train_step is ONE compiled program containing the paper's whole loop body:
  sample worker response times (straggler simulation) -> fastest-k mask ->
  per-example weighted loss -> grad (XLA emits the data-parallel reduction)
  -> optimizer update -> renewal-clock advance -> Algorithm-1 controller
  update (k, Pflug counters, prev-gradient inner product).
k is a traced int32 in the carried state, so adaptation never recompiles.
Its parts carry execmode's named scopes (``repro.sampler``, ``repro.ranks``,
``repro.grad`` for the model's forward and backward, ``repro.update`` for
the optimizer, ``repro.controller``), and the post-update eval forward is
``repro.eval``.

The loop body is traced from the SAME per-mode step builders the sim engines
use (``repro.core.execmode.make_mode_steps``): the straggler draw, renewal
residuals, fastest-K ranking and mode bookkeeping are one shared
implementation, with the LM loss plugged in as the ``sync_grad``/
``stale_grad`` closures and the real optimizer plugged in via the
``apply_update`` hook.  ``mode`` selects sync fastest-k (default), K-async,
or K-batch-async; the async modes persist their renewal state (parameter
snapshots, residual clocks, staleness) across calls through
``TrainState.exec_async``.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core import aggregation, execmode
from repro.core.straggler import StragglerModel
from repro.launch.specs import window_for
from repro.models.model import Model
from repro.optim.optimizers import Optimizer, apply_updates


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    ctrl_state: Any
    sim_time: jax.Array  # renewal clock (f32 scalar)
    step: jax.Array  # int32
    # Async-mode renewal state: (worker_params, remaining, staleness, pending)
    # carried between steps.  None for sync mode (an empty pytree node, so
    # the sync TrainState layout — what the dry-run lowers — is unchanged).
    exec_async: Any = None


def init_train_state(model: Model, opt: Optimizer, controller, key) -> TrainState:
    params = model.init(key)
    return TrainState(
        params=params,
        opt_state=opt.init(params),
        ctrl_state=controller.init(params),
        sim_time=jnp.zeros((), jnp.float32),
        step=jnp.zeros((), jnp.int32),
    )


def per_row_loss_fn(model: Model) -> Callable:
    """``(params, tokens, targets) -> (rows,)`` adapter over ``model.loss_fn``
    — the per-example signature the shared stale-gradient machinery
    (``execmode.make_stale_grad_fns``) and ``LMSource`` consume."""

    def per_row(params, tokens, targets):
        losses, _ = model.loss_fn(params, {"tokens": tokens, "targets": targets})
        return losses

    return per_row


def make_train_step(
    model: Model,
    opt: Optimizer,
    controller,
    straggler: StragglerModel,
    n_workers: int,
    comm: Optional[aggregation.CommModel] = None,
    n_micro: int = 1,
    mode: str = "sync",
) -> Callable[[TrainState, Dict[str, jax.Array], jax.Array], Tuple[TrainState, Dict]]:
    """Build the fastest-k train step for a given worker count / policy.

    The step body is traced from ``execmode.make_mode_steps`` — the same
    per-mode builders the Monte-Carlo and sweep engines trace — with the LM
    loss as the gradient closures and ``opt`` plugged in through the
    ``apply_update`` hook.  Workers = contiguous worker-major row shards of
    the global batch (eq. (2): each participating worker contributes
    ``(1/k) * (1/s) * sum`` of its rows' gradients).

    ``mode`` selects the execution mode: ``"sync"`` (fastest-k lock step,
    the default), ``"kasync"``, or ``"kbatch"``.  Async modes evaluate stale
    shard gradients at each worker's dispatch-time parameter snapshot and
    persist the renewal state across calls via ``TrainState.exec_async``
    (first call initializes it; expect one retrace as its structure fills
    in).

    n_micro > 1 enables gradient accumulation over microbatches (sync mode
    only): each worker's rows are split across microbatches (worker-major
    layout preserved inside every microbatch) and the scanned fwd+bwd holds
    only one microbatch's activations live — the lever that fits
    nemotron-4-340b's residuals in HBM.  Because the fastest-k loss is a
    weighted SUM, the accumulated gradient is bit-identical in expectation
    to the single-shot one.
    """
    if mode not in execmode.MODES:
        raise ValueError(f"unknown mode {mode!r}; options {sorted(execmode.MODES)}")
    if mode != "sync" and n_micro != 1:
        raise ValueError("gradient accumulation (n_micro > 1) is sync-only")
    mode_idx = execmode.MODES[mode]
    try:
        accepts_stats = len(inspect.signature(controller.update).parameters) >= 4
    except (TypeError, ValueError):  # builtins / exotic callables
        accepts_stats = True

    def train_step(state: TrainState, batch: Dict[str, jax.Array], key: jax.Array):
        b = batch["tokens"].shape[0]
        assert b % n_workers == 0, (b, n_workers)
        rows_per_worker = b // n_workers

        def draw(sub, sim_time):
            del sim_time
            return straggler.sample(sub, n_workers)

        def weighted_loss(params, batch_part, weights_part):
            per_row, metrics = model.loss_fn(params, batch_part)
            return jnp.sum(weights_part.astype(per_row.dtype) * per_row), metrics

        def sync_grad(params, arrive_f, k):
            weights = aggregation.per_example_weights(arrive_f, k, rows_per_worker)
            if n_micro == 1:
                grads, _ = jax.grad(weighted_loss, has_aux=True)(
                    params, batch, weights
                )
                return grads
            assert rows_per_worker % n_micro == 0, (rows_per_worker, n_micro)

            def to_micro(x):
                # (W*R, ...) -> (n_micro, W*R/n_micro, ...) keeping worker-major
                tail = x.shape[1:]
                x = x.reshape(n_workers, n_micro, rows_per_worker // n_micro, *tail)
                return jnp.moveaxis(x, 1, 0).reshape(
                    n_micro, n_workers * rows_per_worker // n_micro, *tail
                )

            micro_batch = jax.tree.map(to_micro, batch)
            micro_weights = to_micro(weights)

            def micro_body(grads_acc, xs):
                batch_part, w_part = xs
                g, _ = jax.grad(weighted_loss, has_aux=True)(
                    params, batch_part, w_part
                )
                grads_acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), grads_acc, g
                )
                return grads_acc, None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, _ = jax.lax.scan(micro_body, zeros, (micro_batch, micro_weights))
            return grads

        if mode == "sync":
            stale_grad = shard_grad_at = None
        else:
            extra = set(batch) - {"tokens", "targets"}
            if extra:
                raise ValueError(
                    f"async modes support tokens/targets batches only; got extra "
                    f"keys {sorted(extra)}"
                )
            toks_w = batch["tokens"].reshape(
                (n_workers, rows_per_worker) + batch["tokens"].shape[1:]
            )
            tgts_w = batch["targets"].reshape(
                (n_workers, rows_per_worker) + batch["targets"].shape[1:]
            )
            stale_grad, shard_grad_at = execmode.make_stale_grad_fns(
                per_row_loss_fn(model), toks_w, tgts_w, n_workers
            )

        def apply_update(params, g, opt_state):
            updates, opt_state = opt.update(g, opt_state, params)
            return apply_updates(params, updates), opt_state

        def ctrl_update(cstate, g, sim_time, stats):
            if accepts_stats:
                return controller.update(cstate, g, sim_time, stats)
            return controller.update(cstate, g, sim_time)

        steps = execmode.make_mode_steps(
            n_slots=n_workers,
            draw=draw,
            sync_grad=sync_grad,
            stale_grad=stale_grad,
            shard_grad_at=shard_grad_at,
            comm_time=comm.time if comm is not None else None,
            eta=0.0,  # unused: apply_update supersedes the default SGD map
            ctrl_update=ctrl_update,
            apply_update=apply_update,
        )

        if state.exec_async is None:
            carry = execmode.init_exec_carry(
                state.params, n_workers, state.ctrl_state, key,
                opt_state=state.opt_state,
            )._replace(sim_time=state.sim_time)
        else:
            worker_params, remaining, staleness, pending = state.exec_async
            carry = execmode.ExecCarry(
                params=state.params,
                worker_params=worker_params,
                remaining=remaining,
                staleness=staleness,
                pending=pending,
                ctrl_state=state.ctrl_state,
                sim_time=state.sim_time,
                key=key,
                opt_state=state.opt_state,
            )
        new_carry, k_used = steps[mode_idx](carry)

        # Post-update eval forward: the logged loss/ce are the new params'.
        with jax.named_scope("repro.eval"):
            per_row, metrics = model.loss_fn(new_carry.params, batch)
        t_iter = new_carry.sim_time - state.sim_time
        out_metrics = {
            "loss": jnp.mean(per_row),
            "ce": metrics["ce"],
            "k": k_used,
            "iter_time": t_iter,
            "sim_time": new_carry.sim_time,
            "active_workers": k_used,
        }
        exec_async = (
            None if mode == "sync"
            else (new_carry.worker_params, new_carry.remaining,
                  new_carry.staleness, new_carry.pending)
        )
        new_state = TrainState(
            params=new_carry.params,
            opt_state=new_carry.opt_state,
            ctrl_state=new_carry.ctrl_state,
            sim_time=new_carry.sim_time,
            step=state.step + 1,
            exec_async=exec_async,
        )
        return new_state, out_metrics

    return train_step


def make_prefill_step(model: Model, cfg: ModelConfig, shape: InputShape):
    w = window_for(cfg, shape)

    def prefill_step(params, batch):
        return model.prefill(params, batch, window=w)

    return prefill_step


def make_decode_step(model: Model, cfg: ModelConfig, shape: InputShape):
    w = window_for(cfg, shape)

    def decode_step(params, token, cache, pos, **extras):
        return model.decode_step(params, token, cache, pos, window=w, **extras)

    return decode_step
