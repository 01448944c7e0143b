"""Pluggable gradient sources: the engines' loss abstraction.

Historically both Monte-Carlo engines (``repro.core.montecarlo`` and
``repro.core.sweep``) hardcoded a ``per_example_loss_fn(params, X, y)``
closure and built the eq.-(2) aggregation around it inline.  A **gradient
source** factors that seam out: the engines ask the source for the four
functions they actually consume, and anything that can produce per-worker
shard gradients of *some* loss — the quadratic toy, a real jitted LM train
step (``repro.launch.lm_source.LMSource``), a future RL objective — plugs
into every execution mode, controller, and dispatch path unchanged.

The protocol (``GradSource``)::

    source.check(data, n_workers)        # host-side validation, clear errors
    fns = source.build(data, n_workers)  # -> SourceFns (sync-path closures)
    fns.grad(params, mask, k)            # eq.-(2) masked aggregate gradient
    fns.eval_loss(params)                # mean loss over all shards
    fns.eval_loss_active(params, n_active)   # inactive shards held out
    stale_grad, shard_grad_at = source.build_stale(data, n_workers)
    source.cache_token()                 # hashable program-cache key part

``data`` is an arbitrary pytree of arrays — it is threaded through the
compiled programs as a **traced jit argument**, never baked into the trace
(a baked data constant would let XLA refold reductions and break the
bitwise sweep-vs-looped contract; see ``mean_loss`` in montecarlo).
``build``/``build_stale`` are called INSIDE the traced function, once per
trace.  ``build`` must emit no eager ops of its own (closure definitions
only); ``build_stale`` may emit the worker-shard reshape — it is only
invoked by the async/mode-switch programs, exactly where the historical
inline reshape sat, so sync programs stay byte-identical.

``cache_token()`` replaces the loss function in both engines' program-cache
keys: two source instances with equal tokens must trace identical programs.

``PerExampleSource`` is the reference implementation — the historical
per-example closure path, op for op.  The eq.-(2) segment-sum and the
stale weighted aggregate are its *methods* (``weighted_loss`` /
``stale_weighted_loss``), delegating to ``repro.core.aggregation``; the
engines reach them only through the source.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Hashable, NamedTuple, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import aggregation, execmode

__all__ = [
    "SourceFns",
    "GradSource",
    "PerExampleSource",
    "lane_data",
]


def lane_data(data: Any, n_lanes: int) -> Any:
    """The source's data pytree broadcast along a leading lane axis.

    Both engines vmap their lanes (replicas; flattened cell x replica lanes
    in the sweep) over this with ``in_axes=0`` and hand it to
    ``build_stale``.  With one shared copy, the stale gradients' per-slot
    ``X_i @ w_i`` becomes, per slot, one product whose free dimension is the
    lane count, and XLA's CPU kernels round that product differently for
    different lane counts.  With per-lane data each lane's products are a
    batch of independent same-shape products, so a lane's bits do not
    depend on how many lanes (or devices) share the program — the bitwise
    sweep-vs-looped contract across grid sizes and mesh shapes.  The sync
    closures keep the shared copy: their products are lane-count invariant
    and several times faster that way.  Its device time is async state
    (``repro.async_state``).
    """
    with jax.named_scope("repro.async_state"):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_lanes,) + a.shape), data
        )


class SourceFns(NamedTuple):
    """The sync-path closures a source hands the engines (built per trace).

    ``grad(params, mask, k)`` is the eq.-(2) masked aggregate gradient:
    ``(1/k) sum_{i: mask_i} (1/s) sum_{a in S_i} grad F(a, params)`` with the
    (n_workers,) participation ``mask`` and traced int32 ``k``.
    ``eval_loss(params)`` is the mean loss over every shard;
    ``eval_loss_active(params, n_active)`` holds the shards of inactive
    worker slots (slot index >= n_active) out of the mean — bitwise-equal to
    ``eval_loss`` when every slot is active (the heterogeneity contract).
    """

    grad: Callable  # (params, mask, k) -> grad pytree
    eval_loss: Callable  # (params,) -> f32 scalar
    eval_loss_active: Callable  # (params, n_active) -> f32 scalar


@runtime_checkable
class GradSource(Protocol):
    """What the engines require of a pluggable gradient source."""

    def check(self, data: Any, n_workers: int) -> None:
        """Host-side validation (shard divisibility etc.); raise ValueError."""

    def build(self, data: Any, n_workers: int) -> SourceFns:
        """Sync-path closures over traced ``data``.  No eager ops."""

    def build_stale(self, data: Any, n_workers: int) -> Tuple[Callable, Callable]:
        """``(stale_grad, shard_grad_at)`` for the async modes (may emit the
        worker-shard reshape; see ``execmode.make_stale_grad_fns``)."""

    def cache_token(self) -> Hashable:
        """Hashable identity for the program caches: equal tokens must
        trace identical programs."""


@dataclasses.dataclass(frozen=True)
class PerExampleSource:
    """The reference source: a per-example loss over a ``(X, y)`` data pair.

    ``per_example_loss_fn(params, X, y) -> (m,)`` per-example losses, with
    batch rows worker-major (worker i owns rows [i*s, (i+1)*s)).  This is
    the historical engine path verbatim; ``run_monte_carlo``/``run_sweep``
    wrap their loss argument in one of these, and equality of the wrapped
    function keeps the program caches hitting across wrapper calls.
    """

    per_example_loss_fn: Callable

    # --- the eq.-(2) aggregates, as source methods (delegating to
    # repro.core.aggregation so the formulas live in one place).

    def weighted_loss(self, per_example_losses, mask, k, examples_per_worker):
        """Eq.-(2) segment-sum weighted loss (no (m,) weight vector)."""
        return aggregation.fastest_k_weighted_loss(
            per_example_losses, mask, k, examples_per_worker
        )

    def stale_weighted_loss(self, losses_by_worker, mask, k):
        """Eq.-(2)-style weighted loss over stale per-worker evaluations."""
        return aggregation.stale_weighted_loss(losses_by_worker, mask, k)

    # --- the GradSource protocol.

    def check(self, data, n_workers: int) -> None:
        m = data[0].shape[0]
        if m % n_workers:
            raise ValueError(f"m={m} not divisible by n_workers={n_workers}")

    def build(self, data, n_workers: int) -> SourceFns:
        X, y = data
        s = X.shape[0] // n_workers
        loss = self.per_example_loss_fn

        def step_loss(params, mask, k):
            losses = loss(params, X, y)
            return self.weighted_loss(losses, mask, k, s)

        grad = jax.grad(step_loss)

        def eval_loss(params):
            losses = loss(params, X, y)
            return aggregation.active_worker_mean_loss(losses, n_workers, n_workers, s)

        def eval_loss_active(params, n_active):
            losses = loss(params, X, y)
            return aggregation.active_worker_mean_loss(losses, n_active, n_workers, s)

        return SourceFns(grad=grad, eval_loss=eval_loss, eval_loss_active=eval_loss_active)

    def build_stale(self, data, n_workers: int):
        X, y = data
        s = X.shape[0] // n_workers
        Xw = X.reshape((n_workers, s) + X.shape[1:])
        yw = y.reshape((n_workers, s) + y.shape[1:])
        return execmode.make_stale_grad_fns(
            self.per_example_loss_fn, Xw, yw, n_workers,
            stale_weighted_loss=self.stale_weighted_loss,
        )

    def cache_token(self) -> Hashable:
        return ("per_example", self.per_example_loss_fn)
