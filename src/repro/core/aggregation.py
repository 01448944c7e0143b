"""fastest-k gradient aggregation, expressed TPU-natively.

The paper's update (eq. 2):

    w_{j+1} = w_j - (eta/k) * sum_{i in R_j} grad F(S_i, w_j)

where R_j is the set of the k workers with the smallest response times at
iteration j and grad F(S_i, w) = (1/s) sum_{a in S_i} grad F(a, w).

On a TPU mesh the batch is sharded along ("pod","data"): data-parallel worker
i owns batch rows [i*s, (i+1)*s).  We therefore realize eq. (2) as the
gradient of a *per-example weighted loss*

    L(w) = sum_ell  v_ell * loss(a_ell, w),   v_ell = m_{worker(ell)} / (k*s)

with m the fastest-k participation mask.  XLA's ordinary data-parallel
gradient reduction then computes exactly  (1/k) sum_{i in R} (1/s) sum grads:
no bespoke collective, composes with any tensor/expert parallelism, and k can
be a *traced* value so the adaptive controller never forces a recompile.

The simulated wall-clock advanced per iteration is X_(k) (the time the master
waits for the k-th response), plus an optional affine communication model
(a beyond-paper extension; the paper folds communication into X_i).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.straggler import StragglerModel

__all__ = [
    "CommModel",
    "sample_worker_times",
    "worker_ranks",
    "fastest_k_mask",
    "iteration_time",
    "per_example_weights",
    "masked_mean_weights",
    "fastest_k_weighted_loss",
    "stale_weighted_loss",
    "fastest_k_mask_time",
    "fastest_k_draw",
    "ordered_sum",
    "active_worker_mean_loss",
    "AGG_KINDS",
    "AGG_MEAN",
    "AGG_TRIMMED",
    "AGG_MEDIAN",
    "AGG_GEOMEDIAN",
    "WEISZFELD_ITERS",
    "trimmed_mean_rows",
    "coordinate_median_rows",
    "geometric_median_rows",
    "make_robust_select",
]


@dataclasses.dataclass(frozen=True)
class CommModel:
    """Affine master-side communication cost: t_comm = alpha + beta * k.

    The master receives k partial-gradient messages per iteration; with a
    single-port master the receive time grows linearly in k.  Setting
    alpha = beta = 0 recovers the paper's model exactly.
    """

    alpha: float = 0.0
    beta: float = 0.0

    def time(self, k: jax.Array) -> jax.Array:
        return self.alpha + self.beta * k.astype(jnp.float32)


def sample_worker_times(model: StragglerModel, key: jax.Array, n_workers: int) -> jax.Array:
    """iid response times for one iteration, shape (n_workers,)."""
    return model.sample(key, n_workers)


# Measured on a 2-core CPU host with B=256 batched lanes (the Monte-Carlo
# engine's regime): pairwise wins below n=128 (190 us vs 2.5 ms at n=32),
# top_k wins above n=256 (20 ms vs 68 ms, and 11x at n=1024).  The O(n^2)
# pairwise compare is quadratic in both flops *and* memory traffic, so the
# crossover is sharp; 192 splits the measured bracket.
_TOPK_CROSSOVER_N = 192


def worker_ranks(times: jax.Array, method: str = "auto") -> jax.Array:
    """Stable rank of each entry (0 = smallest), ties broken by index.

    Two exactly-equivalent paths, chosen by the *static* length n (so the
    choice never causes a retrace):

    * ``pairwise`` — O(n^2) comparisons.  For the small n of the simulation
      layer this is dramatically cheaper than a sort on CPU, especially
      batched under vmap inside a scan (the Monte-Carlo engine's hot path).
    * ``topk`` — ``jax.lax.top_k`` of the negated times (n log n).  top_k
      returns equal values lowest-index-first, so negation yields exactly the
      stable ascending order; scattering positions inverts it into ranks.
      Above ``_TOPK_CROSSOVER_N`` (measured) this wins, e.g. 100-1000-worker
      scenario sweeps.

    Both assign the rank a stable argsort would, ties included.  +inf
    entries (the heterogeneous engines' *inactive* worker slots) are
    ordinary values to both paths: they compare strictly after every finite
    time and tie among themselves by index, so with ``a`` active (finite)
    slots the inactive slots occupy ranks a..n-1 in slot order — they can
    never enter a fastest-k set with k <= a (pinned by
    tests/test_hetero.py on both paths, straddling the crossover).  NaN
    times are NOT supported on either path.
    """
    n = times.shape[0]
    if method == "auto":
        method = "topk" if n >= _TOPK_CROSSOVER_N else "pairwise"
    if method == "pairwise":
        idx = jnp.arange(n)
        before = (times[None, :] < times[:, None]) | (
            (times[None, :] == times[:, None]) & (idx[None, :] < idx[:, None])
        )
        return jnp.sum(before, axis=1).astype(jnp.int32)
    if method == "topk":
        _, order = jax.lax.top_k(-times, n)  # stable ascending-time order
        return (
            jnp.zeros((n,), jnp.int32)
            .at[order]
            .set(jnp.arange(n, dtype=jnp.int32), unique_indices=True)
        )
    raise ValueError(f"unknown rank method {method!r}; options: auto|pairwise|topk")


def fastest_k_mask(times: jax.Array, k: jax.Array) -> jax.Array:
    """{0,1} mask of the k smallest entries of `times` (exactly k ones).

    `k` may be a traced int32 scalar (1 <= k <= n) — we rank rather than
    threshold so ties cannot produce more than k participants.
    """
    return (worker_ranks(times) < k).astype(times.dtype)


def _time_from_ranks(
    ranks: jax.Array, times: jax.Array, k: jax.Array, comm: Optional[CommModel]
) -> jax.Array:
    """k-th order statistic of `times` given precomputed ranks (+ comm)."""
    rank_wanted = jnp.clip(k - 1, 0, times.shape[0] - 1)
    t = jnp.sum(jnp.where(ranks == rank_wanted, times, 0.0))
    if comm is not None:
        t = t + comm.time(k)
    return t


def iteration_time(
    times: jax.Array, k: jax.Array, comm: Optional[CommModel] = None
) -> jax.Array:
    """Simulated duration of one fastest-k iteration: X_(k) (+ comm)."""
    return _time_from_ranks(worker_ranks(times), times, k, comm)


def per_example_weights(
    mask: jax.Array, k: jax.Array, examples_per_worker: int
) -> jax.Array:
    """Per-example loss weights v (shape (n*s,)) realizing eq. (2).

    v_ell = m_{worker(ell)} / (k * s).  Batch rows are laid out worker-major:
    worker i owns rows [i*s, (i+1)*s) — matching the ("pod","data") sharding
    of the leading batch axis.
    """
    s = examples_per_worker
    w_worker = mask * (1.0 / (k.astype(mask.dtype) * s))
    return jnp.repeat(w_worker, s, total_repeat_length=mask.shape[0] * s)


def masked_mean_weights(mask: jax.Array, k: jax.Array) -> jax.Array:
    """Per-worker weights m_i / k (for losses already averaged within a worker)."""
    return mask * (1.0 / k.astype(mask.dtype))


def fastest_k_weighted_loss(
    per_example_losses: jax.Array, mask: jax.Array, k: jax.Array, examples_per_worker: int
) -> jax.Array:
    """Eq.-(2) weighted loss without ever building a length-m weight vector.

    ``sum_ell v_ell * loss_ell`` with ``v_ell = m_{worker(ell)} / (k*s)``
    factorizes over the worker-major batch layout as a per-worker segment sum
    (a contiguous reshape + row sum — the segments are equal-sized) followed
    by an n-vector dot with the mask: O(m + n) adds and no (m,) temporary,
    vs the reference ``per_example_weights`` path's repeat + multiply.
    Gradients agree: d/dw of both forms weight example ell's gradient by
    exactly v_ell.
    """
    s = examples_per_worker
    shard_sums = per_example_losses.reshape(-1, s).sum(axis=1)  # (n,)
    return jnp.dot(shard_sums, mask) * (1.0 / (k.astype(per_example_losses.dtype) * s))


def stale_weighted_loss(
    losses_by_worker: jax.Array, mask: jax.Array, k: jax.Array
) -> jax.Array:
    """Eq.-(2)-style weighted loss over *stale* per-worker evaluations.

    ``losses_by_worker`` is (n, s): row i holds worker i's per-example losses
    evaluated at worker i's OWN parameter snapshot (the dispatch-time model,
    per the K-async execution modes).  Differentiating wrt the stacked
    snapshots gives ``mask_i/(k*s) * sum_{a in S_i} grad F(a, w_i)`` per row
    — each arriving worker's stale partial gradient with the eq.-(2) weight
    — so the master's update is the row-sum of that gradient stack.  Reuses
    the segment-sum path (`fastest_k_weighted_loss`): no (m,) weight vector,
    and for identical snapshots the arithmetic is the sync engine's.
    """
    n, s = losses_by_worker.shape
    return fastest_k_weighted_loss(losses_by_worker.reshape(n * s), mask, k, s)


def fastest_k_mask_time(times: jax.Array, k: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(participation mask, X_(k)) from one draw of response times.

    Ranks are computed once and shared between the mask and the k-th order
    statistic.  This is THE per-iteration hot-path primitive: both
    ``run_monte_carlo`` (via ``fastest_k_draw``) and the sweep engine (which
    samples through its packed-parameter ``lax.switch``) call it, so the two
    engines stay bitwise-identical by construction.  Its device time is
    the ``repro.ranks`` scope.
    """
    with jax.named_scope("repro.ranks"):
        ranks = worker_ranks(times)
        mask = (ranks < k).astype(times.dtype)
        return mask, _time_from_ranks(ranks, times, k, None)


def fastest_k_draw(
    model: StragglerModel,
    key: jax.Array,
    n_workers: int,
    k: jax.Array,
    comm: Optional[CommModel] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One iteration's straggler draw: (participation mask, iteration time).

    The Monte-Carlo hot path: response times are sampled once, ranked once,
    and the ranks shared between the fastest-k mask and the k-th order
    statistic.  Unlike ``fastest_k_iteration`` no per-example weight vector
    is materialized — pair with ``fastest_k_weighted_loss``.
    """
    times = sample_worker_times(model, key, n_workers)
    mask, t = fastest_k_mask_time(times, k)
    if comm is not None:
        t = t + comm.time(k)
    return mask, t


def ordered_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis in a fixed pairwise order (zero-padded to 2^j).

    ``jnp.sum`` leaves the order of its adds to the backend, and XLA:TPU
    picks it from the array's physical layout, which it chooses per program
    from the shapes: the same (lanes, 400) eval reduction runs along the
    128-wide lane axis in a 32-lane program and along the sublanes in a
    480-lane one, and the two round differently.  Halving adds are
    elementwise, so this sum's bits do not depend on layout, lane count or
    mesh shape — the bitwise sweep-vs-looped contract on the chip.
    """
    n = x.shape[-1]
    width = 1 << max(n - 1, 0).bit_length()
    if width > n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - n)])
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def active_worker_mean_loss(
    per_example_losses: jax.Array, n_active, n_slots: int,
    examples_per_worker: int,
) -> jax.Array:
    """Mean loss over the ACTIVE workers' examples (the first n_active shards).

    With n as a grid axis, cells are padded to ``n_slots`` worker slots and
    only the first ``n_active`` own data that trains; their shards are the
    cell's objective.  ``n_active`` may be traced (it is a grid leaf in the
    sweep engine), so both forms are computed and selected: when every slot
    is active the full mean's scale ``1/m`` is a host constant, the same
    bits whether ``n_active`` is traced (sweep) or a Python int (the looped
    engine's homogeneous eval, where the select folds away).  Both sums are
    ``ordered_sum``s of the per-shard ``ordered_sum``s.

    ``n_active == 0`` (an all-crashed fleet has no objective left) is
    pinned to **+inf**, not the 0/0 NaN the naive division would produce:
    the denominator is clamped to 1 — exact (an int max; for every
    ``n_active >= 1`` the clamp is the identity, so positive-count cells
    keep their bits) — and the zero-count lane is overridden by a select.
    """
    s = examples_per_worker
    dtype = per_example_losses.dtype
    shard_sums = ordered_sum(per_example_losses.reshape(n_slots, s))
    full = ordered_sum(shard_sums) * (1.0 / (n_slots * s))
    active = (jnp.arange(n_slots) < n_active).astype(dtype)
    masked = ordered_sum(shard_sums * active) * (
        1.0 / (jnp.maximum(n_active, 1).astype(dtype) * s)
    )
    masked = jnp.where(n_active == 0, jnp.inf, masked)
    return jnp.where(n_active == n_slots, full, masked)


# --------------------------------------------------------------------------
# Robust aggregation (the Byzantine-fault axis, ROADMAP item 3).
#
# The eq.-(2) weighted mean is a single corrupted worker away from an
# arbitrary update; the classic robust alternatives operate on the
# per-worker gradient ROWS (each arriving worker's unweighted shard-mean
# gradient) instead of their mask-weighted sum.  All three are in-graph,
# fixed-shape, and take a traced participation mask + traced k, so they
# drop into the engines as a per-cell ``agg`` leaf (see sweep.SweepCase):
#
# * ``trimmed``   — per-coordinate trimmed mean: drop the floor(beta*k)
#   smallest and largest of the k arrived values, average the rest;
# * ``median``    — per-coordinate median of the k arrived values;
# * ``geomedian`` — geometric median via fixed-iteration Weiszfeld
#   (Draco's checkpoint aggregator), smoothed with an eps-clamped
#   denominator so coincident points are exact fixed points.
#
# ``make_robust_select`` wraps them as a per-cell select OVER the mean
# path's gradient: a mean-aggregation cell's value rides the select
# passthrough bit for bit, which is what lets mixed mean/robust grids share
# one compiled program while mean-only grids prune to today's exact program
# (sweep.GridSignature.agg_kinds).
# --------------------------------------------------------------------------

# Aggregator kinds — select indices baked into compiled sweep programs.
# Append; never reorder.
AGG_KINDS = {"mean": 0, "trimmed": 1, "median": 2, "geomedian": 3}
AGG_MEAN, AGG_TRIMMED, AGG_MEDIAN, AGG_GEOMEDIAN = range(4)

# Weiszfeld iteration count: static (baked into every robust program) so
# the looped and sweep engines trace identical graphs.  8 iterations
# reach ~1e-6 relative accuracy on the unit-scale gradient clouds the
# tests pin (geometric-median convergence is linear away from degeneracy).
WEISZFELD_ITERS = 8
_WEISZFELD_EPS = 1e-12


def _sorted_masked(mat: jax.Array, mask: jax.Array) -> jax.Array:
    """Per-coordinate ascending sort with non-participants pushed to +inf.

    ``mat`` is the (n_slots, D) row matrix, ``mask`` the {0,1} participation
    vector with k ones: after the sort rows 0..k-1 of each column hold the
    arrived values, rows k.. hold +inf.
    """
    vals = jnp.where(mask[:, None] > 0, mat, jnp.inf)
    return jnp.sort(vals, axis=0)


def trimmed_mean_rows(
    mat: jax.Array, mask: jax.Array, k: jax.Array, trim_frac
) -> jax.Array:
    """Per-coordinate beta-trimmed mean over the masked rows.

    Drops the ``t = floor(trim_frac * k)`` smallest and largest of the k
    arrived values per coordinate (t clipped to ``(k-1)//2`` so at least
    one value always survives) and averages the remaining ``k - 2t``.
    ``trim_frac`` may be a traced leaf (sweep) or a Python float (looped
    engine) — the multiply-then-floor is the same value either way.
    """
    n = mat.shape[0]
    t = jnp.floor(trim_frac * k.astype(jnp.float32)).astype(jnp.int32)
    t = jnp.minimum(t, (k - 1) // 2)
    svals = _sorted_masked(mat, mask)
    pos = jnp.arange(n, dtype=jnp.int32)[:, None]
    keep = (pos >= t) & (pos <= k - 1 - t)
    cnt = (k - 2 * t).astype(mat.dtype)
    return jnp.sum(jnp.where(keep, svals, 0.0), axis=0) * (1.0 / cnt)


def coordinate_median_rows(
    mat: jax.Array, mask: jax.Array, k: jax.Array
) -> jax.Array:
    """Per-coordinate median of the k masked rows (lower/upper averaged
    for even k, the exact middle value for odd k)."""
    svals = _sorted_masked(mat, mask)
    lo = jnp.take(svals, (k - 1) // 2, axis=0)
    hi = jnp.take(svals, k // 2, axis=0)
    return 0.5 * (lo + hi)


def geometric_median_rows(
    mat: jax.Array, mask: jax.Array, k: jax.Array,
    n_iter: int = WEISZFELD_ITERS,
) -> jax.Array:
    """Geometric median of the masked rows via fixed-iteration Weiszfeld.

    Starts at the masked mean and iterates ``y <- sum_i w_i x_i / sum_i
    w_i`` with ``w_i = mask_i / max(||x_i - y||, eps)`` a fixed ``n_iter``
    times — in-graph, no convergence branch, so the trace is static.  The
    eps clamp makes the all-rows-coincident case an exact fixed point
    (every weight equals mask_i/eps, and the weighted mean of identical
    points is that point up to one rounding) and protects the iterate from
    a 0/0 when y lands exactly on a data point.
    """
    kf = k.astype(mat.dtype)
    y = jnp.tensordot(mask, mat, axes=1) * (1.0 / kf)
    for _ in range(n_iter):
        d = jnp.sqrt(jnp.sum((mat - y[None, :]) ** 2, axis=1))
        w = mask / jnp.maximum(d, _WEISZFELD_EPS)
        y = jnp.tensordot(w, mat, axes=1) / jnp.sum(w)
    return y


def _flatten_rows(rows):
    """Pytree of (n_slots, ...) row leaves -> ((n_slots, D) f32 matrix,
    unflatten(vec) -> params-shaped pytree).  D is static."""
    leaves, treedef = jax.tree_util.tree_flatten(rows)
    n = leaves[0].shape[0]
    mat = jnp.concatenate(
        [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1
    )

    def unflatten(vec):
        out, off = [], 0
        for l in leaves:
            sz = 1
            for s in l.shape[1:]:
                sz *= s
            out.append(vec[off:off + sz].reshape(l.shape[1:]).astype(l.dtype))
            off += sz
        return jax.tree_util.tree_unflatten(treedef, out)

    return mat, unflatten


def make_robust_select(agg_kind, agg_param, present: tuple):
    """Per-cell aggregator select: ``select(mean_g, rows, mask, k) -> g``.

    ``present`` is the STATIC set of aggregator kinds the program must
    trace (the grid signature's ``agg_kinds``); only the robust members are
    computed.  ``agg_kind``/``agg_param`` are per-cell leaves — traced in
    the sweep, baked constants in the looped engine (the select then folds,
    leaving the chosen aggregator's bits).  Returns ``None`` when no robust
    kind is present: the engines skip row materialization entirely and the
    mean path is today's exact program.

    Mean-aggregation cells inside a robust program take ``mean_g`` through
    the ``where`` chain unchanged — the select-passthrough bitwise rule.
    """
    robust = tuple(sorted(set(present) - {AGG_MEAN}))
    if not robust:
        return None

    def select(mean_g, rows, mask, k):
        mat, unflatten = _flatten_rows(rows)
        g = mean_g
        for kind in robust:
            if kind == AGG_TRIMMED:
                val = trimmed_mean_rows(mat, mask, k, agg_param)
            elif kind == AGG_MEDIAN:
                val = coordinate_median_rows(mat, mask, k)
            elif kind == AGG_GEOMEDIAN:
                val = geometric_median_rows(mat, mask, k)
            else:
                raise ValueError(f"unknown aggregator kind {kind}")
            vg = unflatten(val)
            g = jax.tree.map(
                lambda a, b: jnp.where(agg_kind == kind, b, a), g, vg
            )
        return g

    return select


def fastest_k_iteration(
    model: StragglerModel,
    key: jax.Array,
    n_workers: int,
    k: jax.Array,
    examples_per_worker: int,
    comm: Optional[CommModel] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Convenience bundle: (per-example weights, iteration mask, iteration time).

    Ranks are computed once and shared between the mask and the k-th order
    statistic (the standalone `fastest_k_mask`/`iteration_time` each rank on
    their own).  This is the documented eq.-(2) reference realization; the
    Monte-Carlo engines use `fastest_k_draw` + `fastest_k_weighted_loss`,
    which never materialize the (m,) weight vector.
    """
    times = sample_worker_times(model, key, n_workers)
    ranks = worker_ranks(times)
    mask = (ranks < k).astype(times.dtype)
    weights = per_example_weights(mask, k, examples_per_worker)
    t = _time_from_ranks(ranks, times, k, comm)
    return weights, mask, t
