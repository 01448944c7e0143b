"""Tests for the heterogeneous worker model: the per-worker packed-parameter
protocol, time-varying rate schedules, n-as-a-grid-axis, heterogeneous
order-statistic theory, and the sketched-Pflug sweep cell.

Two hard invariants are pinned here:

* a forced-heterogeneous sweep cell is BITWISE-equal to a looped
  ``run_monte_carlo`` call with the same per-worker spec and PRNG keys, and
  an all-identical-rows fleet is BITWISE-equal to the scalar (pre-refactor)
  homogeneous path;
* repopulating an equally-shaped (grid, n_slots) sweep never retraces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation
from repro.core.aggregation import active_worker_mean_loss, worker_ranks
from repro.core.controller import (
    FixedKController,
    PflugController,
    SketchedPflugController,
)
from repro.core.montecarlo import run_monte_carlo
from repro.core.straggler import (
    Bimodal,
    Deterministic,
    Exponential,
    Pareto,
    RateSchedule,
    ShiftedExponential,
    WorkerFleet,
    family_index,
    pack_params,
    pack_params_per_worker,
    pack_schedule,
    sample_times_per_worker,
    schedule_multiplier,
)
from repro.core.sweep import SweepCase, run_sweep, sweep_cache_stats
from repro.core.theory import SGDSystem, hetero_order_stat_moments, switching_times
from repro.data import make_linreg_data

N, M, D = 10, 200, 5

ALL_MODELS = (
    Exponential(rate=1.3),
    ShiftedExponential(shift=0.7, rate=2.0),
    Pareto(x_m=0.5, alpha=1.5),
    Bimodal(fast_mean=0.5, slow_mean=8.0, p_slow=0.2),
    Deterministic(value=3.0),
)


@pytest.fixture(scope="module")
def linreg():
    data = make_linreg_data(jax.random.PRNGKey(0), m=M, d=D)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / M).max())
    return data, 0.5 / L


def _loss(w, X, y):
    return (X @ w - y) ** 2


def _assert_bitwise(res, g, ref, what):
    for name, a, b in (("time", res.time[g], ref.time),
                       ("loss", res.loss[g], ref.loss),
                       ("k", res.k[g], ref.k)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f"{what}: {name} differs"


# ------------------------------------------ per-worker sampling: the protocol


@pytest.mark.parametrize("model", ALL_MODELS, ids=lambda m: type(m).__name__)
def test_identical_rows_bitwise_equal_scalar_path(model):
    """A parameter matrix whose rows all equal one model's packed vector must
    reproduce the scalar ``_sample_packed`` path bit for bit — the invariant
    that keeps homogeneous grids bitwise-stable across the refactor."""
    key = jax.random.PRNGKey(3)
    n = 9
    p = pack_params(model)
    scalar = np.asarray(type(model)._sample_packed(key, n, jnp.asarray(p)))
    pmat = jnp.asarray(np.tile(p, (n, 1)))
    rows = np.asarray(type(model)._sample_packed_rows(key, pmat))
    np.testing.assert_array_equal(scalar, rows)
    kinds = jnp.full((n,), family_index(model), jnp.int32)
    selected = np.asarray(sample_times_per_worker(kinds, pmat, key))
    np.testing.assert_array_equal(scalar, selected)


def test_per_slot_marginals_match_scalar_models_ks():
    """Each slot of a mixed fleet must draw from ITS model's distribution:
    KS distance of the slot's empirical CDF to the model's analytic CDF."""
    models = (Exponential(1.0), Exponential(0.25), Pareto(0.5, 1.5),
              Bimodal(0.5, 8.0, 0.2), ShiftedExponential(0.7, 2.0))
    pmat, kinds, n_active = pack_params_per_worker(WorkerFleet(models=models), len(models))
    K = 2000
    keys = jax.random.split(jax.random.PRNGKey(5), K)
    draws = np.asarray(jax.vmap(
        lambda k: sample_times_per_worker(jnp.asarray(kinds), jnp.asarray(pmat), k)
    )(keys))  # (K, n)
    crit = 1.63 / np.sqrt(K)  # ~1% KS critical value
    for i, m in enumerate(models):
        x = np.sort(draws[:, i])
        ecdf = np.arange(1, K + 1) / K
        d = float(np.max(np.abs(ecdf - m.cdf(x))))
        assert d < crit, f"slot {i} ({type(m).__name__}): KS distance {d:.4f}"


def test_pack_params_per_worker_padding_and_validation():
    fleet = WorkerFleet(models=(Exponential(1.0), Pareto(0.5, 1.5)))
    pmat, kinds, n_active = pack_params_per_worker(fleet, 4)
    assert n_active == 2 and pmat.shape == (4, 3) and kinds.shape == (4,)
    assert kinds[0] == family_index(Exponential()) and kinds[1] == family_index(Pareto())
    assert np.all(np.isinf(pmat[2:, 0]))  # inactive rows sample +inf
    # scalar broadcast with explicit n_active
    pmat2, kinds2, n2 = pack_params_per_worker(Exponential(2.0), 4, n_active=3)
    assert n2 == 3 and np.all(kinds2[:3] == family_index(Exponential()))
    np.testing.assert_array_equal(pmat2[0], pmat2[2])
    with pytest.raises(ValueError, match="active workers"):
        pack_params_per_worker(fleet, 1)
    with pytest.raises(ValueError, match="at least one"):
        WorkerFleet(models=())


def test_fleet_sample_pads_inactive_with_inf():
    fleet = WorkerFleet(models=(Exponential(1.0),) * 3)
    t = np.asarray(fleet.sample(jax.random.PRNGKey(0), 6))
    assert np.all(np.isfinite(t[:3])) and np.all(np.isinf(t[3:]))


# --------------------------------------------------- rate schedules in-graph


def test_rate_schedule_step_and_linear_multiplier():
    mode, leaf, times, scales = pack_schedule(
        RateSchedule(times=(10.0, 20.0), scales=(0.5, 0.25)), 4)
    for t, want in ((5.0, 1.0), (10.0, 0.5), (15.0, 0.5), (25.0, 0.25)):
        got = float(schedule_multiplier(mode, times, scales, t))
        assert got == pytest.approx(want), (t, got)
    mode, _, times, scales = pack_schedule(
        RateSchedule(times=(0.0, 10.0), scales=(1.0, 0.5), mode="linear"), 4)
    assert float(schedule_multiplier(mode, times, scales, 5.0)) == pytest.approx(0.75)
    assert float(schedule_multiplier(mode, times, scales, 50.0)) == pytest.approx(0.5)


def test_rate_schedule_validation():
    with pytest.raises(ValueError, match="non-decreasing"):
        RateSchedule(times=(5.0, 1.0), scales=(1.0, 1.0))
    with pytest.raises(ValueError, match="times vs"):
        RateSchedule(times=(1.0,), scales=(1.0, 2.0))
    with pytest.raises(ValueError, match="unknown mode"):
        RateSchedule(times=(1.0,), scales=(1.0,), mode="cubic")


def test_mid_run_slowdown_slows_the_simulated_clock(linreg):
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    base = (Exponential(1.0),) * N
    kw = dict(n_workers=N, controller=FixedKController(n_workers=N, k=3),
              eta=eta, num_iters=200, keys=keys, eval_every=50)
    drift = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y,
        straggler=WorkerFleet(models=base,
                              schedule=RateSchedule(times=(5.0,), scales=(0.25,))),
        **kw)
    still = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y,
        straggler=WorkerFleet(models=base), **kw)
    assert float(drift.time[:, -1].mean()) > 1.5 * float(still.time[:, -1].mean())


# --------------------------------- inactive (+inf) slots through worker_ranks


@pytest.mark.parametrize("n", [64, 190, 192, 200, 384])
def test_inactive_inf_slots_rank_past_n_active_both_paths(n):
    """+inf slots must occupy ranks n_active..n-1 in slot order on BOTH rank
    paths (n values straddle the pairwise/top_k crossover at 192)."""
    n_active = n - 7
    key = jax.random.PRNGKey(n)
    finite = jax.random.exponential(key, (n_active,))
    times = jnp.concatenate([finite, jnp.full((7,), jnp.inf)])
    for method in ("pairwise", "topk", "auto"):
        ranks = np.asarray(worker_ranks(times, method=method))
        np.testing.assert_array_equal(
            ranks[n_active:], np.arange(n_active, n),
            err_msg=f"method={method}: inactive ranks not pinned past n_active",
        )
        assert sorted(ranks[:n_active]) == list(range(n_active))
    # an inactive slot can therefore never enter a fastest-k set, k <= n_active
    mask = np.asarray(aggregation.fastest_k_mask(times, jnp.asarray(n_active)))
    assert np.all(mask[n_active:] == 0) and mask.sum() == n_active


def _pairwise_sum(a):
    """float32 numpy reference of aggregation.ordered_sum (last axis)."""
    a = np.asarray(a, np.float32)
    width = 1 << max(a.shape[-1] - 1, 0).bit_length()
    a = np.concatenate([a, np.zeros(a.shape[:-1] + (width - a.shape[-1],), np.float32)], -1)
    while a.shape[-1] > 1:
        half = a.shape[-1] // 2
        a = a[..., :half] + a[..., half:]
    return a[..., 0]


def test_active_worker_mean_loss_full_grid_is_bitwise_mean():
    losses = jax.random.normal(jax.random.PRNGKey(0), (24,)) ** 2
    full = active_worker_mean_loss(losses, jnp.asarray(6, jnp.int32), 6, 4)
    # the fixed-order mean: pairwise within shards, then across shards
    want = _pairwise_sum(_pairwise_sum(np.asarray(losses).reshape(6, 4))) * np.float32(1 / 24)
    assert np.array_equal(np.asarray(full), want)
    # a traced all-active count gives the bits of the looped engine's
    # homogeneous eval (n_active a Python int, the select folded away)
    homo = jax.jit(lambda l: active_worker_mean_loss(l, 6, 6, 4))(losses)
    assert np.array_equal(np.asarray(full), np.asarray(homo))
    np.testing.assert_allclose(float(full), float(jnp.mean(losses)), rtol=1e-6)
    # masked form averages exactly the first n_active shards
    part = active_worker_mean_loss(losses, jnp.asarray(2, jnp.int32), 6, 4)
    np.testing.assert_allclose(float(part), float(jnp.mean(losses[:8])), rtol=1e-6)



@pytest.mark.parametrize("n", [1, 5, 20, 400])
def test_ordered_sum_is_the_pairwise_sum_at_any_lane_count(n):
    x = jax.random.normal(jax.random.PRNGKey(n), (48, n)) * 100.0
    want = _pairwise_sum(np.asarray(x))
    for lanes in (2, 16, 48):
        got = jax.jit(jax.vmap(aggregation.ordered_sum))(x[:lanes])
        assert np.array_equal(np.asarray(got), want[:lanes]), lanes

# ----------------------------- the acceptance invariants, engine vs engine


def test_forced_hetero_sweep_cell_bitwise_vs_looped_monte_carlo(linreg):
    """Acceptance: forced-heterogeneous cells (mixed families, rate drift,
    n < n_slots) bitwise-equal looped run_monte_carlo; an all-identical-rows
    fleet cell bitwise-equals the scalar homogeneous path."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    mixed = WorkerFleet(
        models=(Exponential(1.0),) * 7 + (Pareto(0.5, 1.5),) * 3,
        schedule=RateSchedule(times=(5.0,), scales=(0.5,)),
    )
    iid_rows = WorkerFleet(models=(Exponential(rate=1.0),) * N)
    small = WorkerFleet(models=(Exponential(2.0),) * 5)
    cases = [
        SweepCase(PflugController(n_workers=N, k0=2, step=2, thresh=5, burnin=10),
                  mixed, eta, label="mixed+drift"),
        SweepCase(PflugController(n_workers=N, k0=2, step=2, thresh=5, burnin=10),
                  iid_rows, eta, label="iid_rows"),
        SweepCase(FixedKController(n_workers=5, k=3), small, eta, label="n5"),
    ]
    res = run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                    cases=cases, num_iters=120, keys=keys, eval_every=40)
    for g, c in enumerate(cases):
        ref = run_monte_carlo(
            _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
            controller=c.controller, straggler=c.straggler, eta=c.eta,
            num_iters=120, keys=keys, eval_every=40)
        _assert_bitwise(res, g, ref, c.label)
    # the identical-rows fleet ALSO equals the scalar pre-refactor path
    scalar = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
        controller=cases[1].controller, straggler=Exponential(rate=1.0),
        eta=eta, num_iters=120, keys=keys, eval_every=40)
    _assert_bitwise(res, 1, scalar, "iid_rows vs scalar engine")
    # padded-cell k respects its n_active, and loss is finite throughout
    assert int(np.max(np.asarray(res.k[2]))) <= 5
    assert bool(np.all(np.isfinite(np.asarray(res.loss))))


def test_hetero_grid_repopulation_does_not_retrace(linreg):
    """Acceptance: under ``specialize=False`` repopulating an equally-shaped
    (grid, n_slots) sweep — different fleets, schedules, active counts,
    controllers — must reuse the compiled program (kinds and per-worker
    parameters are traced leaves).  ``specialize=False`` pins the
    fully-grid-agnostic program family here; the default per-signature
    cache happens to no-retrace these two grids as well (same controller
    kinds and flags — family composition never enters the signature), and
    tests/test_specialize.py pins that contract directly."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    kw = dict(n_workers=N, num_iters=80, keys=keys, eval_every=40,
              specialize=False)
    grid_a = [
        SweepCase(FixedKController(n_workers=N, k=2),
                  WorkerFleet(models=(Exponential(1.0),) * 6 + (Pareto(0.5, 1.5),) * 4,
                              schedule=RateSchedule(times=(3.0,), scales=(0.5,))),
                  eta, label="a0"),
        SweepCase(PflugController(n_workers=7, k0=1, step=1, thresh=3),
                  WorkerFleet(models=(Bimodal(),) * 7), eta, label="a1"),
    ]
    run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, cases=grid_a, **kw)
    before = sweep_cache_stats()["traces"]
    grid_b = [
        SweepCase(FixedKController(n_workers=4, k=2),
                  WorkerFleet(models=(ShiftedExponential(0.5, 2.0),) * 4), eta,
                  label="b0"),
        SweepCase(PflugController(n_workers=N, k0=2, step=2, thresh=4),
                  WorkerFleet(models=(Exponential(0.5),) * 10,
                              schedule=RateSchedule(times=(1.0,), scales=(2.0,),
                                                    mode="linear")),
                  eta, label="b1"),
    ]
    res = run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, cases=grid_b, **kw)
    assert sweep_cache_stats()["traces"] == before, "same-shape hetero grid retraced"
    ref = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
        controller=grid_b[1].controller, straggler=grid_b[1].straggler, eta=eta,
        num_iters=80, keys=keys, eval_every=40)
    _assert_bitwise(res, 1, ref, "repopulated hetero cell")


# ------------------------------------------- sketched Pflug as a sweep cell


def test_sketched_pflug_sweep_cell_bitwise_vs_looped(linreg):
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    cases = [
        SweepCase(SketchedPflugController(n_workers=N, k0=1, step=2, thresh=3,
                                          burnin=5, sketch_dim=8),
                  Exponential(rate=1.0), eta, label="sketched"),
        SweepCase(FixedKController(n_workers=N, k=4), Exponential(rate=1.0), eta,
                  label="fixed"),
    ]
    res = run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                    cases=cases, num_iters=120, keys=keys, eval_every=40)
    for g, c in enumerate(cases):
        ref = run_monte_carlo(
            _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
            controller=c.controller, straggler=c.straggler, eta=c.eta,
            num_iters=120, keys=keys, eval_every=40)
        _assert_bitwise(res, g, ref, c.label)


def test_sketched_cells_must_share_sketch_dim(linreg):
    data, eta = linreg
    cases = [
        SweepCase(SketchedPflugController(n_workers=N, sketch_dim=8),
                  Exponential(), eta, label="s8"),
        SweepCase(SketchedPflugController(n_workers=N, sketch_dim=16),
                  Exponential(), eta, label="s16"),
    ]
    with pytest.raises(ValueError, match="sketch_dim"):
        run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                  cases=cases, num_iters=10, key=jax.random.PRNGKey(0),
                  n_replicas=2)


# ------------------------------------------------- sweep-level validation


def test_monte_carlo_rejects_fleet_controller_mismatch(linreg):
    """The ground-truth engine must reject the same fleet/controller size
    mismatch the sweep rejects — otherwise k can exceed n_active and every
    trajectory's clock silently saturates to +inf."""
    data, eta = linreg
    fleet = WorkerFleet(models=(Exponential(1.0),) * 5)
    with pytest.raises(ValueError, match="fleet has 5 models"):
        run_monte_carlo(
            _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
            controller=FixedKController(n_workers=N, k=8), straggler=fleet,
            eta=eta, num_iters=10, key=jax.random.PRNGKey(0), n_replicas=2)


def test_sweep_rejects_fleet_controller_mismatch(linreg):
    data, eta = linreg
    fleet = WorkerFleet(models=(Exponential(1.0),) * 4)
    with pytest.raises(ValueError, match="fleet has 4 models"):
        run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                  cases=[SweepCase(FixedKController(n_workers=6, k=2), fleet, eta)],
                  num_iters=10, key=jax.random.PRNGKey(0), n_replicas=2)


def test_sweep_rejects_n_active_above_slots(linreg):
    data, eta = linreg
    with pytest.raises(ValueError, match="exceeds"):
        run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                  cases=[SweepCase(FixedKController(n_workers=N + 5, k=2),
                                   Exponential(), eta)],
                  num_iters=10, key=jax.random.PRNGKey(0), n_replicas=2)


# ---------------------------------------------- heterogeneous order statistics


def test_hetero_order_stats_reduce_to_iid_closed_forms():
    exp = Exponential(rate=1.3)
    n = 8
    for k in (1, 3, 8):
        m1, m2 = hetero_order_stat_moments((exp,) * n, k)
        assert m1 == pytest.approx(exp.mean_order_statistic(k, n), abs=2e-3)
        assert (m2 - m1 * m1) == pytest.approx(exp.var_order_statistic(k, n), abs=5e-3)


def test_hetero_order_stats_deterministic_fleet_sorts():
    fleet = (Deterministic(1.0), Deterministic(3.0), Deterministic(2.0))
    for k, want in ((1, 1.0), (2, 2.0), (3, 3.0)):
        m1, _ = hetero_order_stat_moments(fleet, k, num=2001)
        assert m1 == pytest.approx(want, abs=2e-2)


def test_theorem1_switch_times_on_heterogeneous_fleet():
    """The schedule controller's Theorem-1 policy stays available on a
    two-speed fleet: times are finite, non-decreasing, and slower fleets
    switch later (their mu_k are larger)."""
    fast, slow = Exponential(1.0), Exponential(0.25)
    mk = lambda fleet: switching_times(
        SGDSystem(eta=0.001, L=2.0, c=1.0, sigma2=10.0, s=10, F0_gap=100.0,
                  n=8, straggler=fleet), list(range(1, 8)))
    t_mixed = mk(WorkerFleet(models=(fast,) * 4 + (slow,) * 4))
    t_fast = mk(WorkerFleet(models=(fast,) * 8))
    assert all(np.isfinite(t_mixed)) and t_mixed == sorted(t_mixed)
    assert t_mixed[-1] > t_fast[-1]
    # fleet order statistics must agree between SGDSystem.mu and the moments
    wf = WorkerFleet(models=(fast,) * 4 + (slow,) * 4)
    assert wf.mean_order_statistic(3, 8) == pytest.approx(
        hetero_order_stat_moments(wf.models, 3)[0])


def test_every_family_has_a_cdf_consistent_with_quantile():
    u = np.linspace(0.05, 0.95, 19)
    for m in ALL_MODELS:
        if isinstance(m, Deterministic):
            continue
        x = m.quantile(u)
        np.testing.assert_allclose(m.cdf(x), u, atol=2e-3,
                                   err_msg=type(m).__name__)
