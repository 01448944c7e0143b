"""GradSource conformance suite (the tentpole refactor's contract).

Three layers of protection:

  1. **Historical bitwise pins** — the ``run_monte_carlo`` thin wrapper (now
     routed through ``PerExampleSource``) must reproduce the pre-refactor
     engine's trajectories BITWISE for all five registered controllers in all
     three execution modes.  The goldens (tests/goldens/quadratic_mc.npz)
     were first generated from the engine before the gradient source became
     pluggable, and regenerated under jax 0.9.0, whose PRNG streams differ —
     see tests/goldens/gen_quadratic_goldens.py.
  2. **Wrapper == source** — calling the source-level entry points directly
     with ``PerExampleSource`` is the same computation as the historical
     signatures, bitwise, in both engines.
  3. **A real loss through the same pipes** — ``LMSource`` (a jitted LM
     train step over token shards) runs under every execution mode in the
     looped engine and is bitwise sweep-vs-looped as a fleet cell, proving
     the engines are loss-generic rather than quadratic-shaped.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import (
    FixedKController,
    PflugController,
    ScheduleController,
    SketchedPflugController,
    VarianceRatioController,
)
from repro.core.gradsource import GradSource, PerExampleSource, SourceFns
from repro.core.montecarlo import run_monte_carlo, run_monte_carlo_source
from repro.core.straggler import Exponential, WorkerFleet
from repro.core.sweep import SweepCase, run_sweep, run_sweep_source
from repro.data import make_linreg_data
from repro.launch.lm_source import LMSource

# Mirrors tests/goldens/gen_quadratic_goldens.py (_GOLDEN_* constants): keep
# the two in sync if the goldens are ever regenerated.
_GOLDEN_N, _GOLDEN_M, _GOLDEN_D = 6, 60, 4
_GOLDEN_ETA = 0.005
_GOLDEN_NUM_ITERS = 60
_GOLDEN_EVAL_EVERY = 25
_GOLDEN_N_REPLICAS = 2
_GOLDEN_DATA_SEED, _GOLDEN_KEY_SEED = 0, 123
_MODES = ("sync", "kasync", "kbatch")


def _quad_loss(w, X, y):
    return (X @ w - y) ** 2


def _golden_controllers():
    n = _GOLDEN_N
    return {
        "fixed": FixedKController(n_workers=n, k=2),
        "pflug": PflugController(n_workers=n, k0=1, step=1, thresh=3, burnin=5),
        "sketched_pflug": SketchedPflugController(
            n_workers=n, k0=1, step=1, thresh=3, burnin=5, sketch_dim=8
        ),
        "schedule": ScheduleController(
            n_workers=n, switch_times=[2.0, 6.0], k0=1, step=2
        ),
        "variance_ratio": VarianceRatioController(
            n_workers=n, k0=1, step=2, burnin=10
        ),
    }


@pytest.fixture(scope="module")
def goldens():
    import os

    path = os.path.join(os.path.dirname(__file__), "goldens", "quadratic_mc.npz")
    return np.load(path)


@pytest.fixture(scope="module")
def golden_inputs():
    data = make_linreg_data(
        jax.random.PRNGKey(_GOLDEN_DATA_SEED), m=_GOLDEN_M, d=_GOLDEN_D
    )
    keys = jax.random.split(
        jax.random.PRNGKey(_GOLDEN_KEY_SEED), _GOLDEN_N_REPLICAS
    )
    return data, keys


# A tiny LM so trace+run stays cheap; the architecture is the real registered
# qwen1.5-0.5b graph, just shrunk.
_TINY = (("n_layers", 1), ("d_model", 32), ("n_heads", 2), ("n_kv_heads", 2),
         ("d_ff", 64), ("vocab_size", 64))


@pytest.fixture(scope="module")
def lm():
    src = LMSource(arch="qwen1.5-0.5b", smoke=True, overrides=_TINY)
    params0 = src.init_params(jax.random.PRNGKey(0))
    data = src.make_data(n_rows=16, seq_len=16, seed=0)
    return src, params0, data


# ------------------------------------------------- protocol conformance


def test_protocol_isinstance():
    assert isinstance(PerExampleSource(_quad_loss), GradSource)
    assert isinstance(LMSource(overrides=_TINY), GradSource)
    assert not isinstance(object(), GradSource)


def test_per_example_source_build_shapes(golden_inputs):
    data, _ = golden_inputs
    src = PerExampleSource(_quad_loss)
    fns = src.build((data.X, data.y), _GOLDEN_N)
    assert isinstance(fns, SourceFns)
    w = jnp.zeros((_GOLDEN_D,))
    mask = jnp.ones((_GOLDEN_N,))
    g = fns.grad(w, mask, jnp.asarray(_GOLDEN_N, jnp.int32))
    assert g.shape == w.shape
    assert fns.eval_loss(w).shape == ()
    full = fns.eval_loss_active(w, jnp.asarray(_GOLDEN_N, jnp.int32))
    # all-active must be bitwise the plain mean (the sweep/looped eval pin)
    assert np.array_equal(np.asarray(full), np.asarray(fns.eval_loss(w)))


def test_check_rejects_indivisible_rows():
    X = jnp.zeros((10, 2))
    y = jnp.zeros((10,))
    with pytest.raises(ValueError, match="divisible"):
        PerExampleSource(_quad_loss).check((X, y), 4)


def test_cache_token_distinguishes_sources():
    t1 = PerExampleSource(_quad_loss).cache_token()
    t2 = LMSource(overrides=_TINY).cache_token()
    assert hash(t1) != hash(t2) or t1 != t2
    assert t1 == PerExampleSource(_quad_loss).cache_token()


# ------------------------------------------------- pre-refactor goldens


@pytest.mark.parametrize("mode", _MODES)
@pytest.mark.parametrize("name", sorted(_golden_controllers()))
def test_wrapper_bitwise_vs_prerefactor_goldens(name, mode, goldens, golden_inputs):
    data, keys = golden_inputs
    res = run_monte_carlo(
        _quad_loss, jnp.zeros((_GOLDEN_D,)), data.X, data.y,
        n_workers=_GOLDEN_N, controller=_golden_controllers()[name],
        straggler=Exponential(rate=1.0), eta=_GOLDEN_ETA,
        num_iters=_GOLDEN_NUM_ITERS, keys=keys,
        eval_every=_GOLDEN_EVAL_EVERY, mode=mode,
    )
    for field in ("time", "loss", "k"):
        got = np.asarray(getattr(res, field))
        want = goldens[f"{name}__{mode}__{field}"]
        assert np.isfinite(want).all(), (name, mode, field)
        assert np.array_equal(got, want), (
            f"{name}/{mode}/{field}: refactored engine drifted from the "
            f"pre-refactor goldens (max abs diff "
            f"{np.max(np.abs(got - want))})"
        )


# ------------------------------------------------- wrapper == source


@pytest.mark.parametrize("mode", _MODES)
def test_mc_wrapper_equals_source_entry(mode, golden_inputs):
    data, keys = golden_inputs
    ctrl = PflugController(n_workers=_GOLDEN_N, k0=1, step=1, thresh=3, burnin=5)
    common = dict(
        n_workers=_GOLDEN_N, controller=ctrl, straggler=Exponential(rate=1.0),
        eta=_GOLDEN_ETA, num_iters=30, keys=keys, eval_every=10, mode=mode,
    )
    a = run_monte_carlo(_quad_loss, jnp.zeros((_GOLDEN_D,)), data.X, data.y, **common)
    b = run_monte_carlo_source(
        PerExampleSource(_quad_loss), jnp.zeros((_GOLDEN_D,)), (data.X, data.y),
        **common,
    )
    for field in ("time", "loss", "k"):
        assert np.array_equal(np.asarray(getattr(a, field)),
                              np.asarray(getattr(b, field))), (mode, field)


def test_sweep_wrapper_equals_source_entry(golden_inputs):
    data, keys = golden_inputs
    cases = [
        SweepCase(FixedKController(n_workers=_GOLDEN_N, k=2),
                  Exponential(rate=1.0), eta=_GOLDEN_ETA),
        SweepCase(PflugController(n_workers=_GOLDEN_N, k0=1, step=1, thresh=3,
                                  burnin=5),
                  Exponential(rate=1.0), eta=_GOLDEN_ETA, mode="kasync"),
    ]
    common = dict(n_workers=_GOLDEN_N, cases=cases, num_iters=30, keys=keys,
                  eval_every=10)
    a = run_sweep(_quad_loss, jnp.zeros((_GOLDEN_D,)), data.X, data.y, **common)
    b = run_sweep_source(
        PerExampleSource(_quad_loss), jnp.zeros((_GOLDEN_D,)), (data.X, data.y),
        **common,
    )
    for g in range(len(cases)):
        for field in ("time", "loss", "k"):
            assert np.array_equal(np.asarray(getattr(a.cell(g), field)),
                                  np.asarray(getattr(b.cell(g), field))), (g, field)


# ------------------------------------------------- a real LM through the pipes


@pytest.mark.parametrize("mode", _MODES)
def test_lm_source_every_mode_smoke(mode, lm):
    src, params0, data = lm
    res = run_monte_carlo_source(
        src, params0, data, n_workers=4,
        controller=PflugController(n_workers=4, k0=2, step=1, thresh=2, burnin=2),
        straggler=Exponential(rate=1.0), eta=0.1, num_iters=8,
        keys=jax.random.split(jax.random.PRNGKey(7), 1), eval_every=4,
        mode=mode,
    )
    t, l, k = (np.asarray(a) for a in (res.time, res.loss, res.k))
    assert np.isfinite(t).all() and np.isfinite(l).all()
    assert np.all(np.diff(t, axis=1) > 0)
    assert ((1 <= k) & (k <= 4)).all()


def test_lm_sweep_vs_looped_bitwise(lm):
    """ONE sweep dispatch over LM cells == per-cell looped runs, bitwise.

    Cells are WorkerFleet-backed: the fleet path is the documented bitwise
    ground truth (looped fleet eval shares the sweep's active-worker eval
    graph, so even the LM forward's XLA fusion agrees to the last ulp).
    Two graph-structure knobs are pinned, both instances of the known
    last-ulp drift class (structurally different programs; see
    GridSignature's docstring) that the quadratic escapes but the larger LM
    graph does not: ``unroll`` is set to the same value in both engines
    (scan-body fusion differs across unroll factors), and the grid is
    single-mode (a mixed-mode grid wraps the step in a ``lax.switch``, which
    refuses the kasync eval's fusion by one ulp)."""
    src, params0, data = lm
    n = 4
    fleet = WorkerFleet(models=(Exponential(rate=1.0),) * n)
    keys = jax.random.split(jax.random.PRNGKey(9), 2)
    cases = [
        SweepCase(FixedKController(n_workers=n, k=2), fleet, eta=0.1,
                  label="k2", mode="kasync"),
        SweepCase(PflugController(n_workers=n, k0=2, step=1, thresh=2,
                                  burnin=2),
                  fleet, eta=0.1, label="pflug", mode="kasync"),
    ]
    swept = run_sweep_source(src, params0, data, n_workers=n, cases=cases,
                             num_iters=8, keys=keys, eval_every=4, unroll=4)
    for g, case in enumerate(cases):
        looped = run_monte_carlo_source(
            src, params0, data, n_workers=n, controller=case.controller,
            straggler=case.straggler, eta=case.eta, num_iters=8, keys=keys,
            eval_every=4, mode=case.mode, unroll=4,
        )
        for field in ("time", "loss", "k"):
            a = np.asarray(getattr(swept.cell(g), field))
            b = np.asarray(getattr(looped, field))
            assert np.array_equal(a, b), (
                f"{case.label}/{field}: max abs diff {np.max(np.abs(a - b))}"
            )
