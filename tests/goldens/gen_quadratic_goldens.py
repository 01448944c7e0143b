"""Regenerate tests/goldens/quadratic_mc.npz — the pre-refactor engine pins.

The GradSource conformance suite (tests/test_gradsource.py) asserts that the
`run_monte_carlo` thin wrapper over `PerExampleSource` reproduces these
trajectories BITWISE, for all five registered controllers in all three
execution modes.  The arrays were first generated from the engine as it
stood before the gradient source became pluggable, pinning that refactor to
the historical arithmetic.  They were regenerated under jax 0.9.0: its
default PRNG (``jax_threefry_partitionable``) draws different streams, so
the old arrays could not be met by any engine; they now pin the engine's
arithmetic against drift under the installed jax.  The ``loss`` arrays were
regenerated once more when the eval loss became a fixed-order pairwise mean
(``aggregation.ordered_sum``): they moved by at most 2.5e-7 relative, and
``time`` and ``k`` kept their bits.

The configuration constants below are mirrored in tests/test_gradsource.py
(_GOLDEN_* names) — keep the two in sync if you ever regenerate.

Run from the repo root:

    PYTHONPATH=src python tests/goldens/gen_quadratic_goldens.py
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import (
    FixedKController,
    PflugController,
    ScheduleController,
    SketchedPflugController,
    VarianceRatioController,
)
from repro.core.montecarlo import run_monte_carlo
from repro.core.straggler import Exponential
from repro.data import make_linreg_data

N, M, D = 6, 60, 4
ETA = 0.005  # small enough that every controller/mode trajectory stays finite
NUM_ITERS = 60
EVAL_EVERY = 25  # -> eval points at 25, 50, 60
N_REPLICAS = 2
DATA_SEED, KEY_SEED = 0, 123
MODES = ("sync", "kasync", "kbatch")


def controllers():
    return {
        "fixed": FixedKController(n_workers=N, k=2),
        "pflug": PflugController(n_workers=N, k0=1, step=1, thresh=3, burnin=5),
        "sketched_pflug": SketchedPflugController(
            n_workers=N, k0=1, step=1, thresh=3, burnin=5, sketch_dim=8
        ),
        "schedule": ScheduleController(n_workers=N, switch_times=[2.0, 6.0], k0=1, step=2),
        "variance_ratio": VarianceRatioController(n_workers=N, k0=1, step=2, burnin=10),
    }


def per_example_loss(w, X, y):
    return (X @ w - y) ** 2


def main():
    data = make_linreg_data(jax.random.PRNGKey(DATA_SEED), m=M, d=D)
    keys = jax.random.split(jax.random.PRNGKey(KEY_SEED), N_REPLICAS)
    out = {
        "n_workers": N, "m": M, "d": D, "eta": ETA, "num_iters": NUM_ITERS,
        "eval_every": EVAL_EVERY, "n_replicas": N_REPLICAS,
        "data_seed": DATA_SEED, "key_seed": KEY_SEED,
    }
    for name, ctrl in controllers().items():
        for mode in MODES:
            res = run_monte_carlo(
                per_example_loss, jnp.zeros((D,)), data.X, data.y, n_workers=N,
                controller=ctrl, straggler=Exponential(rate=1.0), eta=ETA,
                num_iters=NUM_ITERS, keys=keys, eval_every=EVAL_EVERY, mode=mode,
            )
            for field in ("time", "loss", "k"):
                out[f"{name}__{mode}__{field}"] = np.asarray(getattr(res, field))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "quadratic_mc.npz")
    np.savez(path, **out)
    print(f"wrote {path}: {len(out)} arrays/scalars")


if __name__ == "__main__":
    main()
