"""Faults planted in the program's timed path, to show that the comparison
with the reference catches them.  Each is a context manager that patches
the program (``repro``) for its duration; the tests run a cell under each
at a small size, and ``bench/calibrate.py --fault-seeds`` reads them on the chip
at the cell's own size.

* ``state_unchanged``: the update returns the weights it was given (a
  sweep lane's SGD step), or the train step returns the whole state it was
  given (weights, optimizer, controller and clock);
* ``half_batch``: half of the rows are left out of the gradient and the mean
  is taken over the rest;
* ``answer_altered``: what the step reports (the sweep's loss records, the
  train step's loss) is off by a small amount where it is produced;
* ``exchange_left_out`` (sweeps on several chips): the lanes held by every
  chip but the first never reach the host; the first chip's take their place.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _fresh_sweep_programs():
    from repro.core import sweep

    sweep.clear_sweep_cache()


@contextlib.contextmanager
def _sweep(patch):
    _fresh_sweep_programs()
    try:
        with patch:
            yield
    finally:
        _fresh_sweep_programs()


def sweep_state_unchanged():
    from repro.core import execmode

    return _sweep(_patched(execmode, "sgd_update", lambda params, g, eta: params))


def sweep_half_batch():
    import jax.numpy as jnp

    from repro.core import aggregation

    def half(losses, mask, k, s):
        h = s // 2
        shard = losses.reshape(-1, s)[:, :h].sum(axis=1)
        return jnp.dot(shard, mask) * (1.0 / (k.astype(losses.dtype) * h))

    return _sweep(_patched(aggregation, "fastest_k_weighted_loss", half))


def sweep_answer_altered():
    from repro.core import sweep

    real = sweep.run_sweep

    def altered(*a, **kw):
        res = real(*a, **kw)
        return res._replace(loss=res.loss.at[..., -1].multiply(1.001))

    return _sweep(_patched(sweep, "run_sweep", altered))


def sweep_exchange_left_out():
    from repro.core import sweep

    real = sweep.run_sweep

    def lost(*a, **kw):
        res = real(*a, **kw)
        held = -(-len(kw["cases"]) // kw["mesh"].shape["cells"])  # cells per chip
        first = lambda x: x[np.arange(x.shape[0]) % held]  # noqa: E731
        return res._replace(time=first(res.time), loss=first(res.loss), k=first(res.k))

    return _sweep(_patched(sweep, "run_sweep", lost))


def train_state_unchanged():
    from repro.launch import steps

    real = steps.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def unchanged(state, batch, key):
            _, metrics = step(state, batch, key)
            return state, metrics

        return unchanged

    return _patched(steps, "make_train_step", make)


def train_half_batch():
    import jax.numpy as jnp

    from repro.core import aggregation

    real = aggregation.per_example_weights

    def half(mask, k, s):
        w = real(mask, k, s)
        keep = (jnp.arange(w.shape[0]) % 2 == 0).astype(w.dtype)
        return w * keep * 2.0

    return _patched(aggregation, "per_example_weights", half)


def train_answer_altered():
    from repro.launch import steps

    real = steps.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def altered(state, batch, key):
            state, metrics = step(state, batch, key)
            return state, {**metrics, "ce": metrics["ce"] * 1.01}

        return altered

    return _patched(steps, "make_train_step", make)


FAULTS = {
    "sweep": {"state_unchanged": sweep_state_unchanged,
              "half_batch": sweep_half_batch,
              "answer_altered": sweep_answer_altered,
              "exchange_left_out": sweep_exchange_left_out},
    "train": {"state_unchanged": train_state_unchanged,
              "half_batch": train_half_batch,
              "answer_altered": train_answer_altered},
}


def for_cell(entry: str, chips: int) -> dict:
    """The faults a cell of this entry kind on this many chips can have."""
    out = dict(FAULTS[entry])
    if chips == 1:
        out.pop("exchange_left_out", None)
    return out
