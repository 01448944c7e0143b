"""The sweep's flat lane layout, built on the host.

``run_sweep_source`` lays the padded (Gp, Rp) grid out as one cell-major
lane axis.  ``_lane_layout`` builds every lane leaf with NumPy and returns
one composed replica-key index; these tests pin it to the device path it
replaced (a ``jnp.asarray(a)[cell_idx]`` gather per padded leaf and the
two-step ``keys[pad][rep_idx]`` key gather), bitwise and dtype for dtype,
over unpadded, cell-padded and replica-padded grids (one lane per device
included) and over hetero, faulty and sketched cells.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import (
    FixedKController,
    PflugController,
    SketchedPflugController,
)
from repro.core.faults import byzantine_plan
from repro.core.straggler import (
    Exponential,
    Pareto,
    RateSchedule,
    ShiftedExponential,
    WorkerFleet,
)
from repro.core.sweep import SweepCase, _cell_of, _lane_layout

N = 6
PARAMS = {"w": np.zeros((5,), np.float32), "b": np.zeros((2, 3), np.float32)}


def _plain(g):
    return SweepCase(FixedKController(n_workers=N, k=1 + g % N), Exponential(1.0),
                     eta=0.1 * (g + 1), label=f"fixed{g}")


def _hetero(g):
    fleet = WorkerFleet(
        models=(Exponential(1.3), Pareto(x_m=0.5, alpha=1.5),
                ShiftedExponential(shift=0.7, rate=2.0), Exponential(0.4)),
        schedule=RateSchedule(times=(5.0, 9.0), scales=(0.5, 2.0)),
    )
    return SweepCase(PflugController(n_workers=4, k0=2, k_max=3), fleet,
                     eta=0.05, label=f"hetero{g}")


def _faulty(g):
    return SweepCase(FixedKController(n_workers=N, k=4), Exponential(1.0), eta=0.05,
                     fault=byzantine_plan(N, 0.5, "crash", onset=1.0 + g),
                     agg="median", label=f"faulty{g}")


def _sketched(g):
    return SweepCase(SketchedPflugController(n_workers=N, k0=1, sketch_dim=4,
                                             seed=17 + g),
                     Exponential(1.0), eta=0.05, label=f"sketched{g}")


CELLS = {"plain": _plain, "hetero": _hetero, "faulty": _faulty, "sketched": _sketched}

# (G, R, mc, mr): no padding, cell padding, replica padding, both, and grids
# of one lane per device (the replicas pad once more).
GRIDS = {
    "unpadded": (5, 4, 1, 1),
    "unpadded_mesh": (4, 6, 2, 3),
    "cells_padded": (5, 4, 4, 1),
    "replicas_padded": (2, 3, 1, 2),
    "both_padded": (3, 5, 2, 2),
    "one_lane": (1, 1, 1, 1),
    "one_lane_per_device": (2, 2, 2, 2),
    "one_lane_cells_padded": (3, 1, 4, 1),
}


def _stacked(kind, G):
    make = CELLS[kind]
    cases = [make(g) if g % 2 == 0 else _plain(g) for g in range(G)]
    cells = [_cell_of(c, N, 1, 2, 4, PARAMS) for c in cases]
    return jax.tree.map(lambda *xs: np.stack(xs), *cells)


def _device_layout(stacked, keys, G, R, mc, mr):
    """The device path run_sweep_source used to take."""
    Gp, Rp = G + (-G) % mc, R + (-R) % mr
    if Gp * Rp == mc * mr:
        Rp += mr
    padded_cells = jax.tree.map(
        lambda a: np.concatenate(
            [np.asarray(a), np.zeros((Gp - G,) + a.shape[1:], a.dtype)]
        )
        if Gp > G
        else np.asarray(a),
        stacked,
    )
    padded_keys = (
        keys[np.concatenate([np.arange(R), np.zeros(Rp - R, np.int64)])]
        if Rp > R
        else keys
    )
    cell_idx = np.repeat(np.arange(Gp), Rp)
    rep_idx = np.tile(np.arange(Rp), Gp)
    flat_cells = jax.tree.map(lambda a: jnp.asarray(a)[cell_idx], padded_cells)
    return flat_cells, padded_keys[rep_idx], Gp, Rp


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("grid", sorted(GRIDS))
def test_lane_layout_matches_device_gathers(kind, grid):
    G, R, mc, mr = GRIDS[grid]
    stacked = _stacked(kind, G)
    keys = jax.random.split(jax.random.PRNGKey(3), R)
    flat, key_index, Gp, Rp = _lane_layout(stacked, G, R, mc, mr)
    ref_flat, ref_keys, ref_Gp, ref_Rp = _device_layout(stacked, keys, G, R, mc, mr)

    assert (Gp, Rp) == (ref_Gp, ref_Rp)
    assert Gp % mc == 0 and Rp % mr == 0 and Gp * Rp >= 2 * mc * mr
    assert jax.tree.structure(flat) == jax.tree.structure(ref_flat)
    for path, a in jax.tree_util.tree_leaves_with_path(flat):
        assert isinstance(a, np.ndarray), jax.tree_util.keystr(path)
    for a, ref in zip(jax.tree.leaves(jax.device_put(flat)), jax.tree.leaves(ref_flat)):
        assert a.dtype == ref.dtype and a.shape == ref.shape == (Gp * Rp,) + a.shape[1:]
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(ref).view(np.uint8)
        )
    np.testing.assert_array_equal(np.asarray(keys[key_index]), np.asarray(ref_keys))


def test_lane_layout_pads_inert_cells_and_key_zero():
    G, R, mc, mr = GRIDS["both_padded"]
    stacked = _stacked("hetero", G)
    flat, key_index, Gp, Rp = _lane_layout(stacked, G, R, mc, mr)
    lanes = np.arange(Gp * Rp)
    pad_cell = lanes // Rp >= G
    for a in jax.tree.leaves(flat):
        assert not a[pad_cell].any()
    np.testing.assert_array_equal(
        key_index, np.where(lanes % Rp < R, lanes % Rp, 0)
    )
