"""Production mesh construction.

`make_production_mesh` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — smoke tests must keep
seeing 1 CPU device; only dryrun.py (which sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import)
sees the 512 placeholder devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``.  Explicit axes (the
    default) reject ``with_sharding_constraint`` on the activation layouts
    and the NamedSharding placement the sweep dispatch relies on; this repo
    pins layouts by constraint and lets XLA propagate the rest."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def sweep_mesh_shape(n_devices: int, n_cells: int, n_replicas: int) -> tuple[int, int]:
    """The (cells, replicas) mesh shape for a G-cell x R-replica sweep grid.

    Picks the largest divisor of ``n_devices`` that does not exceed
    ``n_cells`` for the cells axis and gives the rest to replicas — so a
    480-device slice dispatching the 15-cell x 32-replica baseline grid
    forms a (15, 32) mesh (every device busy), while a grid with more cells
    than devices degenerates to the historical all-cells 1-D layout
    (``(n_devices, 1)``).  Grids are padded up to mesh-shape multiples at
    dispatch (cells with inert empty rows, replicas by repeating a key);
    padded lanes are sliced off before results are returned, so any shape
    returned here is *correct* — the heuristic only decides utilization.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    if n_cells < 1 or n_replicas < 1:
        raise ValueError(
            f"grid must be non-empty, got n_cells={n_cells} n_replicas={n_replicas}"
        )
    mc = max(d for d in range(1, n_devices + 1) if n_devices % d == 0 and d <= n_cells)
    return mc, n_devices // mc


def make_sweep_mesh(
    n_cells: int, n_replicas: int, *, devices=None
) -> jax.sharding.Mesh:
    """2-D ``("cells", "replicas")`` mesh over GLOBAL devices for the sweep
    engine — spans processes whenever ``jax.distributed`` is initialized
    (``jax.devices()`` is the global list; single-process it equals
    ``jax.local_devices()`` and this degenerates to the historical local
    mesh).  Shape comes from ``sweep_mesh_shape``."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    mc, mr = sweep_mesh_shape(len(devices), n_cells, n_replicas)
    return _auto_mesh((mc, mr), ("cells", "replicas"), devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh() -> jax.sharding.Mesh:
    """Degenerate 1-device mesh with the production axis names — used by CPU
    integration tests so the same sharded code paths run unchanged."""
    return _auto_mesh((1, 1), ("data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> tuple:
    """The mesh axes that carry data parallelism (= the paper's n workers)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_workers(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n
