"""What every driver shares: the run's description, its outcome, the clock,
host spans, seeded keys and the correctness checks' bookkeeping."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import sys
import time
from typing import Any

_IMPORT_WALL = time.time()


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``);
    the time this module was imported where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _IMPORT_WALL


@dataclasses.dataclass
class CellRun:
    """One run of one cell, as the harness hands it to a driver."""

    name: str
    cell: dict  # the BENCHMARK.json workloads entry
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list  # exactly the cell's chips
    peaks: dict
    start_wall: float  # process start, the origin of setup_s
    trace_dir: str


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # NaN compares False: not correct


@dataclasses.dataclass
class Outcome:
    """What a driver returns.  ``metrics`` are the end-to-end numbers it
    measured by the host clock; ``layer`` is what the per-layer readers need
    (work counts, records of the traced window); ``trace`` the reduction of
    the traced window (``bench.trace.TraceSummary``) in a traced run."""

    metrics: dict
    attempted: int
    failed: int
    checks: dict  # name -> Check
    memory_peak_bytes: int
    layer: dict = dataclasses.field(default_factory=dict)
    trace: Any = None

    @property
    def correct(self) -> bool:
        return self.failed == 0 and bool(self.checks) and all(
            c.ok for c in self.checks.values())


def span(name: str):
    """A host span in the profiler's trace (a no-op when nothing traces)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def seed_key(seed: int):
    """A PRNG key from every bit of ``seed`` (``PRNGKey`` alone keeps 32)."""
    import jax

    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


@contextlib.contextmanager
def traced(run: CellRun):
    """Profile the block when the run is traced; otherwise do nothing."""
    if not run.trace:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host spans and device ops, no Python calls
    opts.enable_hlo_proto = False
    with jax.profiler.trace(run.trace_dir, profiler_options=opts):
        yield


@contextlib.contextmanager
def settled():
    """Around the measured window: collect what set-up left behind first and
    exempt every object alive then from collections until the window ends,
    so that no full collection over set-up's objects lands in the window."""
    gc.collect()
    gc.freeze()
    try:
        yield
    finally:
        gc.unfreeze()


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def phase(run: CellRun, what: str) -> None:
    """Log how far into the process a set-up phase ended."""
    log(f"{time.time() - run.start_wall:8.2f} s  {what}")


def rel_gap(a, b):
    """Largest ``|a - b| / |b|`` (``b`` is the reference), elementwise over
    arrays."""
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        g = np.abs(a - b) / np.abs(b)
    g = np.where(np.isnan(g), np.inf, g)  # a NaN anywhere is no agreement
    return float(np.max(g)) if g.size else float("inf")
