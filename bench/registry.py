"""Find the benchmark's parts by name.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its configuration
is ``bench/configs/<config>.json``, its traffic mix ``bench/traffic/<traffic>.json``
(whose ``entry`` names the driver), its driver ``bench/drivers/<entry>.py`` and
each per-layer metric ``bench/metrics/<name>.py``.  A later change adds any of
these as new files plus ``BENCHMARK.json`` entries; nothing here lists them.
An unknown name is an error, never a default.
"""

from __future__ import annotations

import importlib.util
import json
import os
import types
import zlib

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownName(LookupError):
    """A cell, configuration, traffic mix, driver, metric or device kind
    that has no file or entry."""


def _read_json(path: str, what: str, name: str) -> dict:
    if not os.path.isfile(path):
        raise UnknownName(f"no {what} named {name!r} ({path} does not exist)")
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"), "BENCHMARK.json", root)


def cell(name: str, root: str = ROOT) -> dict:
    """The ``workloads`` entry called ``name``."""
    for w in benchmark(root)["workloads"]:
        if w["name"] == name:
            return w
    raise UnknownName(f"no cell named {name!r} in BENCHMARK.json")


def config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _read_json(os.path.join(bench_dir, "configs", f"{name}.json"),
                      "configuration", name)


def traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _read_json(os.path.join(bench_dir, "traffic", f"{name}.json"),
                      "traffic mix", name)


def _module(path: str, what: str, name: str) -> types.ModuleType:
    if not os.path.isfile(path):
        raise UnknownName(f"no {what} named {name!r} ({path} does not exist)")
    spec = importlib.util.spec_from_file_location(
        f"bench_{what}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(entry: str, bench_dir: str = BENCH_DIR) -> types.ModuleType:
    """``bench/drivers/<entry>.py``: defines ``run(cell: CellRun) -> Outcome``."""
    return _module(os.path.join(bench_dir, "drivers", f"{entry}.py"), "driver", entry)


def metric(name: str, bench_dir: str = BENCH_DIR) -> types.ModuleType:
    """``bench/metrics/<name>.py``: defines ``read(ctx) -> float | None``."""
    return _module(os.path.join(bench_dir, "metrics", f"{name}.py"), "metric", name)


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    table = _read_json(os.path.join(bench_dir, "peaks.json"), "peaks table", "peaks")
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise UnknownName(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(known: {sorted(table['kinds'])})") from None


def metrics_for(cell_name: str, section: str, root: str = ROOT) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell_name`` reports:
    those whose ``workloads`` list names it, and those without the key."""
    return [m for m in benchmark(root)[section]
            if cell_name in m.get("workloads", [cell_name])]


def seed32(seed: int, salt: str = "") -> int:
    """A 32-bit number drawn from the whole seed (and a salt), for the
    generators that take no more bits than that."""
    return zlib.crc32(f"{salt}:{seed}".encode())
