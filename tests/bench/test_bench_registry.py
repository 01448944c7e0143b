"""The harness finds every part by name, and nothing else."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from bench import registry

ROOT = registry.ROOT


def test_every_cell_of_benchmark_json_resolves():
    b = registry.benchmark()
    for w in b["workloads"]:
        cell = registry.cell(w["name"])
        cfg = registry.config(cell["config"])
        tr = registry.traffic(cell["traffic"])
        assert cfg["name"] == cell["config"]
        assert hasattr(registry.driver(tr["entry"]), "run")
        assert hasattr(registry.driver(tr["entry"]), "control")
    for m in b["per_layer"]:
        assert callable(registry.metric(m["name"]).read)


@pytest.mark.parametrize("lookup", [
    lambda: registry.cell("no-such-cell"),
    lambda: registry.config("no-such-config"),
    lambda: registry.traffic("no-such-traffic"),
    lambda: registry.driver("no_such_entry"),
    lambda: registry.metric("no.such_metric"),
    lambda: registry.peaks("TPU v99 imaginary"),
])
def test_an_unknown_name_is_an_error(lookup):
    with pytest.raises(registry.UnknownName):
        lookup()


def test_peaks_are_keyed_by_device_kind_and_name_a_source():
    with open(os.path.join(registry.BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    assert table["source"]
    v5e = registry.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_a_new_cell_config_entry_and_metric_are_new_files_only(tmp_path):
    """A throwaway cell, configuration, entry kind and per-layer metric,
    added as files plus BENCHMARK.json entries, are found with no edit to
    any file the benchmark already has."""
    root = tmp_path
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = registry.benchmark()
    before = {p: open(os.path.join(registry.BENCH_DIR, p), "rb").read()
              for p in ("registry.py", "common.py", "trace.py")}
    (root / "bench" / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "precision": {"matmul": "highest"}, "reduced": [], "assumed": []}))
    (root / "bench" / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"entry": "toy_entry", "size": 3}))
    (root / "bench" / "drivers" / "toy_entry.py").write_text(
        "def run(c):\n    return c.traffic['size']\n\n\ndef control(c, p):\n    return {}\n")
    (root / "bench" / "metrics" / "toy.count.py").write_text(
        "def read(ctx):\n    return ctx['layer']['count']\n")
    b["workloads"].append({"name": "toy-cell", "config": "toy", "traffic": "toy-mix",
                           "chips": 1, "why": "throwaway"})
    b["end_to_end"].append({"name": "toy_rate", "unit": "1/s", "better": "higher",
                            "bound": 0.05, "source": "host_clock", "workloads": ["toy-cell"]})
    b["per_layer"].append({"name": "toy.count", "unit": "1", "better": "higher",
                           "source": "program_counter", "layer": "toy",
                           "moves": "toy_rate", "workloads": ["toy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    bench_dir = str(root / "bench")
    cell = registry.cell("toy-cell", str(root))
    assert registry.config(cell["config"], bench_dir)["name"] == "toy"
    tr = registry.traffic(cell["traffic"], bench_dir)
    assert registry.driver(tr["entry"], bench_dir).run(
        type("C", (), {"traffic": tr})()) == 3
    assert registry.metric("toy.count", bench_dir).read({"layer": {"count": 7}}) == 7
    names = [m["name"] for m in registry.metrics_for("toy-cell", "per_layer", str(root))]
    assert names == ["setup.first_call_s", "toy.count"]
    e2e = [m["name"] for m in registry.metrics_for("toy-cell", "end_to_end", str(root))]
    assert e2e == ["setup_s", "toy_rate"]
    for p, data in before.items():
        assert open(os.path.join(registry.BENCH_DIR, p), "rb").read() == data


def test_metrics_for_follows_the_workloads_key():
    for w in registry.benchmark()["workloads"]:
        e2e = {m["name"] for m in registry.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = registry.metrics_for(w["name"], "per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)


def test_seed32_uses_every_bit_of_the_seed():
    assert registry.seed32(5) != registry.seed32(5 + 2 ** 32)
    assert registry.seed32(5, "a") != registry.seed32(5, "b")
    assert registry.seed32(2 ** 31 + 17) == registry.seed32(2 ** 31 + 17)
