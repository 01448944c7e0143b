"""BENCHMARK.json and the result line keep to the benchmark's contract, and
``bench/run.py`` prints no result where it cannot run."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import common, registry, run
from bench.trace import TraceSummary

ROOT = registry.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _b():
    return registry.benchmark()


def test_benchmark_json_has_exactly_the_contract_keys():
    b = _b()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert 1 <= len(b["paths"]) <= 16
    for p in b["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(b["command"]) <= 32
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in b["paths"])


def test_names_units_and_single_line_texts():
    b = _b()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[section]]
        assert len(names) == len(set(names))
        for e in b[section]:
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_configs_and_cells():
    b = _b()
    files = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg
        assert cfg["precision"]["dtype"] and cfg["precision"]["why"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    used = {w["config"] for w in b["workloads"]}
    assert used == {c["name"] for c in b["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in b["workloads"])
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        registry.traffic(w["traffic"])


def test_metrics_keys_bounds_and_moves():
    b = _b()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert {"setup_s", "cell_iters_per_s", "tokens_per_s", "step_ms_p90",
            "peak_hbm_gb"} >= set(e2e) >= {"setup_s"}
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25
    layers = {}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
        for w in m.get("workloads", []):
            assert w in [x["name"] for x in b["workloads"]]
            assert w in e2e[m["moves"]].get("workloads", [w])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _outcome(traced: bool):
    trace = TraceSummary(window_s=2.0, busy_s=1.5, n_devices=1, n_ops=10,
                         op_s={"fusion.1": 1.0, "dot.2": 0.5}, collective_s=0.0,
                         gaps=[("bench.read", 0.3), ("bench.draw", 0.2)],
                         spans={"bench.read": 0.3}) if traced else None
    return common.Outcome(
        metrics={"cell_iters_per_s": 123.5, "setup_s": 9.5, "tokens_per_s": 1.0,
                 "step_ms_p90": 2.0, "peak_hbm_gb": 3.0},
        attempted=4, failed=0,
        checks={"loss_gap": common.Check(1e-6, 1e-4)}, memory_peak_bytes=1234,
        layer={"first_call_s": 3.0, "serial_iters": 1000, "window_s": 2.0,
               "k_records": [[[10, 10]]], "eval_every": 10, "m": 20, "d": 2,
               "n_workers": 2, "chips": 1},
        trace=trace)


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_schema(traced):
    line = run.result_line("fig2-sync", _outcome(traced), [_Dev()], traced)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] == 4 and line["failed"] == 0
    dev = line["device"]
    assert dev == {**dev, "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 1234}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if traced:
        assert dev["busy_s"] == 1.5 and dev["window_s"] == 2.0
        assert set(line["metrics"]) >= {"setup.first_call_s", "sweep.idle_share",
                                        "sweep.device_us_per_iter", "sweep.mfu"}
        assert line["metrics"]["sweep.idle_share"]["value"] == pytest.approx(25.0)
        bd = line["breakdown"]
        assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
        assert bd["device_ops"][0] == ["fusion.1", 1.0]
    else:
        assert set(line["metrics"]) == {"cell_iters_per_s", "setup_s"}
        assert "breakdown" not in line
    assert line["checks"] == {"loss_gap": {"value": 1e-6, "limit": 1e-4}}
    json.dumps(line)


def test_a_check_over_its_limit_or_nan_is_not_correct():
    o = _outcome(False)
    o.checks["loss_gap"] = common.Check(2e-4, 1e-4)
    assert not o.correct
    o.checks["loss_gap"] = common.Check(float("nan"), 1e-4)
    assert not o.correct
    o.checks = {}
    assert not o.correct


def _bench_cmd(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig2-sync", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_with_no_result_off_the_tpu():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_in_a_lone_copy_of_the_benchmark(tmp_path):
    for path in _b()["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _bench_cmd(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_seed_key_takes_every_bit():
    import jax

    a = jax.random.key_data(common.seed_key(5))
    b = jax.random.key_data(common.seed_key(5 + 2 ** 32))
    assert (a != b).any()
    with pytest.raises(ValueError):
        common.seed_key(-1)
