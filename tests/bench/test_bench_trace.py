"""The trace reduction: busy union, idle share, top ops, collective time,
and idle gaps named by the host spans -- on hand-made events, and on a
small trace recorded on a TPU v5e (tests/bench/data/: three dispatches of a
jitted 200-step scan over an 8x128 block and a 128x128 product, inside
``bench.draw``/``dispatch``/``block``/``read`` spans; the recording script's
path in its metadata is replaced by a placeholder of the same length)."""

from __future__ import annotations

import glob
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_busy_is_the_union_of_overlapping_ops():
    devs = {"/device:TPU:0": [(0, 10, "a"), (5, 15, "b"), (20, 30, "a")]}
    spans = [(0, 40, "bench.dispatch")]
    s = trace.summarize(devs, spans)
    assert s.window_s == pytest.approx(40e-9)
    assert s.busy_s == pytest.approx(25e-9)
    assert s.idle_share == pytest.approx(15 / 40)
    # b starts inside a, so a's self time loses the overlap
    assert s.op_s == pytest.approx({"a": 15e-9, "b": 10e-9})


def test_nested_ops_count_their_self_time():
    # a loop (0-100) around two body ops; busy is the loop, ops are self time
    devs = {"/device:TPU:0": [(0, 100, "%while.1"), (10, 30, "%fusion.2"),
                              (50, 60, "%fusion.3")]}
    s = trace.summarize(devs, [(0, 120, "bench.dispatch")])
    assert s.busy_s == pytest.approx(100e-9)
    assert s.op_s == pytest.approx({"%while.1": 70e-9, "%fusion.2": 20e-9,
                                    "%fusion.3": 10e-9})
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)


def test_gaps_are_named_by_the_innermost_host_span():
    devs = {"/device:TPU:0": [(0, 10, "x"), (30, 40, "x"), (45, 50, "x")]}
    spans = [(0, 50, "bench.dispatch"), (10, 30, "bench.read"), (40, 44, "bench.draw")]
    s = trace.summarize(devs, spans)
    assert s.gaps[0] == ("bench.read", pytest.approx(20e-9))
    assert s.gaps[1] == ("bench.draw", pytest.approx(5e-9))
    bd = s.breakdown()
    assert bd["idle_gaps"][0][0] == "bench.read"
    assert bd["device_ops"] == [["x", pytest.approx(25e-9)]]


def test_ops_outside_the_window_are_clipped_and_devices_averaged():
    devs = {"/device:TPU:0": [(-10, 10, "x")], "/device:TPU:1": [(0, 20, "all-gather.3")]}
    s = trace.summarize(devs, [(0, 20, "bench.block")])
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(15e-9)  # (10 + 20) / 2
    assert s.collective_s == pytest.approx(10e-9)  # (0 + 20) / 2


def test_a_trace_with_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize({"/device:TPU:0": []}, [(0, 1, "bench.read")])


def _recorded():
    paths = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))
    assert paths, "no recorded trace under tests/bench/data"
    return paths[0]


def test_recorded_tpu_trace_reduces():
    path = _recorded()
    assert os.path.getsize(path) < 1_100_000
    s = trace.reduce_xplane(path)
    assert s.n_devices >= 1 and s.n_ops > 0
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert set(s.spans) >= {"bench.dispatch", "bench.block", "bench.read"}
    assert s.gaps and all(g[1] > 0 for g in s.gaps)
    bd = s.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    total = sum(v for v in s.op_s.values())
    assert total == pytest.approx(s.busy_s, rel=1e-6)  # self times tile busy
