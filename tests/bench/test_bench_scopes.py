"""The scoped trace reduction (bench/scopes.py): device time per named scope
and idle gaps named by the program's spans -- on hand-made events, and on a
small trace recorded on a TPU v5e (tests/bench/data/tpu_v5e_scoped.xplane.pb:
two dispatches of a jitted 50-step scan over an 8x128 block whose body runs
under ``repro.sampler`` (tanh), ``repro.ranks`` (a double argsort),
``repro.async_state`` with ``repro.grad`` nested in it (the gradient of a
128x128 product) and ``repro.update``, followed by a ``repro.eval`` mean;
each dispatch inside ``bench.draw``/``dispatch``/``block``/``read`` spans
with ``repro.sweep.run`` > ``repro.sweep.layout`` (3 ms of host sleep) and
``repro.sweep.call`` inside ``bench.dispatch``, and a last dispatch that
compiles a new program inside the window.  Recorded with the harness's
profiler options (``enable_hlo_proto`` off); the recording script's path in
its metadata is replaced by a placeholder of the same length)."""

from __future__ import annotations

import dataclasses
import os

import pytest

from bench import scopes, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "tpu_v5e_scoped.xplane.pb")
SMALL = os.path.join(DATA, "tpu_v5e_small.xplane.pb")
BASE_FIELDS = [f.name for f in dataclasses.fields(trace.TraceSummary)]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/while/body/repro.sampler/exp", "repro.sampler"),
    ("jit(f)/while/body/closed_call/repro.async_state/repro.grad/"
     "transpose(jvp())/dot_general:", "repro.grad"),
    ("jit(f)/repro.aggregate/repro.grad/vmap(jvp(mul))", "repro.grad"),
    ("jit(run_grid)/vmap()/while/body/add", None),
    ("", None),
    (None, None),
])
def test_scope_is_the_innermost_repro_component(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_program_id_of_a_module_event():
    assert scopes.program_id("jit_toy(1165186149177776038)") == 1165186149177776038
    assert scopes.program_id("jit_run_grid(7)") == 7
    assert scopes.program_id("no id here") is None


def test_nested_scopes_take_the_self_time_of_their_ops():
    # a loop (unscoped) around a sampler op and a grad op nested in an
    # async_state op: each scope keeps its ops' self time, the loop's own
    # time is unscoped, and the parts tile the busy time
    devs = {"/device:TPU:0": [(0, 100, "%while.1"), (10, 30, "%fusion.1"),
                              (40, 80, "%fusion.2"), (50, 70, "%fusion.3")]}
    tags = {"/device:TPU:0": [None, "repro.sampler", "repro.async_state", "repro.grad"]}
    s = scopes.summarize(devs, [(0, 120, "bench.dispatch")], tags)
    assert s.scope_s == pytest.approx({"repro.sampler": 20e-9,
                                       "repro.async_state": 20e-9,
                                       "repro.grad": 20e-9})
    assert s.unscoped_s == pytest.approx(40e-9)
    assert sum(s.scope_s.values()) + s.unscoped_s == pytest.approx(s.busy_s, abs=1e-15)
    assert s.breakdown()["scopes"][-1] == ["unscoped", pytest.approx(40e-9)]


def test_scopes_average_over_devices_and_clip_to_the_window():
    devs = {"/device:TPU:0": [(-10, 10, "a")], "/device:TPU:1": [(0, 20, "b")]}
    tags = {"/device:TPU:0": ["repro.grad"], "/device:TPU:1": ["repro.grad"]}
    s = scopes.summarize(devs, [(0, 20, "bench.block")], tags)
    assert s.scope_s == pytest.approx({"repro.grad": 15e-9})  # (10 + 20) / 2
    assert s.unscoped_s == 0


def test_gaps_take_the_innermost_program_span_and_keep_the_window():
    devs = {"/device:TPU:0": [(0, 10, "x"), (30, 40, "x"), (60, 70, "x")]}
    bench = [(0, 70, "bench.dispatch")]
    program = [(5, 45, "repro.sweep.run"), (10, 30, "repro.sweep.layout"),
               (40, 60, "backend_compile_and_load"),
               (-50, 200, "repro.sweep.run")]  # outside the window: cut to it
    s = scopes.summarize(devs, bench + program)
    plain = trace.summarize(devs, bench)
    assert s.window_s == plain.window_s and s.busy_s == plain.busy_s
    assert s.gaps == [("repro.sweep.layout", pytest.approx(20e-9)),
                      ("backend_compile_and_load", pytest.approx(20e-9))]
    assert plain.gaps[0][0] == "bench.dispatch"
    assert s.unscoped_s == pytest.approx(s.busy_s)  # no scope map: all unscoped


def test_ops_are_looked_up_in_their_own_program():
    # the same instruction text in two programs, with different scopes
    devs = {"/device:TPU:0": [(0, 10, "%fusion.1 = f32[8]"), (20, 30, "%fusion.1 = f32[8]")]}
    mods = {"/device:TPU:0": [(0, 10, "jit_a(1)"), (20, 30, "jit_b(2)")]}
    names = {"/device:TPU:0": {(1, "%fusion.1 = f32[8]"): "jit(a)/repro.grad/dot",
                               (2, "%fusion.1 = f32[8]"): "jit(b)/repro.eval/dot"}}
    assert scopes.scopes_of(devs, mods, names) == {"/device:TPU:0": ["repro.grad", "repro.eval"]}


def _same_base(a, b):
    return all(getattr(a, f) == getattr(b, f) for f in BASE_FIELDS if f not in ("gaps", "spans"))


@pytest.mark.parametrize("path", [SMALL, SCOPED])
def test_recorded_traces_keep_the_accepted_reduction_bit_for_bit(path):
    s, base = scopes.reduce_xplane(path), trace.reduce_xplane(path)
    assert _same_base(s, base)
    assert s.idle_share == base.idle_share
    assert {n: v for n, v in s.spans.items() if n.startswith("bench.")} == base.spans
    assert sum(s.scope_s.values()) + s.unscoped_s == pytest.approx(s.busy_s, rel=1e-6)


def test_the_unscoped_recording_reads_no_scope():
    s = scopes.reduce_xplane(SMALL)
    assert s.scope_s == {}
    assert s.unscoped_s == pytest.approx(s.busy_s, rel=1e-6)


def test_recorded_scoped_trace_names_its_parts():
    assert os.path.getsize(SCOPED) < 1_100_000
    s = scopes.reduce_xplane(SCOPED)
    assert set(s.scope_s) == {"repro.sampler", "repro.ranks", "repro.grad",
                              "repro.update", "repro.eval"}
    assert all(v > 0 for v in s.scope_s.values())
    # the double argsort outweighs the 8x128 tanh and the 128x128 products
    assert max(s.scope_s, key=s.scope_s.get) == "repro.ranks"
    assert 0 < s.unscoped_s < s.busy_s
    assert sum(s.scope_s.values()) + s.unscoped_s == pytest.approx(s.busy_s, rel=1e-6)
    assert {"repro.sweep.run", "repro.sweep.layout", "repro.sweep.call",
            "backend_compile_and_load"} <= set(s.spans)
    named = [n for n, _ in s.gaps]
    assert "repro.sweep.layout" in named  # the host sleep inside the program span
    assert "backend_compile_and_load" in named  # the compile inside the window
    assert s.gaps[0][0] == "backend_compile_and_load"
    assert ["unscoped", pytest.approx(s.unscoped_s)] in s.breakdown()["scopes"]


def test_nested_scope_in_the_recording_goes_to_the_inner_one():
    with open(SCOPED, "rb") as f:
        names = scopes.op_names(f.read())
    op_names = [v for plane in names.values() for v in plane.values()]
    nested = [v for v in op_names if "repro.async_state/repro.grad" in v]
    assert nested and {scopes.scope_of(v) for v in nested} == {"repro.grad"}
