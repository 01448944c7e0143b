"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time (the union of the intervals in which an
operation ran), the traced window, the operations that took the most time,
collective time, and the longest idle gaps named by what the host was doing
(the harness's own ``bench.*`` spans).

Read with ``jax.profiler.ProfileData``; nothing else is needed.  The device
planes are those named ``/device:<platform>:<n>``; operations are the events
of their ``XLA Ops`` line.  Those nest: a ``while`` loop's event spans every
operation of its body.  Busy time is the union of all of them, so the time
between the body's operations counts as busy (the device is running the
loop) and only the time in which no program runs counts as idle.  The time
per operation is its self time: its span less that of the operations nested
in it.  Host spans are events whose name starts with ``bench.`` on any other
plane.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"\bsend\b|\brecv\b|send-done|recv-done|all-gather-done|all-reduce-done",
    re.IGNORECASE)
TOP = 10
NAME_CHARS = 80


@dataclasses.dataclass
class TraceSummary:
    window_s: float  # length of the traced window (host spans, else device ops)
    busy_s: float  # union of op intervals in the window, averaged over devices
    n_devices: int
    n_ops: int
    op_s: dict  # op name -> seconds, summed over devices / n_devices
    collective_s: float  # per device, averaged
    gaps: list  # the TOP longest idle gaps of the first device: [(host span, s)]
    spans: dict  # host span name -> total seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_xplane(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = {}  # plane name -> [(start, end, name)]
    spans = []  # (start, end, name)
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((s, s + float(ev.duration_ns), ev.name))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns), ev.name))
    return summarize(devices, spans)


def summarize(devices: dict, spans: list) -> TraceSummary:
    """The reduction proper, over plain ``(start_ns, end_ns, name)`` tuples:
    ``devices`` maps each device to its op events, ``spans`` are host spans."""
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        raise ValueError("the trace holds no device operations")
    if spans:
        lo = min(s for s, _, _ in spans)
        hi = max(e for _, e, _ in spans)
    else:
        lo = min(s for evs in devices.values() for s, _, _ in evs)
        hi = max(e for evs in devices.values() for _, e, _ in evs)
    window_ns = hi - lo
    if window_ns <= 0:
        raise ValueError("the traced window is empty")
    nd = len(devices)
    busy = 0.0
    coll = 0.0
    op_s: dict = {}
    n_ops = 0
    first = sorted(devices)[0]
    gaps = []
    for dev, evs in sorted(devices.items()):
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in evs if e > lo and s < hi]
        n_ops += len(clipped)
        merged = _union((s, e) for s, e, _ in clipped)
        busy += sum(e - s for s, e in merged)
        coll += sum(e - s for s, e in _union(
            (s, e) for s, e, n in clipped if _COLLECTIVE.search(n)))
        for n, t in _self_times(clipped):
            n = n[:NAME_CHARS]
            op_s[n] = op_s.get(n, 0.0) + t * 1e-9 / nd
        if dev == first:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            holes = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                            if edges[i + 1] > edges[i]), key=lambda h: h[0] - h[1])
            gaps = [(_host_doing(spans, a, b), (b - a) * 1e-9) for a, b in holes[:TOP]]
    span_s: dict = {}
    for s, e, n in spans:
        span_s[n] = span_s.get(n, 0.0) + (e - s) * 1e-9
    return TraceSummary(
        window_s=window_ns * 1e-9, busy_s=busy * 1e-9 / nd, n_devices=nd,
        n_ops=n_ops, op_s=op_s, collective_s=coll * 1e-9 / nd, gaps=gaps,
        spans=span_s)


def _self_times(events):
    """(name, self time) of each (start, end, name) event: its span less the
    spans of the events directly nested in it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    self_t = [e - s for s, e, _ in events]
    stack = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= min(e, events[stack[-1]][1]) - s
        stack.append(i)
    return [(events[i][2], self_t[i]) for i in range(len(events))]


def _host_doing(spans, a, b) -> str:
    """The innermost host span running at the middle of [a, b)."""
    mid = 0.5 * (a + b)
    inside = [(e - s, n) for s, e, n in spans if s <= mid < e]
    return min(inside)[1] if inside else "host (no span)"


def reduce_dir(trace_dir: str) -> TraceSummary:
    return reduce_xplane(find_xplane(trace_dir))
