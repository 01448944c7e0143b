"""The trace reduction of ``bench/trace.py`` with the program's own names:
device time per named scope, and idle gaps named by the program's host spans.

The program runs each part of its step under a ``jax.named_scope``
(``repro.sampler``, ``repro.ranks``, ``repro.grad``, ``repro.aggregate``,
``repro.update``, ``repro.controller``, ``repro.eval``,
``repro.async_state``) and wraps the host work of a sweep dispatch in
``repro.sweep.*`` spans.  A device op event's name is its HLO instruction
text; its scope is in the op's ``op_name``, which a TPU trace keeps as the
``tf_op`` stat of the op's event metadata, beside its ``program_id``
(``enable_hlo_proto`` is not needed).  ``jax.profiler.ProfileData`` does not
expose event metadata, so a small protobuf walker (``_fields``) reads it
from the file.  Each op is placed in the ``XLA Modules`` event that
contains it (named ``jit_f(<program id>)``), and each distinct (program,
instruction) is looked up once.

``reduce_xplane`` returns a ``ScopedSummary``: every field of
``trace.TraceSummary`` exactly as ``trace.reduce_xplane`` computes it
(window from the ``bench.`` spans alone), plus

* ``scope_s``: self seconds of the ops whose ``op_name`` holds a
  ``repro.<part>`` component, keyed by the innermost one, averaged over
  devices like ``op_s``;
* ``unscoped_s``: the self seconds of every other op, so that
  ``sum(scope_s) + unscoped_s == busy_s`` where ops nest;
* ``gaps`` named by the innermost host span at each gap's middle, among the
  ``bench.`` spans, the program's ``repro.`` spans and jax's own compile
  annotations (``backend_compile*``).
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from bench import trace

PROGRAM_PREFIX = "repro."
COMPILE_PREFIX = "backend_compile"
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
OP_NAME_STAT = "tf_op"
PROGRAM_STAT = "program_id"
_SCOPE = re.compile(r"\brepro\.[A-Za-z_]+")


@dataclasses.dataclass
class ScopedSummary(trace.TraceSummary):
    scope_s: dict = dataclasses.field(default_factory=dict)  # repro.* scope -> s
    unscoped_s: float = 0.0  # self time of ops in no repro.* scope

    def breakdown(self) -> dict:
        out = super().breakdown()
        scopes = sorted(self.scope_s.items(), key=lambda kv: -kv[1])
        out["scopes"] = [[n, s] for n, s in scopes] + [[UNSCOPED, self.unscoped_s]]
        return out


def scope_of(op_name: str):
    """The innermost ``repro.<part>`` component of an ``op_name``, or None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def program_id(module_event_name: str):
    """The program id of an ``XLA Modules`` event named ``jit_f(<id>)``."""
    head, _, tail = module_event_name.rpartition("(")
    try:
        return int(tail.rstrip(")")) if head else None
    except ValueError:
        return None


# --- the protobuf walker: xplane.proto and hlo.proto field numbers.

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one serialized message:
    varints as ints, length-delimited fields as memoryviews."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _first(buf, number):
    for f, v in _fields(buf):
        if f == number:
            return v
    return None


def op_names(xspace: bytes) -> dict:
    """{device plane: {(program id, op event name): op_name}} from the
    event metadata of each device plane of an ``XSpace`` (XSpace planes 1;
    XPlane name 2, event_metadata 4, stat_metadata 5; XEventMetadata name 2,
    stats 5; XStatMetadata name 2; XStat metadata_id 1, uint64_value 3,
    int64_value 4, str_value 5, ref_value 7)."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        plane_name = bytes(_first(plane, 2) or b"").decode()
        if not trace._DEVICE_PLANE.match(plane_name):
            continue
        stat_names, metas = {}, []
        for g, entry in _fields(plane):
            if g == 5:
                meta = _first(entry, 2)
                stat_names[_first(entry, 1)] = bytes(_first(meta, 2) or b"").decode()
            elif g == 4:
                metas.append(_first(entry, 2))
        found = {}
        for meta in metas:
            name, stats = None, {}
            for h, v in _fields(meta):
                if h == 2:
                    name = bytes(v).decode()
                elif h == 5:
                    stat = dict(_fields(v))
                    key = stat_names.get(stat.get(1))
                    if key == OP_NAME_STAT:
                        stats[key] = (bytes(stat[5]).decode() if 5 in stat
                                      else stat_names.get(stat.get(7), ""))
                    elif key == PROGRAM_STAT:
                        stats[key] = stat.get(3, stat.get(4))
            if name is not None and stats.get(OP_NAME_STAT):
                found[(stats.get(PROGRAM_STAT), name)] = stats[OP_NAME_STAT]
        out[plane_name] = found
    return out


# --- the reduction.

def reduce_xplane(path: str) -> ScopedSummary:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    devices, modules, spans = {}, {}, []
    for plane in pd.planes:
        if trace._DEVICE_PLANE.match(plane.name):
            evs = devices.setdefault(plane.name, [])
            mods = modules.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    for ev in line.events:
                        s = float(ev.start_ns)
                        evs.append((s, s + float(ev.duration_ns), ev.name))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        s = float(ev.start_ns)
                        mods.append((s, s + float(ev.duration_ns), ev.name))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((trace.SPAN_PREFIX, PROGRAM_PREFIX, COMPILE_PREFIX)):
                        s = float(ev.start_ns)
                        spans.append((s, s + float(ev.duration_ns), ev.name))
    return summarize(devices, spans, scopes_of(devices, modules, op_names(raw)))


def scopes_of(devices: dict, modules: dict, names: dict) -> dict:
    """{device: [scope or None per op event]}: each op's program is that of
    the ``XLA Modules`` event containing its start; each distinct (program,
    op) is looked up in ``names`` (``op_names``' map) once."""
    out = {}
    for dev, evs in devices.items():
        mods = sorted(modules.get(dev, []))
        starts = [m[0] for m in mods]
        known = names.get(dev, {})
        memo = {}
        scopes = []
        for s, _, name in evs:
            j = bisect.bisect_right(starts, s) - 1
            pid = program_id(mods[j][2]) if j >= 0 and s < mods[j][1] else None
            key = (pid, name)
            if key not in memo:
                memo[key] = scope_of(known.get(key))
            scopes.append(memo[key])
        out[dev] = scopes
    return out


def summarize(devices: dict, spans: list, scopes: dict | None = None) -> ScopedSummary:
    """Over plain tuples: ``devices`` maps each device to its ``(start_ns,
    end_ns, name)`` op events, ``spans`` are ``(start_ns, end_ns, name)``
    host spans of every prefix, ``scopes`` maps each device to the scope of
    each of its ops (None: unscoped; no map: all unscoped)."""
    bench = [sp for sp in spans if sp[2].startswith(trace.SPAN_PREFIX)]
    if bench:
        lo, hi = min(s for s, _, _ in bench), max(e for _, e, _ in bench)
        # Program and compile spans, cut to the bench. window, leave the
        # window as it was and name the gaps they cover.
        bench += [(max(s, lo), min(e, hi), n) for s, e, n in spans
                  if not n.startswith(trace.SPAN_PREFIX) and e > lo and s < hi]
    base = trace.summarize(devices, bench)
    if not bench:
        lo = min(s for evs in devices.values() for s, _, _ in evs)
        hi = max(e for evs in devices.values() for _, e, _ in evs)
    live = {k: v for k, v in devices.items() if v}
    nd = len(live)
    scope_s: dict = {}
    unscoped = 0.0
    for dev, evs in sorted(live.items()):
        tags = (scopes or {}).get(dev) or [None] * len(evs)
        clipped = [(max(s, lo), min(e, hi), tag) for (s, e, _), tag in zip(evs, tags)
                   if e > lo and s < hi]
        for tag, t in trace._self_times(clipped):
            if tag is None:
                unscoped += t * 1e-9 / nd
            else:
                scope_s[tag] = scope_s.get(tag, 0.0) + t * 1e-9 / nd
    fields = {f.name: getattr(base, f.name) for f in dataclasses.fields(base)}
    return ScopedSummary(**fields, scope_s=scope_s, unscoped_s=unscoped)


def reduce_dir(trace_dir: str) -> ScopedSummary:
    return reduce_xplane(trace.find_xplane(trace_dir))
