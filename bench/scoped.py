"""The program's own names in a profile: device scopes and host spans.

The FFT program names its work (``repro.core.scopes``): each superstep
runs under ``fft.step<i>`` and its work under one leaf (``fft.pass``,
``fft.relayout``, ``fft.swap``, ``fft.combine``); the facade's work
around the supersteps runs under ``fft.boundary``; each plan call is a
host span ``fft.apply.fwd``/``fft.apply.inv``. The scopes reach the
device trace as each op's ``tf_op`` stat, its name stack, which is held
in the op's event metadata. ``jax.profiler.ProfileData`` does not expose
event metadata, so :func:`from_xplane` reads the ``.xplane.pb`` with a
small reader of the protobuf wire format, for the few fields of
XSpace/XPlane/XLine/XEvent/XEventMetadata/XStat it needs.

The result is a :class:`bench.trace.Trace` whose device ops are
:class:`ScopedOp` and whose host spans also hold the program's, so every
reduction of :mod:`bench.trace` works on it unchanged; idle gaps are then
named by the innermost program span open on the host. The functions
below reduce it by scope, in ms per step on the busiest device; each
returns ``None`` when the trace holds no program scope (a profile of a
program that names nothing, or a trace read by :mod:`bench.trace`).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import trace as tracelib

#: host spans kept: the benchmark's, and the program's ``fft.*``
HOST_SPANS = re.compile(tracelib.HOST_SPANS.pattern + r'|^fft\..+$')
#: leaf scope -> the part of the work it names
LEAVES = {'fft.pass': 'pass', 'fft.relayout': 'relayout',
          'fft.swap': 'swap', 'fft.combine': 'combine'}
PARTS = ('boundary', 'pass', 'relayout', 'swap', 'combine', 'unscoped')
STEP = re.compile(r'^fft\.step(\d+)$')
#: a name stack that is a bare name (``x``): XLA names the ops it makes
#: from a program argument after the argument alone
ARGUMENT = re.compile(r'^[A-Za-z_]\w*$')
#: a complex value in an op's HLO text
COMPLEX = re.compile(r'\bc(64|128)\[')
#: the fusion XLA makes of a concatenation written in place, chunk by
#: chunk: named after its root, and without the concatenation's name
INPLACE_JOIN = re.compile(r'^%\S*dynamic-update-slice_fusion')
APPLY = re.compile(r'^fft\.apply\.')


@dataclasses.dataclass
class ScopedOp(tracelib.Op):
    scope: str = ''    # the op's tf_op: its name stack, '' if it has none
    part: str = 'unscoped'     # what it does: one of PARTS (see part())


# -- the protobuf wire format ---------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one message: an int for a
    varint, bytes for a length-delimited field, raw bytes for fixed
    widths."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f'unsupported protobuf wire type {wire}')
        yield key >> 3, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class _Event:
    name: str
    start_ns: float
    duration_ns: float
    tf_op: str = ''


@dataclasses.dataclass
class _Line:
    name: str
    events: List[_Event]


@dataclasses.dataclass
class _Plane:
    name: str
    lines: List[_Line]


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b''
    for f, v in _fields(buf):
        if f == 1:
            key = _signed(v)
        elif f == 2:
            value = v
    return key, value


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, str]:
    """(name, text) of an XStat: metadata_id 1, and a text value as str
    5 or as ref 7 (the name of a stat metadata); '' for other values."""
    name, value = '', ''
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(_signed(v), '')
        elif f == 5:
            value = v.decode('utf-8', 'replace')
        elif f == 7:
            value = stat_names.get(_signed(v), '')
    return name, value


def _plane(buf: bytes) -> _Plane:
    """An XPlane: name 2, lines 3, event_metadata 4 (id -> name 2, stats
    5), stat_metadata 5 (id -> name 2). An XLine: name 2, timestamp_ns 3,
    events 4; an XEvent: metadata_id 1, offset_ps 2, duration_ps 3."""
    name, raw_lines, raw_events, stat_names = '', [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 3:
            raw_lines.append(v)
        elif f == 4:
            key, value = _map_entry(v)
            raw_events[key] = value
        elif f == 5:
            key, value = _map_entry(v)
            stat_names[key] = next((w.decode() for g, w in _fields(value)
                                    if g == 2), '')
    meta: Dict[int, Tuple[str, str]] = {}
    for key, value in raw_events.items():
        ev_name, tf_op = '', ''
        for f, v in _fields(value):
            if f == 2:
                ev_name = v.decode('utf-8', 'replace')
            elif f == 5:
                stat, val = _stat(v, stat_names)
                if stat == 'tf_op':
                    tf_op = val
        meta[key] = (ev_name, tf_op)
    lines = []
    for raw in raw_lines:
        line_name, ts, events = '', 0, []
        for f, v in _fields(raw):
            if f == 2:
                line_name = v.decode()
            elif f == 3:
                ts = _signed(v)
            elif f == 4:
                mid = offset = dur = 0
                for g, w in _fields(v):
                    if g == 1:
                        mid = _signed(w)
                    elif g == 2:
                        offset = _signed(w)
                    elif g == 3:
                        dur = _signed(w)
                ev_name, tf_op = meta.get(mid, ('', ''))
                # whole nanoseconds, as jax.profiler.ProfileData gives them
                events.append(_Event(ev_name, float(ts + offset // 1000),
                                     float(dur // 1000), tf_op))
        lines.append(_Line(line_name, events))
    return _Plane(name, lines)


def read_planes(path: str) -> List[_Plane]:
    """The planes of an ``.xplane.pb`` (an XSpace: planes are field 1)."""
    with open(path, 'rb') as f:
        buf = f.read()
    return [_plane(v) for f, v in _fields(buf) if f == 1]


# -- the trace --------------------------------------------------------------

def from_xplane(path: str, steps: int, calls: Sequence[str]
                ) -> tracelib.Trace:
    """:func:`bench.trace.from_xplane` with each device op's scope and the
    program's host spans: the same ops, kinds, window and benchmark
    spans."""
    devices: Dict[str, List[tracelib.Op]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in read_planes(path):
        if plane.name.startswith('/device:TPU:'):
            ops = tracelib._device_ops(plane, calls)
            events = next((ln.events for ln in plane.lines
                           if ln.name == 'XLA Ops'), [])
            devices[plane.name] = []
            for o, ev in zip(ops, events):
                scope = (ev.tf_op.rpartition(':')[0] if ':' in ev.tf_op
                         else ev.tf_op)
                devices[plane.name].append(ScopedOp(
                    o.name, o.kind, o.start_ns, o.dur_ns, scope,
                    part(scope, ev.name)))
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPANS.match(ev.name):
                        host.append((ev.name, ev.start_ns, ev.duration_ns))
    windows = [(s, s + d) for n, s, d in host if n == 'bench.window']
    if not windows:
        raise ValueError(f'{path}: no bench.window annotation on the host')
    if not devices:
        raise ValueError(f'{path}: no TPU device plane')
    return tracelib.Trace(steps=steps, window=windows[0], devices=devices,
                          host=host)


# -- attribution ------------------------------------------------------------

def names_program(scope: str) -> bool:
    """Whether an op's name stack holds a scope of the program."""
    return any(p.startswith('fft.') for p in _path(scope))


def _path(scope: str) -> List[str]:
    # XLA joins the name stacks of ops it merges with ';': the first is
    # the op's own
    return scope.split(';')[0].split('/')


def part(scope: str, text: str = '') -> str:
    """What an op does, from its name stack ``scope`` and its HLO
    ``text``: its innermost leaf scope; ``unscoped`` inside a superstep
    but outside every leaf; ``boundary`` under the facade's scope, for an
    op that reads or writes a complex value (the program is planar from
    its entry to its exit, and XLA names its split and combine of a
    complex value after the argument, or not at all), and for XLA's copy
    of a program argument (named after the argument alone); ``relayout``
    for a nameless in-place join (XLA's rewrite of a concatenation, such
    as the join of pipelined chunks); else ``unscoped``."""
    path = _path(scope)
    for p in reversed(path):
        if p in LEAVES:
            return LEAVES[p]
    if any(STEP.match(p) for p in path):
        return 'unscoped'
    if names_program(scope) or COMPLEX.search(text):
        return 'boundary'
    name, opcode, _ = tracelib.parse_hlo(text)
    if ARGUMENT.match(scope) and opcode == 'copy':
        return 'boundary'
    if not scope and INPLACE_JOIN.match(name):
        return 'relayout'
    return 'unscoped'


def step(scope: str) -> Optional[int]:
    """The superstep an op belongs to, or None outside every one."""
    for p in reversed(_path(scope)):
        m = STEP.match(p)
        if m:
            return int(m.group(1))
    return None


def scoped(t: tracelib.Trace) -> bool:
    """Whether any op of the trace carries a program scope."""
    return any(names_program(getattr(o, 'scope', ''))
               for ops in t.devices.values() for o in ops)


def _ms(t: tracelib.Trace, dev: str, keep) -> float:
    lo, hi = t.window
    ivs = tracelib.clip([(o.start_ns, o.end_ns) for o in t.ops(dev)
                         if keep(o)], lo, hi)
    return tracelib.union_ns(ivs) / 1e6 / t.steps


def part_ms(t: tracelib.Trace, name: str) -> Optional[float]:
    """Device time per step of one part (:data:`PARTS`) on the busiest
    device, in ms: the union of its ops' intervals."""
    if not scoped(t):
        return None
    return _ms(t, t.busiest(),
               lambda o: getattr(o, 'part', 'unscoped') == name)


def dispatch_ms(t: tracelib.Trace) -> Optional[float]:
    """Host time per step inside the plan's calls (``fft.apply.*``), in
    ms: the union of those spans within the window."""
    lo, hi = t.window
    spans = tracelib.clip([(s, s + d) for n, s, d in t.host
                           if APPLY.match(n)], lo, hi)
    if not spans:
        return None
    return tracelib.union_ns(spans) / 1e6 / t.steps


def supersteps(t: tracelib.Trace) -> Optional[Dict[str, float]]:
    """Device time per step on the busiest device by call, superstep
    and part, in ms: ``'<call> <step|-> <part>' -> ms``."""
    if not scoped(t):
        return None
    dev = t.busiest()
    keys = sorted({(o.name.split(':')[0], step(o.scope), o.part)
                   for o in t.ops(dev)},
                  key=lambda k: (k[0], -1 if k[1] is None else k[1], k[2]))
    out = {}
    for call, st, p in keys:
        out[f'{call} {"-" if st is None else st} {p}'] = _ms(
            t, dev, lambda o, c=call, s=st, q=p: (
                o.name.split(':')[0] == c and step(o.scope) == s
                and o.part == q))
    return out
