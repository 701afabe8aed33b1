"""Reduction of a profiler trace to device intervals.

A traced run writes an ``.xplane.pb``; :func:`from_xplane` keeps, for
each device plane, the XLA operations that ran on it (name, kind, start,
duration), and from the host the benchmark's own annotations
(``bench.window``, ``dispatch.<call>``, ``wait``). The result is a
:class:`Trace`, from which the per-layer readers in ``bench/metrics``
take their numbers; a recorded profile in ``bench/tests/data`` checks
them without a chip.

An operation's kind is one of:

* ``kernel``: a Mosaic (Pallas) kernel, a custom call whose target is
  ``tpu_custom_call``;
* ``collective``: an exchange between chips (all-to-all,
  collective-permute, all-gather, all-reduce, reduce-scatter, send/recv);
* ``compute``: every other device operation (XLA fusions, copies,
  XLA's own custom calls such as the complex split and combine).

On a TPU device plane the ``XLA Ops`` line holds one event per executed
HLO instruction, named by the instruction's text, and the ``XLA
Modules`` line one event per program run. Each op is named after the
call of the mix whose program ran it (programs in the order they first
run in the window), then its HLO name, opcode and custom-call target:
``forward:%fft_matmul.3 custom-call tpu_custom_call``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

#: host annotations the benchmark's loop writes (harness.run_steps)
HOST_SPANS = re.compile(r'^(bench\.window|dispatch\..+|wait)$')
#: opcodes of exchanges between chips, with their async -start/-done
COLLECTIVE = re.compile(r'^(all-to-all|collective-permute|all-gather|'
                        r'all-reduce|reduce-scatter|ragged-all-to-all|'
                        r'collective-broadcast|send|recv)(-start|-done)?$')
OPCODE = re.compile(r'([a-z][a-z0-9-]*)\(')
TARGET = re.compile(r'custom_call_target="([^"]+)"')
KINDS = ('kernel', 'collective', 'compute')


@dataclasses.dataclass
class Op:
    name: str
    kind: str          # 'kernel' | 'collective' | 'compute'
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """(name, opcode, custom-call target) of an op event, whose name is
    the HLO instruction's text: ``%name = <shape> opcode(operands),
    attrs``. Layout letters in the shape are capitals (``T(8,128)``), so
    the first lower-case word before a parenthesis is the opcode."""
    name, sep, rest = text.partition(' = ')
    if not sep:
        return text, text, ''
    m = OPCODE.search(rest)
    t = TARGET.search(rest)
    return name, (m.group(1) if m else ''), (t.group(1) if t else '')


def classify(text: str) -> str:
    """Kind of a device operation from its HLO text."""
    _, opcode, target = parse_hlo(text)
    if COLLECTIVE.match(opcode):
        return 'collective'
    if opcode == 'custom-call' and target == 'tpu_custom_call':
        return 'kernel'
    return 'compute'


def label(text: str) -> str:
    """Short name of an op: its HLO name, opcode and custom-call target."""
    name, opcode, target = parse_hlo(text)
    return ' '.join(x for x in (name, opcode, target) if x)


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of [start, end) intervals, as disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of [start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


@dataclasses.dataclass
class Trace:
    steps: int                                  # steps in the traced window
    window: Tuple[float, float]                 # host span bench.window
    devices: Dict[str, List[Op]]                # device plane -> its ops
    host: List[Tuple[str, float, float]]        # (name, start_ns, dur_ns)

    # -- reductions -------------------------------------------------------

    def ops(self, device: str, kind: Optional[str] = None) -> List[Op]:
        lo, hi = self.window
        return [o for o in self.devices[device]
                if (kind is None or o.kind == kind)
                and o.end_ns > lo and o.start_ns < hi]

    def intervals(self, device: str, kinds=None) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return clip([(o.start_ns, o.end_ns) for o in self.ops(device)
                     if kinds is None or o.kind in kinds], lo, hi)

    def busy_ns(self, device: str) -> float:
        return union_ns(self.intervals(device))

    def busiest(self) -> str:
        return max(sorted(self.devices), key=self.busy_ns)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        return (sum(self.busy_ns(d) for d in self.devices)
                / len(self.devices) / 1e9)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def kind_ns(self, device: str, kind: str) -> float:
        """Summed device duration of one kind's ops (overlaps count
        once)."""
        return union_ns(self.intervals(device, (kind,)))

    def exposed_ns(self, device: str, kind: str) -> float:
        """Time in which an op of ``kind`` runs and no op of another
        kind does, on ``device``."""
        mine = merged(self.intervals(device, (kind,)))
        others = merged(self.intervals(
            device, [k for k in KINDS if k != kind]))
        covered = 0.0
        j = 0
        for s, e in mine:
            while j < len(others) and others[j][1] <= s:
                j += 1
            k = j
            while k < len(others) and others[k][0] < e:
                covered += min(e, others[k][1]) - max(s, others[k][0])
                k += 1
        return sum(e - s for s, e in mine) - covered

    def idle_gaps(self, device: str) -> List[Tuple[float, float]]:
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in merged(self.intervals(device)):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        return gaps

    def host_at(self, t: float) -> str:
        """The innermost benchmark annotation open on the host at t."""
        best = None
        for name, s, d in self.host:
            if s <= t < s + d and name != 'bench.window':
                if best is None or d < best[1]:
                    best = (name, d)
        return best[0] if best else 'host outside any dispatch or wait'

    def breakdown(self, top: int = 10) -> dict:
        """Device operations that took most time, and the longest idle
        gaps by what the host was doing, on the busiest device."""
        dev = self.busiest()
        per_op: Dict[str, float] = {}
        for o in self.ops(dev):
            per_op[o.name] = per_op.get(o.name, 0.0) + o.dur_ns / 1e9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(dev), key=lambda g: g[0] - g[1])[:top]
        return {'device_ops': [[n, s] for n, s in ops],
                'idle_gaps': [[self.host_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in gaps]}

    def summary(self) -> str:
        dev = self.busiest()
        kinds = {k: len(self.ops(dev, k))
                 for k in ('kernel', 'collective', 'compute')}
        return (f'devices={sorted(self.devices)} busiest={dev} '
                f'steps={self.steps} window_s={self.window_s()!r} '
                f'busy_s={self.busy_s()!r} ops_by_kind={kinds}')


def _device_ops(plane, calls) -> List[Op]:
    lines = {line.name: list(line.events) for line in plane.lines}
    modules = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                      e.name) for e in lines.get('XLA Modules', []))
    names: Dict[str, str] = {}
    for _, _, m in modules:
        if m not in names:
            i = len(names)
            names[m] = calls[i] if i < len(calls) else m
    starts = [m[0] for m in modules]
    ops = []
    for ev in lines.get('XLA Ops', []):
        start = float(ev.start_ns)
        i = bisect.bisect_right(starts, start) - 1
        prog = (names[modules[i][2]]
                if i >= 0 and start < modules[i][1] else '?')
        ops.append(Op(f'{prog}:{label(ev.name)}', classify(ev.name), start,
                      float(ev.duration_ns)))
    return ops


def from_xplane(path: str, steps: int, calls: Sequence[str]) -> Trace:
    """Read an ``.xplane.pb``: the XLA ops of each TPU device plane and
    the benchmark's host annotations. ``calls`` names the programs in
    the order they first run (the mix's calls)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith('/device:TPU:'):
            devices[plane.name] = _device_ops(plane, calls)
        elif plane.name.startswith('/host:'):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPANS.match(ev.name):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    windows = [(s, s + d) for n, s, d in host if n == 'bench.window']
    if not windows:
        raise ValueError(f'{path}: no bench.window annotation on the host')
    if not devices:
        raise ValueError(f'{path}: no TPU device plane')
    return Trace(steps=steps, window=windows[0], devices=devices, host=host)


def load(trace_dir: str, *, steps: int, calls: Sequence[str]) -> Trace:
    """Reduce the profile under ``trace_dir``, then delete it."""
    try:
        found = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f'{trace_dir}: expected one .xplane.pb, '
                             f'found {found}')
        return from_xplane(found[0], steps, calls)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
