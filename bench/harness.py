"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names the cell; the cell names a configuration file
(``bench/configs``), a traffic mix (``bench/traffic/<mix>.json``) and
its chips. Each metric is a reader of its own, ``bench/metrics/<name>.py``
with a ``read(run)`` that returns a number or ``None``; a configuration
names its plain reference, ``bench/references/<name>.py``. Adding a
configuration, a mix or a metric is adding files and entries: nothing
here names one.

A run builds the plan through the public facade, draws its input on the
device from the seed, warms every call of the mix, measures the window,
then checks what the timed executables produced against the float64
reference (:mod:`bench.check`).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if os.path.join(ROOT, 'src') not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, 'src'))

#: jax monitoring events that mean a trace or a compile happened
COMPILE_EVENTS = ('/jax/core/compile/jaxpr_trace_duration',
                  '/jax/core/compile/backend_compile_duration')


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, under one checkout."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.spec = load_json(os.path.join(root, 'BENCHMARK.json'))

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e['name'] == name:
                return e
        raise KeyError(f'no {key} entry named {name!r} in BENCHMARK.json')

    def workload(self, name: str) -> dict:
        return self._entry('workloads', name)

    def config(self, name: str) -> dict:
        return load_json(os.path.join(self.root,
                                      self._entry('configs', name)['file']))

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.root, 'bench', 'traffic',
                                      name + '.json'))

    def module(self, kind: str, name: str):
        """Import ``bench/<kind>/<name>.py`` from this checkout."""
        path = os.path.join(self.root, 'bench', kind, name + '.py')
        spec = importlib.util.spec_from_file_location(
            f'bench_{kind}_{name}'.replace('-', '_').replace('.', '_'), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, workload: str, traced: bool) -> List[dict]:
        """The metric entries a run of ``workload`` reports: the
        end-to-end ones untraced, the per-layer ones traced."""
        key = 'per_layer' if traced else 'end_to_end'
        return [m for m in self.spec[key]
                if workload in m.get('workloads', [workload])]


def peak_of(root: str, device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = load_json(os.path.join(root, 'bench', 'peaks.json'))['devices']
    if device_kind not in table:
        raise KeyError(f'no peaks for device kind {device_kind!r} in '
                       f'bench/peaks.json (known: {sorted(table)})')
    return table[device_kind]


def accelerator(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoChip` where JAX
    finds no TPU or fewer chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != 'tpu':
        raise NoChip(f'no TPU: jax sees {devices[0].platform!r} devices')
    if len(devices) < chips:
        raise NoChip(f'the cell needs {chips} chips, jax sees '
                     f'{len(devices)}')
    return devices[:chips]


@dataclasses.dataclass
class Run:
    """What one run measured. The readers in ``bench/metrics`` take
    their numbers from here and from nothing else."""
    steps: int                     # steps completed in the window
    step_s: List[float]            # each step, dispatch to ready
    window_s: float                # host clock, first dispatch to last ready
    setup_s: float                 # process start to the window
    memory_peak_bytes: int         # fullest chip
    calls: int                     # transforms per step
    work: Dict[str, float]         # per device per transform (bench.work)
    peak: Optional[Dict[str, float]]   # published peaks of the device
    trace: Optional[object] = None     # bench.trace.Trace of a traced run


def real_input(cfg: dict) -> bool:
    return not cfg['input'].startswith('complex')


def build_plan(cfg: dict, devices: Sequence):
    import repro.fft as fft
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(cfg['mesh'][str(len(devices))], cfg['mesh_axes'],
                     devices=list(devices))
    return getattr(fft, cfg['planner'])(tuple(cfg['shape']), mesh,
                                        **cfg['plan'])


def describe(p) -> str:
    from repro.fft import methods
    n = p.shape[-1]
    meth = methods.resolve(p.method, n // 2 if p.real else n).name
    return (f'shape={list(p.shape)} real={p.real} method={p.method}->{meth} '
            f'kernel={p.kernel}->{p.resolved_kernel} comm={p.comm} '
            f'overlap_chunks={p.overlap_chunks} '
            f'donates_input={p.donates_input} mesh={dict(p.mesh.shape)}')


def input_maker(cfg: dict, sharding) -> Callable[[int], object]:
    """seed -> the configuration's input, drawn on the device in one
    jitted call (one compile for every seed: the seed is an argument)."""
    import jax
    import jax.numpy as jnp
    shape = tuple(cfg['shape'])
    real = real_input(cfg)

    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        if real:
            return jax.random.normal(key, shape, jnp.float32)
        kr, ki = jax.random.split(key)
        return jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                               jax.random.normal(ki, shape, jnp.float32))

    fn = jax.jit(draw, out_shardings=sharding)

    def make(seed: int):
        return fn(np.uint32(seed & 0xFFFFFFFF),
                  np.uint32((seed >> 32) & 0xFFFFFFFF))
    return make


def step_calls(p, traffic: dict) -> List[Tuple[str, Callable]]:
    return [(name, getattr(p, name)) for name in traffic['calls']]


def run_steps(calls, x, *, seconds: float):
    """The closed loop: one caller, each step chains the calls on the
    previous output and waits for the last. Returns the final output,
    each step's seconds and the window's seconds."""
    from jax.profiler import TraceAnnotation
    times = []
    begin = time.perf_counter()
    deadline = begin + seconds
    with TraceAnnotation('bench.window'):
        while True:
            t = time.perf_counter()
            for name, fn in calls:
                with TraceAnnotation(f'dispatch.{name}'):
                    x = fn(x)
            with TraceAnnotation('wait'):
                x.block_until_ready()
            now = time.perf_counter()
            times.append(now - t)
            if now >= deadline:
                break
    return x, times, now - begin


class CompileCounter:
    """Counts traces and compiles while it is open."""

    def __init__(self):
        self.n = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
             for d in devices]
    return int(max(peaks))


def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             traced: bool, *, devices: Sequence, t0: float,
             config: Optional[dict] = None,
             control: Optional[str] = None) -> dict:
    """One run of ``workload``: returns the result object that
    ``bench/run.py`` prints. ``devices`` are the chips the run holds
    (``accelerator``; tests pass CPU devices). ``config`` replaces the
    configuration file (the tests' small sizes). ``control`` names a
    dtype: the run then drives :class:`bench.control.Control` in that
    dtype in the plan's place."""
    import jax
    from bench import check, trace as tracelib, work as worklib

    wl = bench.workload(workload)
    cfg = config if config is not None else bench.config(wl['config'])
    traffic = bench.traffic(wl['traffic'])
    ref = bench.module('references', cfg['reference'])
    dev = devices[0]

    p = build_plan(cfg, devices)
    log(f'[device] platform={dev.platform} device_kind={dev.device_kind} '
        f'count={len(devices)} jax={jax.__version__}')
    log(f'[plan] {wl["config"]} {describe(p)}')
    if control is not None:
        from bench.control import Control
        p = Control(cfg, p.mesh, control)
        log(f'[control] {control} DFT matmuls in the plan\'s place')
    calls = step_calls(p, traffic)
    make = input_maker(cfg, p.in_sharding)

    x = make(seed)
    for _ in range(traffic['warm_steps']):
        x, _, _ = run_steps(calls, x, seconds=0)
    del x
    x = make(seed)
    jax.block_until_ready(x)

    trace_dir = tempfile.mkdtemp(prefix='bench_trace_') if traced else None
    window = min(seconds, traffic['trace_seconds']) if traced else seconds
    setup_s = time.perf_counter() - t0
    with CompileCounter() as compiles:
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, profiler_options=opts):
                x, step_s, window_s = run_steps(calls, x, seconds=window)
        else:
            x, step_s, window_s = run_steps(calls, x, seconds=window)
    mem = memory_peak(devices)
    slow = sorted(range(len(step_s)), key=lambda i: -step_s[i])[:5]
    log(f'[window] steps={len(step_s)} window_s={window_s!r} '
        f'compiles_in_window={compiles.n} traced={traced} slowest_steps='
        + ' '.join(f'{i}:{step_s[i] * 1e3:.3f}ms' for i in slow))
    trace = (tracelib.load(trace_dir, steps=len(step_s),
                           calls=traffic['calls']) if traced else None)

    # the check: what the timed executables produced, against float64
    outcome = check.check(cfg, ref, calls, x, make, seed,
                          round_trip=traffic.get('round_trip', False))
    del x

    try:
        peak = peak_of(bench.root, dev.device_kind)
    except KeyError:
        if dev.platform == 'tpu':
            raise
        peak = None                 # CPU tests: no device peaks
    w = worklib.transform_work(cfg['shape'], real_input(cfg),
                               cfg['input'], len(devices))
    run = Run(steps=len(step_s), step_s=step_s,
              window_s=window_s, setup_s=setup_s, memory_peak_bytes=mem,
              calls=len(calls), work=w, peak=peak, trace=trace)
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(devices), 'memory_peak_bytes': mem}
    result = {'correct': outcome.correct, 'attempted': len(step_s),
              'failed': 0 if outcome.correct else len(step_s)}
    if trace is not None:
        device['busy_s'] = trace.busy_s()
        device['window_s'] = trace.window_s()
        result['breakdown'] = trace.breakdown()
        log(f'[trace] {trace.summary()}')
    metrics = {}
    for m in bench.metrics(workload, traced):
        value = bench.module('metrics', m['name']).read(run)
        if value is not None:
            metrics[m['name']] = {'value': value, 'unit': m['unit']}
    result['metrics'] = metrics
    result['device'] = device
    result['checks'] = outcome.numbers
    return result
