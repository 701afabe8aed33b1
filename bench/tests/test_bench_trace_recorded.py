"""The trace reduction on a recorded profile: 13 forward+inverse pairs of
the 512^3 c2c plan on one TPU v5e, the ``.xplane.pb`` of a traced run of
this harness with a 1 s window, committed beside this file. Parsing it
needs no chip."""
import os

import pytest

from bench import harness, trace as tracelib, work

DATA = os.path.join(os.path.dirname(__file__), 'data',
                    'c2c512-1chip-13pairs.xplane.pb')
DEV = '/device:TPU:0'


@pytest.fixture(scope='module')
def trace():
    return tracelib.from_xplane(DATA, 13, ['forward', 'inverse'])


@pytest.fixture(scope='module')
def run(trace):
    return harness.Run(
        steps=13, step_s=[], window_s=trace.window_s(), setup_s=0,
        memory_peak_bytes=1, calls=2,
        work=work.transform_work((512,) * 3, False, 'complex64', 1),
        peak=harness.peak_of(harness.ROOT, 'TPU v5 lite'), trace=trace)


def read(name, run):
    return harness.Bench(harness.ROOT).module('metrics', name).read(run)


def test_planes_ops_and_host_spans(trace):
    assert list(trace.devices) == [DEV]
    assert trace.window_s() == pytest.approx(1.058053143)
    assert {n for n, _, _ in trace.host} == {
        'bench.window', 'dispatch.forward', 'dispatch.inverse', 'wait'}
    assert sum(n == 'wait' for n, _, _ in trace.host) == 13
    # every op ran inside one of the two programs, named by its call
    assert all(o.name.split(':')[0] in ('forward', 'inverse')
               for o in trace.ops(DEV))


def test_kernels_are_the_mosaic_calls(trace):
    kernels = trace.ops(DEV, 'kernel')
    # three axis passes a transform, two transforms a pair
    assert len(kernels) == 13 * 6
    assert all('fft_matmul' in o.name and o.name.endswith('tpu_custom_call')
               for o in kernels)
    # XLA's own complex split and combine are not kernels
    x64 = [o for o in trace.ops(DEV) if 'X64' in o.name]
    assert x64 and all(o.kind == 'compute' for o in x64)
    assert trace.ops(DEV, 'collective') == []


def test_one_chip_kinds_partition_busy_time(trace):
    """One core runs one op at a time: kernel + compute time is the busy
    time, and both lie inside the window."""
    busy = trace.busy_ns(DEV)
    parts = trace.kind_ns(DEV, 'kernel') + trace.kind_ns(DEV, 'compute')
    assert parts == pytest.approx(busy, rel=1e-9)
    assert 0 < busy < trace.window[1] - trace.window[0]


def test_per_layer_metrics(run):
    assert read('device_idle_share', run) == pytest.approx(1.28365, rel=1e-4)
    assert read('kernel_ms', run) == pytest.approx(36.44789, rel=1e-5)
    assert read('fusion_ms', run) == pytest.approx(43.89607, rel=1e-5)
    assert read('collective_ms', run) is None
    assert read('collective_exposed_ms', run) is None
    # 5.243 ms of bytes a pair over 80.344 ms busy a pair
    share = read('roofline_share', run)
    assert share == pytest.approx(100 * 5.2432e-3 / 80.344e-3, rel=1e-3)
    assert share <= 100


def test_breakdown(trace):
    b = trace.breakdown()
    assert len(b['device_ops']) == 10 and len(b['idle_gaps']) == 10
    secs = [s for _, s in b['device_ops']]
    assert secs == sorted(secs, reverse=True)
    assert all(name in ('wait', 'dispatch.forward', 'dispatch.inverse',
                        'host outside any dispatch or wait')
               for name, _ in b['idle_gaps'])
