"""The interval arithmetic of the trace reduction, on synthetic
traces whose answers are known by hand."""
import pytest

from bench import harness
from bench.trace import Op, Trace, classify, label, union_ns


def test_union_counts_overlaps_once():
    assert union_ns([]) == 0
    assert union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_ns([(20, 30), (0, 10), (10, 12)]) == 22


@pytest.mark.parametrize('text,kind', [
    ('%all-to-all.3 = f32[2,256,512]{2,1,0:T(8,128)} all-to-all('
     'f32[2,256,512]{2,1,0:T(8,128)} %fusion.2), replica_groups={{0,1}}',
     'collective'),
    # as XLA:TPU names the swap of a 2x2 plan
    ('%all_to_all.404 = f32[2,128,512,512]{3,2,1,0:T(8,128)} all-to-all('
     'f32[2,128,512,512]{3,2,1,0:T(8,128)} %fusion.9), dimensions={1}',
     'collective'),
    ('%all-gather-start = (f32[8]{0}, f32[16]{0}) all-gather-start('
     'f32[8]{0} %p)', 'collective'),
    ('%collective-permute-done.1 = f32[8]{0:T(256)} collective-permute-done('
     'f32[8]{0:T(256)} %cp)', 'collective'),
    # a fusion that reads an all-to-all's result is compute
    ('%fusion.12 = f32[512,512]{1,0:T(8,128)} fusion(f32[512,512]{1,0} '
     '%all-to-all.3), kind=kLoop, calls=%fused_computation.1', 'compute'),
    ('%fft_matmul.3 = (f32[262144,512]{1,0:T(8,128)}, f32[262144,512]'
     '{1,0:T(8,128)}) custom-call(f32[2,128,128]{2,1,0:T(8,128)S(1)} '
     '%copy-done.6), custom_call_target="tpu_custom_call", '
     'frontend_attributes={kernel_metadata={}}', 'kernel'),
    ('%custom-call = f32[512,512,512]{2,1,0:T(8,128)} custom-call('
     'c64[512,512,512]{2,1,0:T(8,128)} %x.1), '
     'custom_call_target="X64SplitHigh"', 'compute'),
    ('%copy.7 = f32[512,512,512]{1,2,0:T(8,128)} copy(f32[512,512,512]'
     '{2,1,0:T(8,128)} %bitcast.14)', 'compute'),
])
def test_classify(text, kind):
    assert classify(text) == kind


def test_label_is_short():
    assert label('%fft_matmul.3 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]'
                 '{0} %a), custom_call_target="tpu_custom_call"') == \
        '%fft_matmul.3 custom-call tpu_custom_call'
    assert label('%copy.4 = f32[512,512,512]{1,2,0:T(8,128)} copy('
                 'f32[512,512,512]{2,1,0:T(8,128)} %bitcast.14)') == \
        '%copy.4 copy'


def synthetic():
    """Two steps in a 100 ns window on one device: a kernel 0-30, a
    collective 25-45 (5 ns under the kernel), compute 50-70, idle
    70-80 and 45-50, and a kernel 80-95; an op outside the window."""
    ops = [Op('k', 'kernel', 0, 30), Op('a2a', 'collective', 25, 20),
           Op('f', 'compute', 50, 20), Op('k', 'kernel', 80, 15),
           Op('late', 'compute', 120, 10)]
    idle = [Op('k', 'kernel', 0, 10)]
    host = [('bench.window', 0, 100), ('dispatch.forward', 40, 15),
            ('wait', 60, 40)]
    return Trace(steps=2, window=(0.0, 100.0),
                 devices={'/device:TPU:0': ops, '/device:TPU:1': idle},
                 host=host)


def test_busy_idle_and_kinds():
    t = synthetic()
    dev = '/device:TPU:0'
    assert t.busiest() == dev
    assert t.busy_ns(dev) == 30 + 15 + 20 + 15
    assert t.busy_s() == pytest.approx((80 + 10) / 2 / 1e9)
    assert t.kind_ns(dev, 'kernel') == 45
    assert t.kind_ns(dev, 'collective') == 20
    assert t.exposed_ns(dev, 'collective') == 15
    assert t.idle_gaps(dev) == [(45, 50), (70, 80), (95, 100)]


def test_breakdown_names_ops_and_host_phases():
    b = synthetic().breakdown()
    assert b['device_ops'][0][0] == 'k'
    assert b['device_ops'][0][1] == pytest.approx(45e-9)
    assert [g[0] for g in b['idle_gaps']] == ['wait', 'dispatch.forward',
                                              'wait']
    assert b['idle_gaps'][0][1] == pytest.approx(10e-9)


def test_per_layer_readers_on_synthetic_trace():
    b = harness.Bench(harness.ROOT)
    run = harness.Run(steps=2, step_s=[], window_s=1e-7,
                      setup_s=0, memory_peak_bytes=1, calls=2,
                      work={'flops': 1.0, 'bytes': 819e9 * 20e-9},
                      peak=harness.peak_of(harness.ROOT, 'TPU v5 lite'),
                      trace=synthetic())

    def read(name):
        return b.module('metrics', name).read(run)
    assert read('device_idle_share') == pytest.approx(20.0)
    assert read('kernel_ms') == pytest.approx(45 / 2 / 1e6)
    assert read('fusion_ms') == pytest.approx(20 / 2 / 1e6)
    assert read('collective_ms') == pytest.approx(20 / 2 / 1e6)
    assert read('collective_exposed_ms') == pytest.approx(15 / 2 / 1e6)
    # bound: 2 calls x 20 ns of bytes a step; busy 40 ns a step
    assert read('roofline_share') == pytest.approx(100.0)
    run.trace = None
    for name in ('device_idle_share', 'kernel_ms', 'roofline_share'):
        assert read(name) is None


def test_readers_return_nothing_where_nothing_runs():
    b = harness.Bench(harness.ROOT)
    t = Trace(steps=1, window=(0.0, 10.0),
              devices={'/device:TPU:0': [Op('f', 'compute', 0, 5)]}, host=[])
    run = harness.Run(steps=1, step_s=[], window_s=1e-8,
                      setup_s=0, memory_peak_bytes=1, calls=2,
                      work={'flops': 1.0, 'bytes': 1.0}, peak=None, trace=t)
    for name in ('kernel_ms', 'collective_ms', 'collective_exposed_ms',
                 'roofline_share'):
        assert b.module('metrics', name).read(run) is None
