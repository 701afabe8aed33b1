"""The work model: the algorithm's least time per transform, from its
nominal flops and the bytes each device must read and write once."""
import random

import pytest

from bench import harness, trace as tracelib, work

V5E = harness.peak_of(harness.ROOT, 'TPU v5 lite')
N3 = (512, 512, 512)


def test_c2c_512_one_chip():
    w = work.transform_work(N3, False, 'complex64', 1)
    assert w['flops'] == 5 * 2 ** 27 * 27
    assert w['bytes'] == 2 * 2 ** 27 * 8            # 2.147 GB read + write
    t, term = work.step_bound(w, 1, V5E)
    assert term == 'bytes'
    assert t == pytest.approx(2.62e-3, rel=2e-3)       # 2.62 ms a transform
    assert w['flops'] / V5E['bf16_flops_per_s'] == pytest.approx(
        9.2e-5, rel=1e-2)                              # 0.092 ms of flops
    pair, _ = work.step_bound(w, 2, V5E)
    assert pair == pytest.approx(5.243e-3, rel=1e-3)


def test_r2c_512_one_chip():
    w = work.transform_work(N3, True, 'float32', 1)
    assert w['flops'] == 5 * 2 ** 27 * 27 / 2
    # 0.537 GB of real input, 0.539 GB of half spectrum (512*512*257 bins)
    assert w['bytes'] == 2 ** 27 * 4 + 512 * 512 * 257 * 8
    t, term = work.step_bound(w, 1, V5E)
    assert term == 'bytes' and t == pytest.approx(1.31e-3, rel=3e-3)


def test_c2c_512_on_four_chips():
    w = work.transform_work(N3, False, 'complex64', 4)
    assert w['bytes'] == 2 ** 27 * 8 * 2 / 4          # 268 MB each way
    t, term = work.step_bound(w, 1, V5E)
    assert term == 'bytes' and t == pytest.approx(0.655e-3, rel=3e-3)


def test_flops_bind_when_bytes_are_few():
    w = {'flops': 1e12, 'bytes': 1.0}
    t, term = work.step_bound(w, 2, V5E)
    assert term == 'flops' and t == pytest.approx(2e12 / 197e12)


@pytest.mark.parametrize('seed', range(6))
def test_bound_never_above_a_possible_busy_time(seed):
    """Synthetic traces in which every transform moves at least its
    minimum bytes at no more than the HBM peak, in passes and gaps of
    random length: the roofline share never passes 100%, and reaches it
    only when the device streams the minimum bytes at peak and nothing
    else."""
    rng = random.Random(seed)
    shape = rng.choice([N3, (256, 256, 256), (1024, 1024, 512)])
    real = rng.random() < 0.5
    chips = rng.choice([1, 4])
    w = work.transform_work(shape, real, 'float32' if real else 'complex64',
                            chips)
    least = w['bytes'] / V5E['hbm_bytes_per_s'] * 1e9    # ns a transform
    steps, calls = rng.randint(1, 5), 2
    ops, t = [], 0.0
    for _ in range(steps * calls):
        # each transform in 1..4 passes that together take >= least
        parts = rng.randint(1, 4)
        for _ in range(parts):
            d = least / parts * (1 + rng.random() * 3)
            ops.append(tracelib.Op('fusion', 'compute', t, d))
            t += d + rng.random() * least * 0.1
    tr = tracelib.Trace(steps=steps, window=(0.0, t), devices={'d0': ops},
                        host=[])
    run = harness.Run(steps=steps, step_s=[], window_s=t / 1e9,
                      setup_s=0.0, memory_peak_bytes=1, calls=calls, work=w,
                      peak=V5E, trace=tr)
    share = harness.Bench(harness.ROOT).module(
        'metrics', 'roofline_share').read(run)
    assert 0 < share <= 100.0
    exact = tracelib.Trace(
        steps=1, window=(0.0, 2 * least),
        devices={'d0': [tracelib.Op('k', 'kernel', 0.0, least),
                        tracelib.Op('k', 'kernel', least, least)]}, host=[])
    run.trace, run.steps = exact, 1
    assert harness.Bench(harness.ROOT).module(
        'metrics', 'roofline_share').read(run) == pytest.approx(100.0)
