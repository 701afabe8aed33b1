"""The harness end to end on the CPU at 16^3, with the look for a chip
skipped: the comparison passes on the program, fails on the
lower-precision control, and the harness finds new configurations,
mixes and metrics by name."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness

SMALL = [16, 16, 16]
SEED = 2 ** 33 + 5          # wider than 32 bits, as the driver's seeds are


def small_config(b, cell):
    cfg = b.config(b.workload(cell)['config'])
    cfg['shape'] = list(SMALL)
    return cfg


def run(b, cell, **kw):
    kw.setdefault('config', small_config(b, cell))
    return harness.run_cell(b, cell, SEED, 0.2, False,
                            devices=jax.devices()[:1],
                            t0=time.perf_counter(), **kw)


@pytest.fixture(scope='module')
def bench():
    return harness.Bench(harness.ROOT)


@pytest.mark.parametrize('cell', ['c2c512-pair-1chip', 'r2c512-pair-1chip'])
def test_program_is_correct(bench, cell):
    r = run(bench, cell)
    assert r['correct'] is True
    assert r['attempted'] > 0 and r['failed'] == 0
    assert list(r)[-1] == 'checks'
    assert set(r['checks']) == {'forward_err', 'roundtrip_err', 'drift_err'}
    for c in r['checks'].values():
        assert 0 <= c['value'] <= c['limit']
    rate = 'pair_ms' if cell.startswith('c2c') else 'pair_ms.r2c'
    assert set(r['metrics']) == {rate, 'pair_p95_ms', 'setup_s'}
    assert r['device']['platform'] == 'cpu' and r['device']['count'] == 1


@pytest.mark.parametrize('cell', ['c2c512-pair-1chip', 'r2c512-pair-1chip'])
def test_bfloat16_control_is_not_correct(bench, cell):
    r = run(bench, cell, control='bfloat16')
    assert r['correct'] is False
    assert r['failed'] == r['attempted']
    assert r['checks']['forward_err']['value'] > 10 * \
        r['checks']['forward_err']['limit']


@pytest.mark.parametrize('cell', ['c2c512-pair-1chip', 'r2c512-pair-1chip'])
def test_float32_control_is_correct(bench, cell):
    """The control's code at float32 passes: it fails by its precision
    alone."""
    assert run(bench, cell, control='float32')['correct'] is True


def test_same_seed_same_input(bench):
    cfg = small_config(bench, 'c2c512-pair-1chip')
    p = harness.build_plan(cfg, jax.devices()[:1])
    make = harness.input_maker(cfg, p.in_sharding)
    a, b = make(SEED), make(SEED)
    assert (a == b).all()
    assert not (make(SEED + 1) == a).all()
    assert not (make(SEED + 2 ** 32) == a).all()


def test_new_files_are_found_by_name(bench, tmp_path):
    """A configuration, a traffic mix and a metric added as new files
    plus new entries in BENCHMARK.json run with no edit to a file that is
    there."""
    root = tmp_path / 'checkout'
    shutil.copytree(os.path.join(harness.ROOT, 'bench'), root / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    spec = dict(bench.spec)
    cfg = small_config(bench, 'c2c512-pair-1chip')
    cfg['name'] = 'tiny-c2c'
    (root / 'bench' / 'configs' / 'tiny-c2c.json').write_text(json.dumps(cfg))
    mix = bench.traffic('pair')
    mix['calls'] = ['forward', 'inverse', 'forward', 'inverse']
    (root / 'bench' / 'traffic' / 'two_pairs.json').write_text(
        json.dumps(mix))
    (root / 'bench' / 'metrics' / 'dummy_steps.py').write_text(
        'def read(run):\n    return float(run.steps * run.calls)\n')
    spec['configs'] = spec['configs'] + [dict(
        name='tiny-c2c', source='test', file='bench/configs/tiny-c2c.json',
        reduced=[], why='test')]
    spec['workloads'] = spec['workloads'] + [dict(
        name='tiny-two', config='tiny-c2c', traffic='two_pairs', chips=1,
        why='test')]
    spec['end_to_end'] = spec['end_to_end'] + [dict(
        name='dummy_steps', unit='calls', better='higher', bound=0.01,
        source='host_clock', workloads=['tiny-two'])]
    (root / 'BENCHMARK.json').write_text(json.dumps(spec))
    b = harness.Bench(str(root))
    r = run(b, 'tiny-two', config=None)
    assert r['correct'] is True
    assert r['metrics']['dummy_steps']['value'] == 4 * r['attempted']
    assert set(r['checks']) == {'forward_err', 'roundtrip_err', 'drift_err'}
    # an existing cell does not report the new metric
    assert 'dummy_steps' not in run(b, 'c2c512-pair-1chip')['metrics']


def test_run_without_a_tpu_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    p = subprocess.run(
        [sys.executable, 'bench/run.py', '--workload', 'c2c512-pair-1chip',
         '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ''
    assert 'no TPU' in p.stderr
    # a directory with only BENCHMARK.json and bench/ fails as well
    shutil.copytree(os.path.join(harness.ROOT, 'bench'), tmp_path / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(harness.ROOT, 'BENCHMARK.json'), tmp_path)
    p = subprocess.run(
        [sys.executable, 'bench/run.py', '--workload', 'c2c512-pair-1chip',
         '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ''


def test_traced_run_reports_per_layer_metrics(bench, monkeypatch):
    """The traced path end to end on the CPU: the profiler runs, its
    profile is reduced (here by a stand-in, as the CPU has no TPU plane)
    and removed, and the result carries the per-layer metrics, the
    device's busy and window seconds and the breakdown."""
    from bench import trace as tracelib
    seen = {}

    def from_xplane(path, steps, calls):
        seen['path'] = path
        ops = [tracelib.Op(f'{c}:%k custom-call tpu_custom_call', 'kernel',
                           10.0 * i, 5.0) for i, c in enumerate(calls)]
        return tracelib.Trace(steps=steps, window=(0.0, 40.0),
                              devices={'/device:TPU:0': ops},
                              host=[('wait', 0.0, 40.0)])
    monkeypatch.setattr(tracelib, 'from_xplane', from_xplane)
    cfg = small_config(bench, 'c2c512-pair-1chip')
    r = harness.run_cell(bench, 'c2c512-pair-1chip', SEED, 0.2, True,
                         devices=jax.devices()[:1], t0=time.perf_counter(),
                         config=cfg)
    assert r['correct'] is True
    assert not os.path.exists(seen['path'])
    assert set(r['metrics']) == {'device_idle_share', 'kernel_ms'}
    assert r['metrics']['device_idle_share']['value'] == pytest.approx(75.0)
    assert r['device']['busy_s'] == pytest.approx(10e-9)
    assert r['device']['window_s'] == pytest.approx(40e-9)
    assert len(r['breakdown']['device_ops']) == 2
    assert list(r)[-1] == 'checks'
