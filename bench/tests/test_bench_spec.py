"""BENCHMARK.json against the benchmark contract's limits, and every
file it names found by name."""
import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
LINE = re.compile(r'^[^\n\t]{1,200}$')
PATH = re.compile(r'^[A-Za-z0-9_./-]{1,200}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
TOP_KEYS = {'command', 'paths', 'run_seconds', 'configs', 'workloads',
            'end_to_end', 'per_layer'}


@pytest.fixture(scope='module')
def spec():
    return harness.Bench(ROOT).spec


def test_top_level_keys_and_sizes(spec):
    assert set(spec) == TOP_KEYS
    assert os.path.getsize(os.path.join(ROOT, 'BENCHMARK.json')) <= 64 * 1024
    assert 1 <= spec['run_seconds'] <= 51
    assert isinstance(spec['run_seconds'], int)
    assert 1 <= len(spec['paths']) <= 16
    for p in spec['paths']:
        assert PATH.match(p) and not p.startswith('/') and '..' not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(spec['command']) <= 32
    for word in spec['command']:
        assert LINE.match(word) and not word.startswith('/')
        assert '..' not in word
    assert os.path.isfile(os.path.join(ROOT, spec['command'][1]))


def test_names_units_and_lines(spec):
    names = []
    for key in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        for e in spec[key]:
            assert NAME.match(e['name']), e['name']
            names.append((key in ('end_to_end', 'per_layer'), e['name']))
    for metric in spec['end_to_end'] + spec['per_layer']:
        assert UNIT.match(metric['unit']), metric['unit']
        assert metric['better'] in ('lower', 'higher')
        assert metric['source'] in SOURCES
    for c in spec['configs']:
        assert LINE.match(c['source']) and LINE.match(c['why'])
        assert len(c['reduced']) <= 16
        assert all(NAME.match(k) for k in c['reduced'])
    for w in spec['workloads']:
        assert LINE.match(w['why'])
        assert NAME.match(w['config']) and NAME.match(w['traffic'])
    for m in spec['per_layer']:
        assert LINE.match(m['layer'])
    for kind in (True, False):
        group = [n for k, n in names if k == kind]
        assert len(group) == len(set(group))
    metrics = [n for k, n in names if k]
    assert len(metrics) == len(set(metrics))


def test_entry_keys(spec):
    assert all(set(c) == {'name', 'source', 'file', 'reduced', 'why'}
               for c in spec['configs'])
    assert all(set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
               for w in spec['workloads'])
    e2e = {'name', 'unit', 'better', 'bound', 'source'}
    assert all(set(m) - {'workloads'} == e2e for m in spec['end_to_end'])
    pl = {'name', 'unit', 'better', 'source', 'layer', 'moves'}
    assert all(set(m) - {'workloads'} == pl for m in spec['per_layer'])


def test_counts_bounds_and_chips(spec):
    assert 1 <= len(spec['configs']) <= 24
    assert 1 <= len(spec['workloads']) <= 24
    assert 1 <= len(spec['end_to_end']) <= 16
    assert 1 <= len(spec['per_layer']) <= 128
    four = [w for w in spec['workloads'] if w['chips'] == 4]
    assert all(w['chips'] in (1, 4) for w in spec['workloads'])
    assert len(four) <= max(1, len(spec['workloads']) // 2)
    pairs = [(w['config'], w['traffic']) for w in spec['workloads']]
    assert len(pairs) == len(set(pairs))
    e2e = {m['name']: m for m in spec['end_to_end']}
    assert 'setup_s' in e2e and e2e['setup_s']['bound'] <= 0.25
    for m in spec['end_to_end']:
        assert 0.01 <= m['bound'] <= 0.25
        assert m['source'] in ('host_clock', 'device_trace')


def test_metric_workloads_name_real_cells(spec):
    cells = {w['name'] for w in spec['workloads']}
    e2e = {m['name'] for m in spec['end_to_end']}
    for m in spec['end_to_end'] + spec['per_layer']:
        assert set(m.get('workloads', [])) <= cells
    for m in spec['per_layer']:
        assert m['moves'] in e2e
    layers = {}
    for m in spec['per_layer']:
        layers.setdefault(m['layer'], set()).add(m['name'])
    for cell in cells:
        reported = [m for m in spec['end_to_end']
                    if cell in m.get('workloads', [cell])]
        assert 'setup_s' in {m['name'] for m in reported}
        assert len(reported) >= 2
        assert any(cell in m.get('workloads', [cell])
                   for m in spec['per_layer'])


def test_every_config_is_used_and_files_lie_under_paths(spec):
    used = {w['config'] for w in spec['workloads']}
    assert used == {c['name'] for c in spec['configs']}
    files = [c['file'] for c in spec['configs']]
    assert len(files) == len(set(files))
    sources = [c['source'] for c in spec['configs']]
    assert len(sources) == len(set(sources))
    for f in files:
        assert any(f.startswith(p.rstrip('/') + '/') for p in spec['paths'])


def test_full_check_fits_the_time_limit(spec):
    # 2 + 14 runs per cell at run_seconds + 60 s each, 2 x 90 s of
    # compile per cell and 1200 s spare, with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (spec['run_seconds'] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize('kind', ['configs', 'workloads', 'metrics',
                                  'references'])
def test_every_file_loads_by_name(spec, kind):
    b = harness.Bench(ROOT)
    if kind == 'configs':
        for c in spec['configs']:
            cfg = b.config(c['name'])
            assert cfg['name'] == c['name']
            assert cfg['reduced'] == c['reduced']
            assert set(cfg['limits']) and cfg['shape']
    elif kind == 'workloads':
        for w in spec['workloads']:
            assert b.workload(w['name'])['config'] == w['config']
            traffic = b.traffic(w['traffic'])
            assert traffic['calls'] and traffic['loop'] == 'closed'
            assert str(w['chips']) in b.config(w['config'])['mesh']
    elif kind == 'metrics':
        for m in spec['end_to_end'] + spec['per_layer']:
            assert callable(b.module('metrics', m['name']).read)
    else:
        for c in spec['configs']:
            ref = b.module('references', b.config(c['name'])['reference'])
            assert callable(ref.forward)


def test_bench_dir_holds_only_the_benchmark():
    """Every file under bench/ is the benchmark's: no stray outputs."""
    for dirpath, dirnames, files in os.walk(os.path.join(ROOT, 'bench')):
        dirnames[:] = [d for d in dirnames if d != '__pycache__']
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert PATH.match(rel), rel
            assert not f.endswith('.log'), rel
            assert not f.endswith('.xplane.pb') or rel.startswith(
                'bench/tests/data/'), rel


def test_peaks_table_has_its_source():
    table = harness.load_json(os.path.join(ROOT, 'bench', 'peaks.json'))
    assert 'TPU v5e' in table['source']
    v5e = table['devices']['TPU v5 lite']
    assert v5e['bf16_flops_per_s'] == 197e12
    assert v5e['hbm_bytes_per_s'] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match='no peaks for device kind'):
        harness.peak_of(ROOT, 'TPU v99 imaginary')
    with pytest.raises(KeyError):
        harness.peak_of(ROOT, 'cpu')
    assert harness.peak_of(ROOT, 'TPU v5 lite')['hbm_bytes'] == 16e9


def test_benchmark_json_is_plain_json():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        json.load(f)
