"""Faults planted under the timed path make ``correct`` false: a
transform that returns its state unchanged, half of the output left
out, one answer altered where it is produced, and (on four CPU
devices) the exchange between chips left out."""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bench import harness
from bench.tests import _faults


def run(cell, kind, mp):
    b = harness.Bench(harness.ROOT)
    cfg = b.config(b.workload(cell)['config'])
    cfg['shape'] = [16, 16, 16]
    with _faults.broken_raw(mp, kind):
        return harness.run_cell(b, cell, 2 ** 31 + 99, 0.2, False,
                                devices=jax.devices()[:1],
                                t0=time.perf_counter(), config=cfg)


@pytest.mark.parametrize('cell,kind', [
    ('c2c512-pair-1chip', 'unchanged'),
    ('c2c512-pair-1chip', 'half'),
    ('c2c512-pair-1chip', 'altered'),
    ('r2c512-pair-1chip', 'half'),
    ('r2c512-pair-1chip', 'altered'),
])
def test_fault_is_not_correct(cell, kind, monkeypatch):
    r = run(cell, kind, monkeypatch)
    assert r['correct'] is False
    worst = max(c['value'] / c['limit'] for c in r['checks'].values())
    assert worst > 10


@pytest.mark.parametrize('no_exchange', [False, True])
def test_exchange_left_out_on_2x2(no_exchange):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    args = [sys.executable, '-m', 'bench.tests._mesh_worker']
    if no_exchange:
        args.append('--no-exchange')
    p = subprocess.run(args, cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r['device']['count'] == 4
    assert r['correct'] is (not no_exchange)
