"""The reduction by the program's own names (bench/scoped.py): its
protobuf reader against jax's, the attribution rules, the readings on
profiles of the scoped program recorded on one TPU v5e with
``bench/record.py`` (1 s windows, committed beside this file), and
record.py's refusal without a chip."""
import os
import subprocess
import sys

import pytest

from bench import harness, scoped, trace as tracelib

DATA = os.path.join(os.path.dirname(__file__), 'data')
UNNAMED = os.path.join(DATA, 'c2c512-1chip-13pairs.xplane.pb')
DEV = '/device:TPU:0'
CALLS = ['forward', 'inverse']
#: profile -> pairs in its window, and its readings in ms a pair
C2C = 'c2c512-1chip-scoped-13pairs.xplane.pb'
SCOPED = {
    C2C: (13, {'boundary': 24.445732, 'pass': 36.447724,
               'relayout': 19.4725, 'swap': 0.0, 'combine': 0.0,
               'unscoped': 6.4923e-05, 'dispatch': 0.593967}),
    'r2c512-1chip-scoped-12pairs.xplane.pb': (
        12, {'boundary': 9.473844, 'pass': 37.389702,
             'relayout': 21.214296, 'swap': 0.0, 'combine': 20.010964,
             'unscoped': 6.9217e-04, 'dispatch': 0.756443}),
}


@pytest.fixture(scope='module')
def unnamed():
    return (tracelib.from_xplane(UNNAMED, 13, CALLS),
            scoped.from_xplane(UNNAMED, 13, CALLS))


@pytest.fixture(scope='module', params=sorted(SCOPED))
def recorded(request):
    steps, want = SCOPED[request.param]
    path = os.path.join(DATA, request.param)
    return (tracelib.from_xplane(path, steps, CALLS),
            scoped.from_xplane(path, steps, CALLS), want)


def same_reduction(old, new):
    """The same ops, kinds, times, window and benchmark spans as the
    ProfileData-based reduction."""
    assert list(old.devices) == list(new.devices) == [DEV]
    assert [(o.name, o.kind, o.start_ns, o.dur_ns) for o in old.devices[DEV]] \
        == [(o.name, o.kind, o.start_ns, o.dur_ns) for o in new.devices[DEV]]
    assert new.window == old.window
    assert [h for h in new.host if not h[0].startswith('fft.')] == old.host
    assert new.busy_ns(DEV) == old.busy_ns(DEV)


def test_reader_matches_jax_on_an_unnamed_program(unnamed):
    """On a program that names nothing every existing reading is
    unchanged, the breakdown too."""
    old, new = unnamed
    same_reduction(old, new)
    assert new.host == old.host
    assert new.breakdown() == old.breakdown()


def test_reader_matches_jax_on_the_scoped_program(recorded):
    old, new, _ = recorded
    same_reduction(old, new)
    assert {n for n, _, _ in new.host} == {
        'bench.window', 'dispatch.forward', 'dispatch.inverse', 'wait',
        'fft.apply.fwd', 'fft.apply.inv'}


def test_parts_of_the_scoped_program(recorded):
    """Each part in ms a pair; on one chip (one op at a time) the parts
    partition the busy time, and nearly every op carries a name."""
    _, t, want = recorded
    got = {p: scoped.part_ms(t, p) for p in scoped.PARTS}
    assert got == pytest.approx({p: want[p] for p in scoped.PARTS},
                                rel=1e-5, abs=1e-8)
    busy = t.busy_ns(DEV) / 1e6 / t.steps
    assert sum(got.values()) == pytest.approx(busy, rel=1e-6)
    assert got['unscoped'] <= 0.01 * busy
    assert scoped.dispatch_ms(t) == pytest.approx(want['dispatch'], rel=1e-5)


def test_supersteps_of_the_scoped_program(recorded):
    """Three supersteps a transform, each with its axis pass; the
    boundary lies outside them."""
    _, t, _ = recorded
    table = scoped.supersteps(t)
    for call in CALLS:
        steps = {k.split()[1] for k in table if k.startswith(call + ' ')}
        assert steps == {'-', '0', '1', '2'}
        assert all(table[f'{call} {i} pass'] > 0 for i in range(3))
    busy = t.busy_ns(DEV) / 1e6 / t.steps
    assert sum(table.values()) == pytest.approx(busy, rel=1e-6)


def test_idle_gaps_are_named_by_the_innermost_span(recorded):
    """A gap opens while the host waits, or inside a plan call, never
    'inside a dispatch': the program's span is the innermost there."""
    _, t, _ = recorded
    names = {n for n, _ in t.breakdown(top=10 ** 6)['idle_gaps']}
    assert 'wait' in names
    assert names <= {'wait', 'fft.apply.fwd', 'fft.apply.inv'}


def test_c2c_parts_against_the_unnamed_records():
    """The attribution against the op table of the program before it had
    names: the complex split and combine 24.5 ms a pair, the kernel
    wrappers' reshapes and the fused pairs' transposes 19.5."""
    t = scoped.from_xplane(os.path.join(DATA, C2C), 13, CALLS)
    assert scoped.part_ms(t, 'boundary') == pytest.approx(24.5, rel=0.1)
    assert scoped.part_ms(t, 'relayout') == pytest.approx(19.5, rel=0.1)
    assert scoped.part_ms(t, 'pass') == pytest.approx(
        tracelib.union_ns(t.intervals(DEV, ('kernel',))) / 1e6 / 13)
    names = {n for n, _ in t.breakdown(top=10 ** 6)['idle_gaps']}
    assert names == {'wait'}


def test_name_stacks_of_an_unnamed_program(unnamed):
    """The op names the profile carries, per pair: the kernels, the
    kernel wrapper's reshapes, the complex split (named after the
    program's argument ``x``) and combine, the fused pair's transposes;
    none is a scope of the program, so every scoped reading is None."""
    _, t = unnamed
    per = {}
    for o in t.ops(DEV):
        per[o.scope] = per.get(o.scope, 0.0) + o.dur_ns / 1e6 / 13
    assert per == pytest.approx({
        'jit(run_complex)/jit(fft_matmul)/pallas_call': 36.448,
        'jit(run_complex)/jit(fft_matmul)/reshape': 16.213,
        'x': 12.284,
        'jit(run_complex)/complex': 12.238,
        'jit(run_complex)/transpose': 3.260,
        '': 0.0}, abs=1e-3)
    assert not scoped.scoped(t)
    assert all(scoped.part_ms(t, p) is None for p in scoped.PARTS)
    assert scoped.dispatch_ms(t) is None
    assert scoped.supersteps(t) is None


@pytest.mark.parametrize('scope, text, part, step', [
    ('jit(run_complex)/fft.boundary/complex', '', 'boundary', None),
    # XLA's ops on the complex argument and result: named after the
    # argument, or not at all
    ('x', '%custom-call = f32[8]{0} custom-call(c64[8]{0} %x), '
     'custom_call_target="X64SplitHigh"', 'boundary', None),
    ('', '%custom-call = c64[8]{0} custom-call(f32[8]{0} %a, f32[8]{0} %b)'
     ', custom_call_target="X64Combine"', 'boundary', None),
    ('', '%copy.45 = c64[8]{0} copy(c64[8]{0} %custom-call)', 'boundary',
     None),
    ('x', '%copy = f32[8]{0} copy(f32[8]{0} %x)', 'boundary', None),
    ('gather', '%fusion.3 = s32[8]{0} fusion(s32[8]{0} %p)', 'unscoped',
     None),
    # XLA's in-place join of pipelined chunks: nameless
    ('', '%constant_dynamic-update-slice_fusion.1 = f32[8,8]{1,0} fusion('
     'f32[8,8]{1,0} %f, f32[2,8]{1,0} %all_to_all.4), kind=kLoop',
     'relayout', None),
    ('', '%slice-done.3 = f32[2,8]{1,0} slice-done(f32[8,8]{1,0} %s)',
     'unscoped', None),
    ('jit(run_complex)/fft.step0/fft.pass/jit(fft_matmul)/pallas_call', '',
     'pass', 0),
    ('jit(run_complex)/fft.step0/fft.pass/jit(fft_matmul)/fft.relayout/'
     'reshape', '', 'relayout', 0),
    ('jit(run_fwd)/fft.step0/fft.pass/fft.combine/jit(_take)/gather', '',
     'combine', 0),
    ('jit(run_complex)/shard_map/fft.step1/fft.swap/all_to_all', '', 'swap',
     1),
    ('jit(run_complex)/fft.step2/fft.relayout/transpose;'
     'jit(run_complex)/fft.step1/fft.pass/fft.relayout/transpose', '',
     'relayout', 2),
    ('jit(run_complex)/fft.step1/mul', '', 'unscoped', 1),
    ('jit(run_complex)/transpose', '', 'unscoped', None),
    ('', '%copy-done = f32[8]{0} copy-done(f32[8]{0} %c)', 'unscoped', None),
])
def test_attribution(scope, text, part, step):
    assert scoped.part(scope, text) == part
    assert scoped.step(scope) == step


def test_scoped_readings_on_a_synthetic_trace():
    """Parts are unions of their ops' intervals within the window, per
    step; dispatch is the host time inside the plan's calls."""
    ops = [scoped.ScopedOp('forward:%a fusion', 'compute', 0.0, 10.0,
                           'jit(f)/fft.boundary/real', 'boundary'),
           scoped.ScopedOp('forward:%k custom-call tpu_custom_call',
                           'kernel', 10.0, 30.0, 'jit(f)/fft.step0/fft.pass/'
                           'pallas_call', 'pass'),
           scoped.ScopedOp('inverse:%c copy', 'compute', 40.0, 20.0,
                           'jit(f)/fft.step1/fft.relayout/copy', 'relayout'),
           scoped.ScopedOp('inverse:%d copy-done', 'compute', 60.0, 5.0)]
    host = [('bench.window', 0.0, 100.0), ('fft.apply.fwd', 0.0, 4.0),
            ('fft.apply.inv', 3.0, 3.0), ('wait', 6.0, 94.0)]
    t = tracelib.Trace(steps=2, window=(0.0, 100.0), devices={DEV: ops},
                       host=host)
    ms = {p: scoped.part_ms(t, p) for p in scoped.PARTS}
    assert ms == pytest.approx({'boundary': 5e-6, 'pass': 15e-6,
                                'relayout': 10e-6, 'swap': 0.0,
                                'combine': 0.0, 'unscoped': 2.5e-6})
    assert sum(ms.values()) == pytest.approx(t.busy_ns(DEV) / 1e6 / 2)
    assert scoped.dispatch_ms(t) == pytest.approx(3e-6)
    assert scoped.supersteps(t) == pytest.approx({
        'forward - boundary': 5e-6, 'forward 0 pass': 15e-6,
        'inverse - unscoped': 2.5e-6, 'inverse 1 relayout': 10e-6})
    # the gap after the last op opened while the host waited
    assert t.breakdown()['idle_gaps'][0][0] == 'wait'


def test_record_without_a_tpu_records_nothing(tmp_path):
    out = tmp_path / 'p.xplane.pb'
    p = subprocess.run(
        [sys.executable, 'bench/record.py', '--workload', 'c2c512-pair-1chip',
         '--seed', '1', '--seconds', '1', '--out', str(out)],
        cwd=harness.ROOT, env=dict(os.environ, JAX_PLATFORMS='cpu'),
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 1
    assert p.stdout.strip() == '' and not out.exists()
    assert 'no TPU' in p.stderr
