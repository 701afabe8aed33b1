"""Properties of the repro.comm redistribution engine.

In-process: plan_swaps minimality (independent BFS distance oracle,
hypothesis-driven when available), cost-model invariants, and the
acceptance check that the cost report for the paper's 512^3/FP32
config reproduces the Table-1 per-superstep cycle structure from
wse_model. The 16-fake-device strategy equivalence / round-trip matrix
runs in a subprocess (see _comm_worker.py)."""
import itertools
import os
import subprocess
import sys

import pytest

from repro import comm
from repro.launch.mesh import make_abstract_mesh
from repro.comm import cost as ccost
from repro.core import plan as planlib
from repro.core import wse_model as wm

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------------
# plan_swaps minimality
# ---------------------------------------------------------------------------

def _bfs_distance(src, dst, axes):
    """Independent oracle: true minimal number of swaps src -> dst."""
    if src == dst:
        return 0
    frontier, seen, d = {src}, {src}, 0
    while frontier:
        d += 1
        nxt = set()
        for st in frontier:
            for ax in axes:
                for mp in planlib.memory_axes(st):
                    st2 = planlib.swap(st, ax, mp)
                    if st2 == dst:
                        return d
                    if st2 not in seen:
                        seen.add(st2)
                        nxt.add(st2)
        frontier = nxt
    raise AssertionError(f"unreachable {src} -> {dst}")


def _all_layouts(ndim, axes):
    out = []
    for owners in itertools.permutations(tuple(axes) + (None,) * ndim, ndim):
        if all(a in owners for a in axes):
            out.append(tuple(owners))
    return sorted(set(out), key=str)


def _check_minimal(src, dst):
    axes = sorted({o for o in src if o is not None}, key=str)
    path = planlib.plan_swaps(src, dst)
    lay = src
    for ax, mp in path:
        assert lay[mp] is None           # every step swaps a memory axis
        lay = planlib.swap(lay, ax, mp)
    assert lay == dst                    # the path reaches dst
    assert len(path) == _bfs_distance(src, dst, axes)   # and is minimal


def test_plan_swaps_minimal_exhaustive_3d():
    layouts = _all_layouts(3, ('x', 'y'))
    for src in layouts:
        for dst in layouts:
            _check_minimal(src, dst)


def test_plan_swaps_minimal_exhaustive_2d():
    layouts = _all_layouts(2, (('x', 'y'),))
    for src in layouts:
        for dst in layouts:
            _check_minimal(src, dst)


def test_plan_swaps_minimal_hypothesis_4d():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    layouts = _all_layouts(4, ('x', 'y'))

    @hyp.given(st.sampled_from(layouts), st.sampled_from(layouts))
    @hyp.settings(deadline=None, max_examples=60)
    def prop(src, dst):
        _check_minimal(src, dst)

    prop()


# ---------------------------------------------------------------------------
# Strategy registry + cost-model invariants
# ---------------------------------------------------------------------------

def test_registry_contents():
    assert set(comm.names()) >= {'all_to_all', 'ppermute', 'hierarchical'}
    with pytest.raises(ValueError, match='unknown comm strategy'):
        comm.get('nope')
    assert comm.validate('auto') == 'auto'
    # below the plan layer, 'auto' resolves to the default schedule
    assert comm.resolve('auto').name == comm.DEFAULT_STRATEGY
    assert comm.resolve('ppermute').name == 'ppermute'


def test_make_fft_executes_with_auto_comm():
    """A PencilPlan carrying comm='auto' must execute, not just build
    (the executor resolves 'auto' to the default strategy)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.plan import PencilPlan
    from repro.fft import pencil
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ('x', 'y'))
    plan = PencilPlan(shape=(8, 8, 8), mesh=mesh, layout=('x', 'y', None),
                      comm='auto')
    fn, _, _ = pencil.make_fft(plan)
    x = np.random.default_rng(0).standard_normal((8, 8, 8))
    yr, yi = fn(jnp.asarray(x, jnp.float32), jnp.zeros((8, 8, 8), jnp.float32))
    want = np.fft.fftn(x)
    got = np.asarray(yr, np.float64) + 1j * np.asarray(yi, np.float64)
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 3e-4


def test_a2a_cost_is_eq1():
    """The all_to_all strategy cost IS the paper's Eq. 1 at pencil
    granularity: p = n/m devices, elems = n*m^2."""
    st = comm.get('all_to_all')
    for n, m in ((512, 1), (256, 2), (64, 4)):
        p = n // m
        sc = st.cost('x', {'x': p}, n * m * m, 'fp32')
        assert sc.cycles == pytest.approx(wm.tt_comm(n, m, 'fp32'))


def test_cost_orderings():
    """Structural properties the selector relies on: the ring halves
    the wire term but pays per-round launches; the pod-split pays two
    small exchanges instead of one wide one."""
    shape = {'x': 32, 'y': 32}
    for elems in (64, 4096, 1 << 20):
        a2a = comm.get('all_to_all').cost(('x', 'y'), shape, elems, 'fp32')
        ring = comm.get('ppermute').cost(('x', 'y'), shape, elems, 'fp32')
        hier = comm.get('hierarchical').cost(('x', 'y'), shape, elems, 'fp32')
        assert ring.wire_cycles < a2a.wire_cycles
        assert ring.fixed_cycles > a2a.fixed_cycles
        assert hier.p == a2a.p == ring.p == 1024
    # tiny messages: latency-bound -> all_to_all wins over the ring
    small = {s.strategy: s.cycles for s in (
        comm.get(n).cost(('x', 'y'), shape, 32, 'fp32')
        for n in comm.names())}
    assert small['all_to_all'] < small['ppermute']
    # huge messages: wire-bound -> the ring beats the one-shot a2a
    big = {s.strategy: s.cycles for s in (
        comm.get(n).cost(('x', 'y'), shape, 1 << 22, 'fp32')
        for n in comm.names())}
    assert big['ppermute'] < big['all_to_all']


def test_select_paper_config_stays_paper_faithful():
    """At the paper's m=1 single-pencil granularity the broadcast-and-
    filter all_to_all must win (the ring's per-round launches dominate
    its halved wire term)."""
    sel = ccost.select((512,) * 3, ('x', 'y', None), {'x': 512, 'y': 512},
                       precision='fp32')
    assert sel.strategy == 'all_to_all'
    assert sel.overlap_chunks == 1      # m=1: no free local axis to chunk


def test_select_method_matches_registry_rule():
    from repro.fft import methods
    for n in (8, 16, 32, 64, 128, 512, 4096):
        assert ccost.select_method(n, 'fp32') == methods.resolve('auto', n).name
    assert ccost.select_method(12) == 'direct'


# ---------------------------------------------------------------------------
# Acceptance: Table-1 per-superstep structure from the cost report
# ---------------------------------------------------------------------------

def test_cost_report_512_fp32_reproduces_table1_structure():
    pc = ccost.pencil_plan_cost((512,) * 3, ('x', 'y', None),
                                {'x': 512, 'y': 512}, precision='fp32',
                                method='stockham', strategy='all_to_all')
    kinds = [s.kind for s in pc.steps]
    assert kinds == ['fft', 'swap', 'fft', 'swap', 'fft']
    for s in pc.steps:
        if s.kind == 'fft':
            assert s.cycles == pytest.approx(wm.pencil_cycles(512, 'fp32'))
        else:
            assert s.cycles == pytest.approx(wm.tt_comm(512, 1, 'fp32'))
    assert pc.serial_cycles == pytest.approx(
        wm.total_cycles_model(512, 1, 'fp32'))
    # same tolerance the model-vs-paper test uses: within 30% of the
    # measured Table-1 cycles, always a lower bound
    meas = wm.TABLE1_CYCLES[512]['fp32']
    assert -0.30 < (pc.serial_cycles - meas) / meas < 0.0
    # the formatted report carries the comparison
    rep = ccost.format_report(pc, (512,) * 3, {'x': 512, 'y': 512})
    assert 'Table 1' in rep and str(meas) in rep


def test_cost_report_via_abstract_mesh_facade():
    """fft.plan on an AbstractMesh prices the paper config without
    devices; .cost_report() is the user-facing acceptance surface."""
    from jax import sharding
    if not hasattr(sharding, 'AbstractMesh'):
        pytest.skip("jax.sharding.AbstractMesh unavailable")
    mesh = make_abstract_mesh((512, 512), ('x', 'y'))
    import repro.fft as fft
    p = fft.plan((512,) * 3, mesh, method='stockham', comm='all_to_all')
    pc = p.plan_cost('fp32')
    assert pc.serial_cycles == pytest.approx(
        wm.total_cycles_model(512, 1, 'fp32'))
    rep = p.cost_report('fp32')
    assert 'wse_model' in rep and 'Table 1' in rep


# ---------------------------------------------------------------------------
# 16-device strategy matrix (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_comm_worker_16_devices():
    """Strategy bit-exactness vs the all_to_all reference, redistribute
    round trips for random layouts, the facade matrix under every
    strategy, and overlap-pipeline equivalence — on 16 fake devices."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_comm_worker.py")],
        capture_output=True, text=True, env=env, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "COMM_WORKER_OK" in proc.stdout
