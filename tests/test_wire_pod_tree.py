"""Half-precision wire formats and cost-searched pod-tree trees.

Fast, single-device: tree-spec parsing/canonicalization, the bounded
factorization enumeration and its cost-dominance guarantee over the
fixed two-phase split (deterministic sweeps plus hypothesis variants
when available), the wire-format helpers, the plan facade's option
round trip through ``FFT.with_options`` (regression: every resolved
comm/dtype option must survive a re-plan), and the serving schedule
table's wire tag. The 16-fake-device fp16/bf16 accuracy gate runs in
a subprocess (see _wire_accuracy_worker.py).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro import comm
from repro.launch.mesh import make_abstract_mesh
from repro.comm import cost as ccost
from repro.comm import strategies as strat
from repro.core import wse_model as wm
from repro.core.plan import PencilPlan

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _abstract_mesh(*sizes, names=('x', 'y')):
    sharding = pytest.importorskip("jax.sharding")
    if not hasattr(sharding, 'AbstractMesh'):
        pytest.skip("jax.sharding.AbstractMesh unavailable")
    return make_abstract_mesh(sizes, names)


# ---------------------------------------------------------------------------
# tree-spec parsing / canonical naming
# ---------------------------------------------------------------------------

def test_parse_format_tree_spec_roundtrip():
    tree = strat.parse_tree_spec('x.4*y.2*y.2')
    assert tree == {'x': (4,), 'y': (2, 2)}
    assert strat.format_tree_spec(tree) == 'x.4*y.2*y.2'
    # axis order in the spec does not matter; the format is canonical
    assert (strat.format_tree_spec(strat.parse_tree_spec('y.2*x.4*y.2'))
            == 'x.4*y.2*y.2')


@pytest.mark.parametrize('bad', ['', 'x', 'x.1', 'x.0', 'x.-2', 'x.a',
                                 'x.2*', '.4'])
def test_parse_tree_spec_rejects(bad):
    with pytest.raises(ValueError):
        strat.parse_tree_spec(bad)


def test_validate_canonicalizes_pod_tree_names():
    assert (comm.validate('pod_tree:y.2*x.4*y.2')
            == 'pod_tree:x.4*y.2*y.2')
    # registered names and 'auto' pass through unchanged
    assert comm.validate('hierarchical') == 'hierarchical'
    assert comm.validate('auto') == 'auto'
    with pytest.raises(ValueError):
        comm.validate('pod_tree:nope')
    with pytest.raises(ValueError):
        comm.validate('no_such_strategy')


def test_pod_tree_strategies_share_one_instance():
    a = comm.get('pod_tree:x.4*y.2*y.2')
    b = comm.get('pod_tree:y.2*x.4*y.2')    # same tree, scrambled spec
    assert a.name == b.name == 'pod_tree:x.4*y.2*y.2'
    assert a.tree == b.tree == {'x': (4,), 'y': (2, 2)}


# ---------------------------------------------------------------------------
# wire-format helpers
# ---------------------------------------------------------------------------

def test_validate_wire_dtype():
    for wd in strat.WIRE_DTYPES:
        assert strat.validate_wire_dtype(wd) == wd
    with pytest.raises(ValueError):
        strat.validate_wire_dtype('fp8')


def test_wire_elem_bytes():
    assert strat.wire_elem_bytes('native', 4) == 4
    assert strat.wire_elem_bytes('native', 8) == 8
    assert strat.wire_elem_bytes('fp16', 4) == 2
    assert strat.wire_elem_bytes('bf16', 8) == 2
    # a compact wire never *widens* an already-narrow component
    assert strat.wire_elem_bytes('fp16', 2) == 2


def test_wire_cast_restore_semantics():
    x = jnp.arange(8, dtype=jnp.float32)
    w, restore = strat.wire_cast(x, 'fp16')
    assert w.dtype == jnp.float16 and restore == jnp.float32
    assert strat.wire_restore(w, restore).dtype == jnp.float32
    # native: no cast, nothing to restore
    w, restore = strat.wire_cast(x, 'native')
    assert w is x and restore is None
    assert strat.wire_restore(w, restore) is w
    # operands already at (or below) wire width pass through untouched
    nar = jnp.arange(8, dtype=jnp.bfloat16)
    w, restore = strat.wire_cast(nar, 'fp16')
    assert w is nar and restore is None
    # non-float operands (index/bool payloads) are never cast
    ints = jnp.arange(8, dtype=jnp.int32)
    w, restore = strat.wire_cast(ints, 'fp16')
    assert w is ints and restore is None


def test_pencil_plan_rejects_unknown_wire_dtype():
    mesh = _abstract_mesh(4, 4)
    p = PencilPlan(shape=(32, 32, 32), mesh=mesh, layout=('x', 'y', None),
                   wire_dtype='fp8')
    with pytest.raises(ValueError, match='wire_dtype'):
        p.validate()


# ---------------------------------------------------------------------------
# factorization enumeration (the pod-tree search space)
# ---------------------------------------------------------------------------

def _check_factorizations(extent, depth):
    seqs = ccost.enumerate_axis_factorizations(extent, depth)
    assert len(set(seqs)) == len(seqs)
    for fs in seqs:
        assert 1 <= len(fs) <= depth or (extent == 1 and fs == ())
        prod = 1
        for f in fs:
            assert f >= 2
            prod *= f
        assert prod == extent
    if extent > 1:
        # the single-level (full all_to_all) split always leads
        assert seqs[0] == (extent,)


@pytest.mark.parametrize('extent', [1, 2, 4, 8, 16, 32, 64, 256])
@pytest.mark.parametrize('depth', [1, 2, 3, 4])
def test_enumerate_axis_factorizations_properties(extent, depth):
    _check_factorizations(extent, depth)


def test_enumerate_trees_properties():
    for mesh_shape in ({'x': 4, 'y': 4}, {'x': 8, 'y': 2},
                       {'x': 16, 'y': 1}, {'x': 2, 'y': 2}):
        names = ccost.enumerate_trees(tuple(mesh_shape), mesh_shape)
        assert 0 < len(names) <= ccost.POD_TREE_MAX_TREES
        assert len(set(names)) == len(names)
        for name in names:
            assert name.startswith(strat.POD_TREE_PREFIX)
            tree = strat.parse_tree_spec(name[len(strat.POD_TREE_PREFIX):])
            for a, fs in tree.items():
                assert len(fs) <= ccost.POD_TREE_MAX_DEPTH
                assert np.prod(fs) == mesh_shape[a]
            # extent-1 axes never appear in a spec
            assert all(mesh_shape[a] > 1 for a in tree)
        # the first candidate is the all-full tree: one level per
        # (non-trivial) axis, i.e. exactly the fixed two-phase split —
        # the search minimum can therefore never beat it by less than 0
        full = {a: (e,) for a, e in mesh_shape.items() if e > 1}
        assert names[0] == strat.POD_TREE_PREFIX + strat.format_tree_spec(
            full)


def test_tree_search_never_worse_than_two_phase():
    """The analytic search minimum is <= the fixed two-phase split's
    cost: 'hierarchical' prices as the all-full two-level tree, which
    is always in the candidate set."""
    for shape, layout, mesh_shape in (
            ((32, 32, 32), ('x', 'y', None), {'x': 4, 'y': 4}),
            ((64, 64, 64), ('x', 'y', None), {'x': 8, 'y': 8}),
            ((512, 512, 512), ('x', 'y', None), {'x': 512, 'y': 512})):
        sel = ccost.select(shape, layout, mesh_shape, measured=None,
                           pod_trees=True)
        hier = sel.costs['hierarchical'].cycles
        assert sel.costs[sel.strategy].cycles <= hier + 1e-9, (
            shape, mesh_shape, sel.strategy)


def test_tree_candidates_policy():
    mesh_shape = {'x': 4, 'y': 4}
    assert ccost._tree_candidates(mesh_shape, None, False) == ()
    full = ccost._tree_candidates(mesh_shape, None, True)
    assert full and all(n.startswith(strat.POD_TREE_PREFIX) for n in full)
    # default: only trees the measured table has rows for on this mesh
    tbl = ccost.MeasuredTable([
        {'mesh': '4x4', 'group': 'x*y', 'strategy': 'pod_tree:x.2*x.2*y.4',
         'local_elems': 1024, 'us': 10.0},
        {'mesh': '4x4', 'group': 'x*y', 'strategy': 'all_to_all',
         'local_elems': 1024, 'us': 12.0},
    ])
    got = ccost._tree_candidates(mesh_shape, tbl, None)
    assert got == ('pod_tree:x.2*x.2*y.4',)
    assert ccost._tree_candidates({'x': 8, 'y': 2}, tbl, None) == ()


# hypothesis variants ------------------------------------------------------

def test_factorization_properties_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(deadline=None, max_examples=80)
    @hyp.given(k=st.integers(0, 10), depth=st.integers(1, 4))
    def run(k, depth):
        _check_factorizations(2 ** k, depth)

    run()


def test_tree_cost_dominance_hypothesis():
    """Min modeled swap cost over the enumerated trees of a mesh axis
    group never exceeds the two-phase hierarchical split's."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(deadline=None, max_examples=60)
    @hyp.given(kx=st.integers(1, 6), ky=st.integers(1, 6),
               loge=st.integers(6, 20))
    def run(kx, ky, loge):
        mesh_shape = {'x': 2 ** kx, 'y': 2 ** ky}
        elems = float(2 ** loge)
        hier = comm.get('hierarchical').cost(
            ('x', 'y'), mesh_shape, elems, 'fp32').cycles
        best = min(
            comm.get(name).cost(('x', 'y'), mesh_shape, elems,
                                'fp32').cycles
            for name in ccost.enumerate_trees(('x', 'y'), mesh_shape))
        assert best <= hier + 1e-9

    run()


# ---------------------------------------------------------------------------
# cost model: trees and wire formats
# ---------------------------------------------------------------------------

def test_swap_cycles_tree_generalizes_hierarchical():
    for p1, p2, elems in ((4, 4, 2048), (8, 8, 65536), (512, 512, 2 ** 18)):
        levels = ((p1, 'a2a', 1.0), (p2, 'a2a', 1.0))
        assert (wm.swap_cycles_tree(levels, elems, 'fp32')
                == wm.swap_cycles_hierarchical(p1, p2, elems, 'fp32'))
    # a single full level prices as plain a2a plus no reorder term
    one = wm.swap_cycles_tree(((16, 'a2a', 1.0),), 4096, 'fp32')
    assert one == wm.swap_cycles_a2a(16, 4096, 'fp32')


def test_wire_dtype_halves_analytic_wire_term():
    """fp16 wire prices every swap's wire term at r=1 (the paper packs
    an fp16 (re, im) pair in one 32-bit wavelet) — the analytic cost
    must strictly drop vs fp32 native wire."""
    pc32 = ccost.pencil_plan_cost((32, 32, 32), ('x', 'y', None),
                                  {'x': 4, 'y': 4}, measured=None)
    pc16 = ccost.pencil_plan_cost((32, 32, 32), ('x', 'y', None),
                                  {'x': 4, 'y': 4}, measured=None,
                                  wire_dtype='fp16')
    assert pc16.wire_dtype == 'fp16'
    sw32 = [s for s in pc32.steps if s.kind == 'swap']
    sw16 = [s for s in pc16.steps if s.kind == 'swap']
    assert len(sw32) == len(sw16)
    for a, b in zip(sw32, sw16):
        assert b.swap.wire_cycles < a.swap.wire_cycles
        assert 'wire=fp16' in b.detail


def test_cost_report_shows_tree_and_wire_bytes():
    mesh = _abstract_mesh(4, 4)
    import repro.fft as fft
    p = fft.plan((32, 32, 32), mesh, comm='pod_tree:x.2*x.2*y.4',
                 wire_dtype='fp16')
    rep = p.cost_report()
    assert 'wire_dtype=fp16' in rep
    assert 'pod tree: x: 4 -> 2x2  y: 4 -> 4' in rep
    assert 'KiB/dev wire' in rep
    # per-superstep wire bytes: 32^3/16 elems/dev, 2 components x 2 B
    assert '8.0 KiB/dev wire' in rep


def test_schedule_table_wire_tag():
    mk = dict(mesh='4x4', shape='32x32x32', kind='complex',
              strategy='all_to_all', coalesce_width=8, overlap_chunks=2,
              us_per_request=10.0)
    wired = dict(mk, wire='fp16', coalesce_width=16, us_per_request=8.0)
    tbl = ccost.ScheduleTable([mk, wired])
    assert len(tbl) == 2            # distinct keys, no clobbering
    ms = {'x': 4, 'y': 4}
    nat = tbl.lookup(ms, (32, 32, 32), 'complex', 'all_to_all')
    assert nat is not None and nat['coalesce_width'] == 8
    f16 = tbl.lookup(ms, (32, 32, 32), 'complex', 'all_to_all',
                     wire='fp16')
    assert f16 is not None and f16['coalesce_width'] == 16
    # a bf16 lookup has no measured row — no silent cross-wire answers
    assert tbl.lookup(ms, (32, 32, 32), 'complex', 'all_to_all',
                      wire='bf16') is None


# ---------------------------------------------------------------------------
# facade: option round trip (regression) and wire selection
# ---------------------------------------------------------------------------

def test_with_options_roundtrips_comm_and_dtype_options():
    """Regression: every resolved non-default option — strategy
    (including parameterized pod trees), wire format, compute dtype,
    method, overlap depth — must survive ``with_options`` re-plans."""
    import repro.fft as fft
    mesh = _abstract_mesh(4, 4)
    p = fft.plan((32, 32, 32), mesh, comm='pod_tree:x.4*y.2*y.2',
                 wire_dtype='fp16', compute_dtype=jnp.bfloat16,
                 method='stockham', overlap_chunks=2)
    q = p.with_options(donate=False)
    assert q.comm == p.comm == 'pod_tree:x.4*y.2*y.2'
    assert q.wire_dtype == 'fp16'
    assert q.compute_dtype == jnp.bfloat16
    assert q.method == 'stockham'
    assert q.overlap_chunks == 2
    assert q.donate is False
    # the override wins without disturbing its neighbors
    r = q.with_options(wire_dtype='bf16')
    assert r.wire_dtype == 'bf16' and r.comm == p.comm
    # the executor plan carries the wire format too
    assert p._pplan.wire_dtype == 'fp16'
    # rank-1 plans round-trip the same set
    p1 = fft.plan((4096,), mesh, comm='hierarchical', wire_dtype='bf16',
                  compute_dtype=jnp.bfloat16)
    q1 = p1.with_options(overlap_chunks=4)
    assert (q1.comm, q1.wire_dtype, q1.compute_dtype,
            q1.overlap_chunks) == ('hierarchical', 'bf16', jnp.bfloat16, 4)
    # real <-> complex re-plans keep the wire format as well
    pr = fft.rplan((32, 32, 32), mesh, comm='hierarchical',
                   wire_dtype='fp16')
    qc = pr.with_options(real=False)
    assert qc.wire_dtype == 'fp16' and qc.comm == 'hierarchical'


def test_plan_rejects_unknown_wire_dtype():
    import repro.fft as fft
    mesh = _abstract_mesh(4, 4)
    with pytest.raises(ValueError, match='wire_dtype'):
        fft.plan((32, 32, 32), mesh, wire_dtype='fp8')


# ---------------------------------------------------------------------------
# kernel tier: plan option, deprecated alias, schedule-table tag
# ---------------------------------------------------------------------------

def test_with_options_roundtrips_kernel_tier():
    """Regression: the kernel tier survives ``with_options`` re-plans,
    like comm/wire/compute-dtype (same contract, same test shape)."""
    import repro.fft as fft
    mesh = _abstract_mesh(4, 4)
    p = fft.plan((32, 32, 32), mesh, method='stockham', kernel='pallas')
    q = p.with_options(donate=False)
    assert q.kernel == p.kernel == 'pallas'
    assert q.resolved_kernel == 'pallas'
    r = q.with_options(kernel='reference')
    assert r.kernel == 'reference' and r.comm == p.comm
    assert 'pallas' in repr(p)
    # rank-1 and real plans carry the tier too
    p1 = fft.plan((4096,), mesh, kernel='pallas')
    assert p1.with_options(overlap_chunks=4).kernel == 'pallas'
    pr = fft.rplan((32, 32, 32), mesh, kernel='pallas')
    assert pr.with_options(real=False).kernel == 'pallas'
    # 'auto' resolves to 'reference' on this CPU host
    pa = fft.plan((32, 32, 32), mesh)
    assert pa.kernel == 'auto' and pa.resolved_kernel == 'reference'


def test_resolved_kernel_covers_every_axis(monkeypatch):
    """On a backend that lowers Pallas natively, 'auto' resolves each
    pencil length on its own: a plan reports the Pallas tier only when
    every axis runs it, and one axis past ``PALLAS_MAX_N`` (reference
    tier) makes the whole plan report 'reference'."""
    import repro.fft as fft
    from repro.fft import methods
    monkeypatch.setattr(methods, 'backend', lambda: 'tpu')
    mesh = _abstract_mesh(4, 4)
    n = methods.PALLAS_MAX_N
    assert fft.plan((n, 64, 64), mesh).resolved_kernel == 'pallas'
    for shape in ((2 * n, 64, 64), (64, 2 * n, 64), (64, 64, 2 * n)):
        assert fft.plan(shape, mesh).resolved_kernel == 'reference', shape


def test_resolved_kernel_real_axis_at_half_length(monkeypatch):
    """A real plan's real axis runs its complex FFT at half length, and
    resolves its tier there, as ``methods.apply_real`` does: where only
    the half length fits ``PALLAS_MAX_N`` the real plan is on the Pallas
    tier and the complex plan of the same shape is not."""
    import repro.fft as fft
    from repro.fft import methods
    monkeypatch.setattr(methods, 'backend', lambda: 'tpu')
    mesh = _abstract_mesh(4, 4)
    n = 2 * methods.PALLAS_MAX_N
    assert fft.rplan((64, 64, n), mesh).resolved_kernel == 'pallas'
    assert fft.plan((64, 64, n), mesh).resolved_kernel == 'reference'
    assert fft.rplan((64, 64, 2 * n), mesh).resolved_kernel == 'reference'
    assert fft.rplan((64, n, 64), mesh).resolved_kernel == 'reference'
    # rank 1: the real four-step's column factor n1 is the real axis
    n1d = n * methods.PALLAS_MAX_N
    assert fft.rplan((n1d,), mesh)._factors == (n, methods.PALLAS_MAX_N)
    assert fft.rplan((n1d,), mesh).resolved_kernel == 'pallas'
    assert fft.plan((n1d,), mesh).resolved_kernel == 'reference'


def test_plan_rejects_unknown_kernel_tier():
    import repro.fft as fft
    mesh = _abstract_mesh(4, 4)
    with pytest.raises(ValueError, match='kernel'):
        fft.plan((32, 32, 32), mesh, kernel='mosaic')


def test_use_kernel_deprecated_alias_warns_once_and_maps():
    import repro.fft as fft
    from repro.core import _deprecated
    mesh = _abstract_mesh(4, 4)
    _deprecated.reset('repro.fft.plan(use_kernel=)')
    with pytest.warns(DeprecationWarning, match="kernel='pallas'"):
        p = fft.plan((32, 32, 32), mesh, use_kernel=True)
    assert p.kernel == 'pallas'
    # one-shot: a second deprecated call stays silent
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        p2 = fft.plan((32, 32, 32), mesh, use_kernel=True)
    assert p2.kernel == 'pallas'
    # use_kernel=False is inert: the kernel option passes through
    p3 = fft.plan((32, 32, 32), mesh, kernel='reference', use_kernel=False)
    assert p3.kernel == 'reference'


def test_cost_report_shows_kernel_tier():
    import repro.fft as fft
    mesh = _abstract_mesh(4, 4)
    rep = fft.plan((32, 32, 32), mesh, method='stockham',
                   kernel='pallas').cost_report()
    assert 'kernel=pallas' in rep
    assert '(stockham/pallas)' in rep
    rep_ref = fft.plan((32, 32, 32), mesh, method='stockham').cost_report()
    assert 'kernel=reference' in rep_ref
    assert '(stockham/reference)' in rep_ref


def test_schedule_table_kernel_tag():
    """Kernel-tagged autotune rows answer only same-tier lookups —
    mirrors the wire-tag contract."""
    mk = dict(mesh='4x4', shape='32x32x32', kind='complex',
              strategy='all_to_all', coalesce_width=8, overlap_chunks=2,
              us_per_request=10.0)
    tiered = dict(mk, kernel='pallas', coalesce_width=4,
                  us_per_request=9.0)
    tbl = ccost.ScheduleTable([mk, tiered])
    assert len(tbl) == 2            # distinct keys, no clobbering
    ms = {'x': 4, 'y': 4}
    ref = tbl.lookup(ms, (32, 32, 32), 'complex', 'all_to_all')
    assert ref is not None and ref['coalesce_width'] == 8
    pal = tbl.lookup(ms, (32, 32, 32), 'complex', 'all_to_all',
                     kernel='pallas')
    assert pal is not None and pal['coalesce_width'] == 4
    # no measured row for an unknown tier — no silent cross-tier answers
    assert tbl.lookup(ms, (32, 32, 32), 'complex', 'all_to_all',
                      kernel='mosaic') is None
    # wire and kernel tags compose into one key space
    both = dict(mk, wire='fp16', kernel='pallas', coalesce_width=16)
    tbl2 = ccost.ScheduleTable([mk, tiered, both])
    assert len(tbl2) == 3
    hit = tbl2.lookup(ms, (32, 32, 32), 'complex', 'all_to_all',
                      wire='fp16', kernel='pallas')
    assert hit is not None and hit['coalesce_width'] == 16
    assert tbl2.lookup(ms, (32, 32, 32), 'complex', 'all_to_all',
                       wire='fp16') is None


def test_auto_select_with_measured_tree_prefers_it():
    """select(): a pod tree with (much faster) measured rows on this
    mesh wins comm='auto'; without measured rows no tree is even
    considered (paper-faithful default ranking)."""
    mesh_shape = {'x': 4, 'y': 4}
    tree = 'pod_tree:x.4*y.2*y.2'
    rows = [{'mesh': '4x4', 'group': g, 'strategy': s,
             'local_elems': e, 'us': us}
            for g in ('x', 'y', 'x*y')
            for e in (256, 8192)
            for s, us in ((tree, 1.0), ('all_to_all', 50.0))]
    tbl = ccost.MeasuredTable(rows)
    sel = ccost.select((32, 32, 32), ('x', 'y', None), mesh_shape,
                       measured=tbl)
    assert sel.strategy == tree
    sel_none = ccost.select((32, 32, 32), ('x', 'y', None), mesh_shape,
                            measured=None)
    assert not sel_none.strategy.startswith(strat.POD_TREE_PREFIX)


# ---------------------------------------------------------------------------
# 16-device accuracy gate (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_wire_accuracy_worker_16_devices():
    """fp16/bf16 wire error bounds vs the fp32 native-wire reference,
    and native-wire bit-identity, for ranks 1/2/3 across strategies
    and pod trees — on 16 fake devices."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "_wire_accuracy_worker.py")],
        capture_output=True, text=True, env=env, timeout=1200)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "WIRE_WORKER_OK" in proc.stdout
