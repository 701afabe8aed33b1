"""Real-input (rfft/irfft) plans: local correctness, the half-spectrum
cost model, the measured-cost autotune table, and facade validation.

Single-device tests run in-process on a 1x1 mesh; the 16-fake-device
matrix (ranks x strategies x methods x shardings x padded mode) runs in
a subprocess (see _rfft_worker.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.fft as fft
from repro.comm import cost as ccost
from repro.core import wse_model as wm
from repro.fft import methods, pencil
from repro.launch.mesh import make_abstract_mesh, make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RNG = np.random.default_rng(23)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


# ---------------------------------------------------------------------------
# Local r2c/c2r machinery
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["stockham", "four_step", "block",
                                    "direct", "auto"])
def test_apply_real_matches_numpy(method):
    x = RNG.standard_normal((3, 64)).astype(np.float32)
    yr, yi = methods.apply_real(jnp.asarray(x), method=method)
    want = np.fft.rfft(x, axis=-1)
    got = np.asarray(yr, np.float64) + 1j * np.asarray(yi, np.float64)
    np.testing.assert_allclose(got, want, atol=3e-4 * np.max(np.abs(want)))
    # bins 0 and n/2 have exactly-zero imaginary parts by construction
    assert np.all(np.asarray(yi)[:, 0] == 0)
    assert np.all(np.asarray(yi)[:, -1] == 0)
    back = methods.apply_real(yr, yi, inverse=True, method=method)
    np.testing.assert_allclose(np.asarray(back), x, atol=1e-4)


def _reverse_readers(closed_jaxpr):
    """Primitive names of the equations that read a reversed array: the
    output of a ``rev``, or of a jitted call that is nothing but one
    (``jnp.flip``), searched through nested jaxprs."""
    from jax.core import jaxprs_in_params
    from jax.extend.core import Literal

    def only_rev(eqn):
        subs = list(jaxprs_in_params(eqn.params))
        return (eqn.primitive.name == "rev"
                or (len(subs) == 1
                    and [e.primitive.name for e in subs[0].eqns] == ["rev"]))

    readers = []

    def walk(jaxpr):
        revs = set()
        for eqn in jaxpr.eqns:
            readers.extend(eqn.primitive.name for v in eqn.invars
                           if not isinstance(v, Literal) and v in revs)
            if only_rev(eqn):
                revs.update(eqn.outvars)
            else:
                for sub in jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(closed_jaxpr.jaxpr)
    return readers


@pytest.mark.parametrize("n", [2, 4, 16, 64, 512])
@pytest.mark.parametrize("method", ["stockham", "four_step"])
def test_c2r_epilogue_has_no_reverse(n, method):
    """The c2r epilogue of the sharded irfft: padded half spectrum ->
    slice to n//2 + 1 bins -> inverse real transform. No reverse of the
    pencil axis is fused into its Hermitian combine (XLA:TPU computed
    that fused reverse wrongly at the 512^3 per-device shape): the
    mirrored bins pass an optimization barrier before any arithmetic
    reads them. It matches numpy.fft.irfft on a Hermitian half
    spectrum. (XLA:CPU drops the barrier when it fuses, so the compiled
    TPU program is checked in test_tpu_compile.py.)"""
    nh = n // 2 + 1
    spec = (RNG.standard_normal((3, 2, nh + 1))
            + 1j * RNG.standard_normal((3, 2, nh + 1))).astype(np.complex64)
    spec[..., [0, nh - 1]] = spec[..., [0, nh - 1]].real     # real DC, Nyquist

    def c2r(re, im):
        return methods.apply_real(re[..., :nh], im[..., :nh], axis=-1,
                                  inverse=True, method=method)

    re, im = jnp.asarray(spec.real), jnp.asarray(spec.imag)
    readers = _reverse_readers(jax.make_jaxpr(c2r)(re, im))
    assert readers and set(readers) == {"custom_vjp_call"}, readers
    got = np.asarray(jax.jit(c2r)(re, im), np.float64)
    want = np.fft.irfft(spec[..., :nh].astype(np.complex128), n=n, axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-6 * np.sqrt(n))


@pytest.mark.parametrize("n", [2, 4, 16, 64, 512])
@pytest.mark.parametrize("method", ["stockham", "four_step"])
def test_r2c_combine_has_no_reverse(n, method):
    """The forward's Hermitian combine reads its mirrored bins by a
    gather, with no reverse of the pencil axis anywhere, and matches
    numpy.fft.rfft."""
    x = RNG.standard_normal((3, 2, n)).astype(np.float32)

    def r2c(a):
        return methods.apply_real(a, axis=-1, method=method)

    xd = jnp.asarray(x)
    assert "rev" not in str(jax.make_jaxpr(r2c)(xd))
    assert "reverse(" not in jax.jit(r2c).lower(xd).compile().as_text()
    yr, yi = jax.jit(r2c)(xd)
    got = np.asarray(yr, np.float64) + 1j * np.asarray(yi, np.float64)
    want = np.fft.rfft(x.astype(np.float64), axis=-1)
    np.testing.assert_allclose(got, want, atol=2e-6 * np.sqrt(n))


def test_apply_real_axis_general():
    x = RNG.standard_normal((4, 16, 3)).astype(np.float32)
    yr, yi = methods.apply_real(jnp.asarray(x), axis=1)
    want = np.fft.rfft(x, axis=1)
    got = np.asarray(yr, np.float64) + 1j * np.asarray(yi, np.float64)
    np.testing.assert_allclose(got, want, atol=1e-4 * np.max(np.abs(want)))
    back = methods.apply_real(yr, yi, axis=1, inverse=True)
    np.testing.assert_allclose(np.asarray(back), x, atol=1e-4)


def test_apply_real_validation():
    x = jnp.zeros((4, 9))
    with pytest.raises(ValueError, match="even length"):
        methods.apply_real(x)
    with pytest.raises(ValueError, match="planar"):
        methods.apply_real(jnp.zeros((4, 5)), inverse=True)
    with pytest.raises(ValueError, match="ONE real array"):
        methods.apply_real(jnp.zeros((4, 8)), jnp.zeros((4, 8)))
    # every registered method carries a real_fn
    for name in methods.names():
        assert methods.get(name).real_fn is not None


# ---------------------------------------------------------------------------
# Facade round trips (1x1 mesh) + validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(256,), (16, 32), (8, 8, 8)])
@pytest.mark.parametrize("method", ["four_step", "stockham"])
def test_rplan_roundtrip(mesh, shape, method):
    x = RNG.standard_normal(shape).astype(np.float32)
    p = fft.rplan(shape, mesh, method=method)
    y = p.forward(jnp.asarray(x))
    rank = len(shape)
    want = np.fft.rfftn(x, axes=tuple(range(-rank, 0)))
    assert y.shape == p.spectrum_shape
    np.testing.assert_allclose(np.asarray(y, np.complex128), want,
                               atol=3e-4 * np.max(np.abs(want)))
    back = p.inverse(y)
    assert not np.iscomplexobj(np.asarray(back))
    np.testing.assert_allclose(np.asarray(back), x, atol=1e-4)
    nb = np.fft.irfftn(want, s=shape, axes=tuple(range(-rank, 0)))
    np.testing.assert_allclose(np.asarray(back, np.float64), nb, atol=1e-4)


def test_rplan_validation(mesh):
    with pytest.raises(ValueError, match="even last axis"):
        fft.rplan((8, 9), mesh)
    with pytest.raises(ValueError, match="padded_spectrum"):
        fft.plan((8, 8), mesh, padded_spectrum=True)
    with pytest.raises(ValueError, match="padded_spectrum"):
        fft.rplan((256,), mesh, padded_spectrum=True)
    p = fft.rplan((8, 8), mesh)
    with pytest.raises(ValueError, match="REAL array"):
        p.forward(jnp.zeros((8, 8), jnp.complex64))
    with pytest.raises(ValueError, match="ONE real array"):
        p.forward((jnp.zeros((8, 8)), jnp.zeros((8, 8))))
    with pytest.raises(ValueError, match="does not end with"):
        p.inverse(jnp.zeros((8, 8), jnp.complex64))   # spectrum is (8, 5)
    with pytest.raises(ValueError, match="must start in memory"):
        fft.rplan((8, 8, 8), mesh, layout=('x', None, 'y'))


def test_apply_accepts_plain_lists(mesh):
    """Planar operands given as plain Python lists must be coerced, not
    crash on `.shape` (regression: only np.ndarray was converted)."""
    p = fft.plan((4,), mesh)
    re = [1.0, 2.0, 3.0, 4.0]
    im = [0.0, 0.0, 0.0, 0.0]
    yr, yi = p.forward((re, im))
    want = np.fft.fft(np.asarray(re))
    got = np.asarray(yr, np.float64) + 1j * np.asarray(yi, np.float64)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # nested lists too (rank 2)
    p2 = fft.plan((2, 2), mesh)
    y2r, y2i = p2.forward(([[1.0, 2.0], [3.0, 4.0]],
                           [[0.0, 0.0], [0.0, 0.0]]))
    np.testing.assert_allclose(
        np.asarray(y2r) + 1j * np.asarray(y2i),
        np.fft.fftn([[1.0, 2.0], [3.0, 4.0]]), atol=1e-5)


# ---------------------------------------------------------------------------
# Half-spectrum schedule bookkeeping + cost model
# ---------------------------------------------------------------------------

def test_real_padded_extent():
    assert pencil.real_half_extent(16) == 9
    assert pencil.real_padded_extent((16, 16, 16), ('x', 'y', None),
                                     {'x': 4, 'y': 4}) == 12
    assert pencil.real_padded_extent((512,) * 3, ('x', 'y', None),
                                     {'x': 4, 'y': 4}) == 260
    assert pencil.real_padded_extent((32, 64), (('x', 'y'), None),
                                     {'x': 4, 'y': 4}) == 48
    # 1x1 mesh: no sharding, the odd extent rides as-is
    assert pencil.real_padded_extent((8, 8, 8), ('x', 'y', None),
                                     {'x': 1, 'y': 1}) == 5


def test_real_schedule_transforms_last_axis_first():
    steps, final = pencil.forward_schedule(('x', 'y', None), 2)
    assert steps[0] == ('fft', 2)
    with pytest.raises(ValueError, match="must start in memory"):
        pencil.forward_schedule(('x', None, 'y'), 2)


def test_real_plan_cost_halves_wire():
    """ACCEPTANCE: a real 3-D plan's wire cycles < 0.55x the matching
    complex plan (analytic model) at multi-pencil granularity."""
    for n, mesh_shape in ((512, {'x': 4, 'y': 4}), (512, {'x': 8, 'y': 8}),
                          (512, {'x': 16, 'y': 16}),
                          (1024, {'x': 32, 'y': 32})):
        cc = ccost.pencil_plan_cost((n,) * 3, ('x', 'y', None), mesh_shape,
                                    measured=None)
        cr = ccost.pencil_plan_cost((n,) * 3, ('x', 'y', None), mesh_shape,
                                    real=True, measured=None)
        ratio = cr.wire_cycles / cc.wire_cycles
        assert ratio < 0.55, (mesh_shape, ratio)
        # compute halves too: r2c superstep + halved later supersteps
        fftc = sum(s.cycles for s in cc.steps if s.kind in ('fft', 'rfft'))
        fftr = sum(s.cycles for s in cr.steps if s.kind in ('fft', 'rfft'))
        assert fftr < 0.62 * fftc, (mesh_shape, fftr / fftc)
    kinds = [s.kind for s in cr.steps]
    assert kinds == ['rfft', 'swap', 'fft', 'swap', 'fft']


def test_real_plan_cost_m1_degenerates_gracefully():
    """At the paper's single-pencil granularity (mesh extent = n) the
    truncated axis pads back to full extent — the cost model must price
    that honestly: no wire win, never a loss."""
    cc = ccost.pencil_plan_cost((512,) * 3, ('x', 'y', None),
                                {'x': 512, 'y': 512}, measured=None)
    cr = ccost.pencil_plan_cost((512,) * 3, ('x', 'y', None),
                                {'x': 512, 'y': 512}, real=True,
                                measured=None)
    assert cr.wire_cycles == pytest.approx(cc.wire_cycles)
    assert pencil.real_padded_extent((512,) * 3, ('x', 'y', None),
                                     {'x': 512, 'y': 512}) == 512


def test_real_plan_cost_np_layout_gather_is_priced():
    cr = ccost.pencil_plan_cost((512,) * 3, ('x', 'y', None),
                                {'x': 8, 'y': 8}, real=True,
                                padded_spectrum=False, measured=None)
    assert [s.kind for s in cr.steps][-1] == 'gather'
    cc = ccost.pencil_plan_cost((512,) * 3, ('x', 'y', None),
                                {'x': 8, 'y': 8}, measured=None)
    # even with the boundary gather the wire stays well under the
    # complex plan
    assert cr.wire_cycles < 0.85 * cc.wire_cycles


def test_rplan_facade_cost_on_abstract_mesh():
    from jax import sharding
    if not hasattr(sharding, 'AbstractMesh'):
        pytest.skip("jax.sharding.AbstractMesh unavailable")
    amesh = make_abstract_mesh((16, 16), ('x', 'y'))
    pr = fft.rplan((512,) * 3, amesh, comm='all_to_all',
                   padded_spectrum=True)
    pc = fft.plan((512,) * 3, amesh, comm='all_to_all')
    ratio = (pr.plan_cost(measured=None).wire_cycles
             / pc.plan_cost(measured=None).wire_cycles)
    assert ratio < 0.55, ratio
    assert 'rfft' in pr.cost_report()


def test_rfft_pencil_cycle_model():
    # rfft pencil ~ half the complex pencil, plus the O(n) combine
    for n in (64, 512, 4096):
        full = wm.pencil_cycles_method(n, 'fp32', 'stockham')
        half = wm.rfft_pencil_cycles_method(n, 'fp32', 'stockham')
        assert half < 0.75 * full
        assert half > wm.pencil_cycles_method(n // 2, 'fp32', 'stockham')


# ---------------------------------------------------------------------------
# r2c overlap (split-combine pair)
# ---------------------------------------------------------------------------

def test_rplan_overlap_bit_equivalence(mesh):
    """ACCEPTANCE: overlapped vs unoverlapped rplan execution is
    bit-identical with overlap_chunks > 1 — the r2c superstep now rides
    inside an overlap pair via the split-combine formulation."""
    shape = (16, 16, 16)
    x = RNG.standard_normal(shape).astype(np.float32)
    base = fft.rplan(shape, mesh, overlap_chunks=1)
    want = np.asarray(base.forward(jnp.asarray(x)))
    for oc in (2, 4):
        p = fft.rplan(shape, mesh, overlap_chunks=oc)
        got = np.asarray(p.forward(jnp.asarray(x)))
        assert np.array_equal(want, got), oc
        back = np.asarray(p.inverse(jnp.asarray(got)))
        assert np.array_equal(
            np.asarray(base.inverse(jnp.asarray(want))), back), oc


def test_r2c_step_is_overlappable_in_cost_model():
    """ACCEPTANCE: cost_report no longer lists the r2c step as
    unoverlappable — the (rfft, swap) pair is priced and marked as an
    overlap pair like any (fft, swap) pair."""
    pc = ccost.pencil_plan_cost((512,) * 3, ('x', 'y', None),
                                {'x': 8, 'y': 8}, real=True,
                                overlap_chunks=4, measured=None)
    assert pc.steps[0].kind == 'rfft' and pc.steps[1].kind == 'swap'
    assert 0 in pc.overlapped_steps() and 1 in pc.overlapped_steps()
    # pipelining the pair makes the r2c total cheaper than serial
    assert pc.cycles < pc.serial_cycles
    rep = ccost.format_report(pc, (512,) * 3, {'x': 8, 'y': 8})
    rfft_line = next(ln for ln in rep.splitlines() if ' rfft ' in ln)
    assert '~ovl' in rfft_line, rfft_line


def test_feasible_overlap_includes_r2c_pair():
    # (24, 24, 24) on 4x4: the r2c pair chunks the free y axis (local
    # 6) and the second pair chunks the padded half axis (16/4 = 4), so
    # depth 2 is feasible for the WHOLE real plan — before the
    # split-combine formulation the r2c pair disqualified everything
    ok = ccost.feasible_overlap((24, 24, 24), ('x', 'y', None),
                                {'x': 4, 'y': 4}, real=True)
    assert 2 in ok
    # (16, 16, 16) on 4x4: the r2c pair could chunk (free local 4), but
    # the second pair's only free axis is the padded half axis at local
    # extent 3 — the every-pair rule honestly reports serial-only (the
    # executor then falls back per pair, bit-exactly)
    ok3 = ccost.feasible_overlap((16, 16, 16), ('x', 'y', None),
                                 {'x': 4, 'y': 4}, real=True)
    assert ok3 == (1,)
    # rank-2 real: the r2c pair has NO free axis (both array axes are
    # the fft axis or the swap's shard axis) -> only the serial depth
    ok2 = ccost.feasible_overlap((32, 64), (('x', 'y'), None),
                                 {'x': 4, 'y': 4}, real=True)
    assert ok2 == (1,)


# ---------------------------------------------------------------------------
# Measured-cost autotune table
# ---------------------------------------------------------------------------

def _table(rows):
    return ccost.MeasuredTable(rows)


def _row(strategy, us, elems, mesh="4x4", group="x"):
    return dict(mesh=mesh, group=group, strategy=strategy, p=4,
                local_elems=elems, us=us)


def test_measured_table_interpolation():
    t = _table([_row('all_to_all', 100.0, 1024),
                _row('all_to_all', 400.0, 16384)])
    # exact endpoints
    assert t.swap_us('all_to_all', {'x': 4, 'y': 4}, 'x', 1024) == 100.0
    assert t.swap_us('all_to_all', {'x': 4, 'y': 4}, 'x', 16384) == 400.0
    # log-space interpolation between samples: geometric midpoint
    mid = t.swap_us('all_to_all', {'x': 4, 'y': 4}, 'x', 4096)
    assert 100.0 < mid < 400.0
    assert mid == pytest.approx(200.0, rel=1e-6)
    # outside the measured range (beyond 2x margin): fall back to model
    assert t.swap_us('all_to_all', {'x': 4, 'y': 4}, 'x', 1 << 22) is None
    assert t.swap_us('all_to_all', {'x': 4, 'y': 4}, 'x', 8) is None
    # unmeasured mesh / group / strategy: no entry
    assert t.swap_us('all_to_all', {'x': 512, 'y': 512}, 'x', 2048) is None
    assert t.swap_us('ppermute', {'x': 4, 'y': 4}, 'x', 2048) is None


def test_select_prefers_measured_over_model():
    """The selector must follow the measurements when they cover the
    config — here a table claiming ppermute is 100x faster flips the
    choice away from the analytic winner."""
    rows = []
    for g in ('x', 'y'):
        rows += [_row('all_to_all', 10000.0, 256, group=g),
                 _row('all_to_all', 10000.0, 4096, group=g),
                 _row('ppermute', 100.0, 256, group=g),
                 _row('ppermute', 100.0, 4096, group=g),
                 _row('hierarchical', 10000.0, 256, group=g),
                 _row('hierarchical', 10000.0, 4096, group=g)]
    t = _table(rows)
    sel = ccost.select((16, 16, 16), ('x', 'y', None), {'x': 4, 'y': 4},
                       measured=t)
    assert sel.strategy == 'ppermute'
    # the same config under the pure analytic model picks all_to_all
    sel_a = ccost.select((16, 16, 16), ('x', 'y', None), {'x': 4, 'y': 4},
                         measured=None)
    assert sel_a.strategy == 'all_to_all'
    # measured steps are labelled in the report
    pc = sel.cost
    assert any('measured' in s.detail for s in pc.steps if s.kind == 'swap')


def test_measured_table_dtype_keying():
    """Rows carrying a dtype tag form separate grids; dtype-less
    (legacy) rows — which timed f32 arrays — answer 'c64' queries only
    (serving them to a c128 query would halve the priced wire time)."""
    rows = [dict(_row('all_to_all', 100.0, 1024), dtype='c64'),
            dict(_row('all_to_all', 300.0, 1024), dtype='c128'),
            _row('ppermute', 50.0, 1024)]          # legacy, no dtype
    t = _table(rows)
    ms = {'x': 4, 'y': 4}
    assert t.swap_us('all_to_all', ms, 'x', 1024) == 100.0           # c64
    assert t.swap_us('all_to_all', ms, 'x', 1024, dtype='c128') == 300.0
    # unmeasured dtype -> None (fall back to the analytic model)
    assert t.swap_us('all_to_all', ms, 'x', 1024, dtype='c256') is None
    # legacy rows answer c64 but NOT other dtypes
    assert t.swap_us('ppermute', ms, 'x', 1024) == 50.0
    assert t.swap_us('ppermute', ms, 'x', 1024, dtype='c128') is None


def test_measured_table_loader(tmp_path, monkeypatch):
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(dict(results=[
        _row('all_to_all', 123.0, 2048)])))
    t = ccost.measured_table(str(path))
    assert t is not None and len(t) == 1
    assert t.swap_us('all_to_all', {'x': 4, 'y': 4}, 'x', 2048) == 123.0
    # env var '' disables the default table entirely
    monkeypatch.setenv(ccost.MEASURED_ENV, '')
    assert ccost.measured_table() is None
    monkeypatch.setenv(ccost.MEASURED_ENV, str(path))
    assert ccost.measured_table() is not None
    # junk file -> None, not an exception
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert ccost.measured_table(str(bad)) is None
    # the repo-root BENCH_redistribute.json loads by default
    monkeypatch.delenv(ccost.MEASURED_ENV, raising=False)
    tbl = ccost.measured_table()
    if os.path.exists(os.path.join(ROOT, 'BENCH_redistribute.json')):
        assert tbl is not None and len(tbl) > 0


# ---------------------------------------------------------------------------
# 16-device matrix (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_rfft_worker_16_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_rfft_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-4000:] + "\n" + proc.stderr[-4000:]
    assert "RFFT_WORKER_OK" in proc.stdout
    assert proc.stdout.count("PASS") >= 40
