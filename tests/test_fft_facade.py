"""The repro.fft facade: plan/execute API, rank dispatch, front-ends.

Single-device tests run in-process on a 1x1 mesh (the machinery is the
same shard_map program; collectives just have group size 1). The full
16-fake-device matrix — ranks 1/2/3 x {complex, planar} x {'four_step',
'block'} round trips — runs in a subprocess so this process keeps one
device (see _fft_facade_worker.py).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

import repro.fft as fft
from repro.core import twiddle as tw
from repro.launch.mesh import make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


RNG = np.random.default_rng(3)


def _rand(shape):
    return RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)


@pytest.mark.parametrize("shape", [(256,), (16, 32), (8, 8, 8)])
@pytest.mark.parametrize("method", ["four_step", "block", "stockham"])
def test_roundtrip_complex(mesh, shape, method):
    x = _rand(shape)
    p = fft.plan(shape, mesh, method=method)
    y = p.forward(jnp.asarray(x, jnp.complex64))
    want = np.fft.fftn(x, axes=tuple(range(-len(shape), 0)))
    np.testing.assert_allclose(np.asarray(y, np.complex128), want,
                               atol=3e-4 * np.max(np.abs(want)))
    back = p.inverse(y)
    np.testing.assert_allclose(np.asarray(back, np.complex128), x, atol=1e-4)


@pytest.mark.parametrize("shape", [(256,), (16, 32), (8, 8, 8)])
def test_roundtrip_planar(mesh, shape):
    x = _rand(shape)
    p = fft.plan(shape, mesh)
    re, im = tw.to_planar(x)
    fr, fi = p.forward((re, im))
    want = np.fft.fftn(x, axes=tuple(range(-len(shape), 0)))
    np.testing.assert_allclose(tw.from_planar((fr, fi)), want,
                               atol=3e-4 * np.max(np.abs(want)))
    br, bi = p.inverse((fr, fi))
    np.testing.assert_allclose(tw.from_planar((br, bi)), x, atol=1e-4)


def test_batch_dims_and_cache(mesh):
    p = fft.plan((8, 8), mesh)
    x = _rand((3, 2, 8, 8))
    y = p.forward(jnp.asarray(x, jnp.complex64))
    want = np.fft.fftn(x, axes=(-2, -1))
    np.testing.assert_allclose(np.asarray(y, np.complex128), want,
                               atol=3e-4 * np.max(np.abs(want)))
    # one executable per (direction, batch_shape, dtype, form)
    assert set(p._exec_cache) == {("fwd", (3, 2), "complex64", False)}
    p.forward(jnp.asarray(x, jnp.complex64))
    assert len(p._exec_cache) == 1
    p.inverse(y)
    assert len(p._exec_cache) == 2


def test_plan_validation(mesh):
    with pytest.raises(ValueError, match="unknown FFT method"):
        fft.plan((8, 8), mesh, method="nope")
    with pytest.raises(ValueError, match="ranks 1-3"):
        fft.plan((4, 4, 4, 4), mesh)
    p = fft.plan((8, 8), mesh)
    with pytest.raises(ValueError, match="does not end with"):
        p.forward(jnp.zeros((8, 4), jnp.complex64))
    with pytest.raises(ValueError, match="not a mesh axis"):
        fft.plan((8, 8), mesh, batch_spec="pod")


def test_registry_is_single_source(mesh):
    from repro.core import fft1d
    assert fft.available_methods() == fft1d.METHODS
    assert "block" in fft.available_methods()
    # the legacy shims route through the registry
    x = _rand((4, 64))
    re, im = tw.to_planar(x)
    want = np.fft.fft(x, axis=-1)
    for shim_out in (
        fft1d.fft1d(re, im, method="block"),
        fft.methods.apply(re, im, method="block"),
    ):
        np.testing.assert_allclose(tw.from_planar(shim_out), want, atol=2e-3)


def test_methods_apply_axis_general():
    x = _rand((4, 32, 3))
    re, im = tw.to_planar(x)
    want = np.fft.fft(x, axis=1)
    for method in ("stockham", "four_step", "block"):
        yr, yi = fft.methods.apply(re, im, axis=1, method=method)
        np.testing.assert_allclose(tw.from_planar((yr, yi)), want, atol=2e-3)


@pytest.mark.slow
def test_fft_facade_16_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_fft_facade_worker.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-4000:]}\nSTDERR:\n{r.stderr[-4000:]}"
    assert "ALL FFT FACADE TESTS PASSED" in r.stdout
    assert r.stdout.count("PASS") >= 30
