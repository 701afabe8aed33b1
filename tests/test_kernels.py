"""Pallas kernel correctness: shape/dtype sweeps vs the ref.py oracle,
executed in interpret mode (kernel body evaluated on CPU), plus the
kernel-tier dispatch contract (fused superstep kernel bit-identical to
the jnp reference; per-backend 'auto' resolution)."""
import functools
import inspect
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fft1d as _f1
from repro.core import twiddle as tw
from repro.fft import methods
from repro.kernels import fft_fused, fft_matmul, fft_pencil, ops, ref

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

RNG = np.random.default_rng(7)


def _rand(shape):
    return RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)


@pytest.mark.parametrize("n", [16, 64, 256, 1024])
@pytest.mark.parametrize("b", [1, 3, 8, 17])
@pytest.mark.parametrize("kernel", ["pencil", "matmul"])
def test_kernel_vs_ref(n, b, kernel):
    x = _rand((b, n))
    re, im = tw.to_planar(x)
    wr, wi = ref.fft_pencil_ref(re, im)
    fn = fft_pencil.fft_pencil if kernel == "pencil" else fft_matmul.fft_matmul
    yr, yi = fn(re, im, interpret=True)
    atol = 2e-4 * np.sqrt(n)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(wr), atol=atol)
    np.testing.assert_allclose(np.asarray(yi), np.asarray(wi), atol=atol)


@pytest.mark.parametrize("n", [64, 512])
@pytest.mark.parametrize("kernel", ["pencil", "matmul"])
def test_kernel_inverse_roundtrip(n, kernel):
    x = _rand((5, n))
    re, im = tw.to_planar(x)
    fn = fft_pencil.fft_pencil if kernel == "pencil" else fft_matmul.fft_matmul
    yr, yi = fn(re, im, interpret=True)
    br, bi = fn(yr, yi, inverse=True, interpret=True)
    np.testing.assert_allclose(np.asarray(br), np.asarray(re), atol=1e-4)
    np.testing.assert_allclose(np.asarray(bi), np.asarray(im), atol=1e-4)


@pytest.mark.parametrize("block_b", [4, 8, 16])
def test_kernel_block_sizes(block_b):
    """BlockSpec tiling must not change results (incl. padded tail)."""
    n, b = 128, 10
    x = _rand((b, n))
    re, im = tw.to_planar(x)
    wr, wi = ref.fft_pencil_ref(re, im)
    yr, yi = fft_pencil.fft_pencil(re, im, block_b=block_b, interpret=True)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(wr), atol=2e-3)
    yr, yi = fft_matmul.fft_matmul(re, im, block_b=block_b, interpret=True)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(wr), atol=2e-3)


def test_kernel_3d_batch_shape():
    n = 64
    x = _rand((2, 3, n))
    re, im = tw.to_planar(x)
    wr, wi = ref.fft_pencil_ref(re, im)
    yr, yi = fft_pencil.fft_pencil(re, im, interpret=True)
    assert yr.shape == (2, 3, n)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(wr), atol=2e-3)


def test_matmul_kernel_explicit_factors():
    n = 256
    x = _rand((4, n))
    re, im = tw.to_planar(x)
    wr, wi = ref.fft_pencil_ref(re, im)
    yr, yi = fft_matmul.fft_matmul(re, im, factors=(64, 4), interpret=True)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(wr), atol=2e-3)


def test_ops_dispatch_paths():
    n = 128
    x = _rand((4, n))
    re, im = tw.to_planar(x)
    wr, _ = ref.fft_pencil_ref(re, im)
    for use_kernel in (False, True):
        for method in ("stockham", "four_step", "auto"):
            yr, _ = ops.pencil_fft(re, im, method=method, use_kernel=use_kernel)
            np.testing.assert_allclose(np.asarray(yr), np.asarray(wr), atol=2e-3)


def test_non_pow2_rejected():
    re, im = tw.to_planar(_rand((2, 24)))
    with pytest.raises(ValueError):
        fft_pencil.fft_pencil(re, im, interpret=True)


# ---------------------------------------------------------------------------
# Block-complex kernel (EXPERIMENTS.md §Perf cell A winner)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('n', [64, 256, 1024])
@pytest.mark.parametrize('batch', [(1,), (3,), (2, 5)])
@pytest.mark.parametrize('inverse', [False, True])
def test_fft_block_kernel_vs_oracle(n, batch, inverse):
    from repro.core import fft1d as f1
    from repro.kernels.fft_block import fft_block
    rng = np.random.default_rng(n + sum(batch))
    x = rng.standard_normal((2,) + batch + (n,)).astype(np.float32)
    xj = jnp.asarray(x)
    got = fft_block(xj, inverse=inverse, interpret=True)
    want = f1.fft_four_step_block(xj, xj.ndim - 1, inverse=inverse)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_fft_block_kernel_vs_numpy():
    from repro.kernels.fft_block import fft_block
    rng = np.random.default_rng(0)
    n = 512
    z = rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))
    x = jnp.stack([jnp.asarray(z.real, jnp.float32),
                   jnp.asarray(z.imag, jnp.float32)])
    y = fft_block(x, interpret=True)
    got = np.asarray(y[0]) + 1j * np.asarray(y[1])
    want = np.fft.fft(z, axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-3)


# ---------------------------------------------------------------------------
# Kernel tier: fused twiddle+transpose superstep + per-backend dispatch
# ---------------------------------------------------------------------------

def _bitwise(got, want, name):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, f"{name}: {got.shape} != {want.shape}"
    assert np.array_equal(got, want), (
        f"{name}: max abs diff {np.max(np.abs(got - want)):.3e} "
        "(not bitwise)")


@pytest.mark.parametrize("n", [16, 64, 256])
@pytest.mark.parametrize("b", [5, 8, 17])
@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("with_w", [False, True])
def test_fused_kernel_bitwise_vs_reference(n, b, inverse, with_w):
    """Interpret-mode fused kernel == jitted jnp reference, bit for bit
    (incl. batch remainders that don't divide block_b)."""
    re, im = tw.to_planar(_rand((b, n)))
    wr = wi = None
    if with_w:
        wr, wi = tw.to_planar(_rand((b, n)))
    want = jax.jit(functools.partial(
        _f1.fft_twiddle_transpose, inverse=inverse))(re, im, wr, wi)
    got = fft_fused.fft_twiddle_transpose(re, im, wr, wi, inverse=inverse,
                                          interpret=True)
    assert got[0].shape == (n, b)
    for g, w, nm in zip(got, want, ("re", "im")):
        _bitwise(g, w, f"fused n={n} b={b} inv={inverse} w={with_w} {nm}")


def test_fused_kernel_lead_dims_and_broadcast_twiddle():
    """Lead dims vectorize over the grid; a (1, n)-broadcast twiddle is
    accepted like the jnp reference accepts it."""
    n, b = 64, 6
    re, im = tw.to_planar(_rand((2, 3, b, n)))
    wr, wi = tw.to_planar(_rand((1, n)))
    want = jax.jit(_f1.fft_twiddle_transpose)(re, im, wr, wi)
    got = fft_fused.fft_twiddle_transpose(re, im, wr, wi, interpret=True)
    assert got[0].shape == (2, 3, n, b)
    for g, w, nm in zip(got, want, ("re", "im")):
        _bitwise(g, w, f"fused lead-dims {nm}")


def test_fused_kernel_rejects_rank1():
    re, im = tw.to_planar(_rand((32,)))
    with pytest.raises(ValueError):
        fft_fused.fft_twiddle_transpose(re, im, interpret=True)


def test_resolve_kernel_per_backend():
    st = methods.resolve("stockham", 64)
    assert methods.resolve_kernel("reference", st, "cpu") == "reference"
    assert methods.resolve_kernel("pallas", st, "cpu") == "pallas"
    # 'auto' takes the Pallas tier only where it lowers natively
    assert methods.resolve_kernel("auto", st, "cpu") == "reference"
    assert methods.resolve_kernel("auto", st, "gpu") == "pallas"
    assert methods.resolve_kernel("auto", st, "cuda") == "pallas"
    assert methods.resolve_kernel("auto", st, "tpu") == "pallas"
    assert methods.resolve_kernel("auto", st, "mystery") == "reference"
    # a method without a kernel for the backend always falls back
    direct = methods.resolve("direct", 24)
    assert methods.resolve_kernel("pallas", direct, "tpu") == "reference"
    assert methods.resolve_kernel("auto", direct, "gpu") == "reference"
    with pytest.raises(ValueError):
        methods.resolve_kernel("mosaic", st)


def test_default_interpret_env_override():
    """Interpret mode follows the backend alone: no environment override
    exists that could put the chip's kernels into interpret mode, and
    every kernel's own ``interpret`` default defers to that resolution
    (on this CPU backend: interpret, so the call below runs)."""
    assert methods.default_interpret("cpu") is True
    assert methods.default_interpret("gpu") is False
    assert methods.default_interpret("tpu") is False
    assert not hasattr(methods, "KERNEL_INTERPRET_ENV")
    from repro.kernels import fft_block
    for fn in (fft_pencil.fft_pencil, fft_matmul.fft_matmul,
               fft_block.fft_block, fft_fused.fft_twiddle_transpose):
        sig = inspect.signature(fn)
        assert sig.parameters["interpret"].default is None, fn
    re, im = tw.to_planar(_rand((2, 16)))
    yr, yi = fft_pencil.fft_pencil(re, im)
    want = np.fft.fft(np.asarray(re) + 1j * np.asarray(im), axis=-1)
    np.testing.assert_allclose(np.asarray(yr) + 1j * np.asarray(yi), want,
                               atol=1e-4)


@pytest.mark.parametrize("inverse", [False, True])
def test_apply_pallas_tier_bitwise_stockham(inverse):
    """methods.apply kernel='pallas' (interpret) == kernel='reference',
    both jitted — the contract the distributed plans rely on."""
    n = 128
    re, im = tw.to_planar(_rand((6, n)))
    tiers = {
        t: jax.jit(functools.partial(methods.apply, method="stockham",
                                     kernel=t, inverse=inverse))(re, im)
        for t in ("reference", "pallas")
    }
    for g, w, nm in zip(tiers["pallas"], tiers["reference"], ("re", "im")):
        _bitwise(g, w, f"apply stockham inv={inverse} {nm}")


@pytest.mark.parametrize("method", ["four_step", "block"])
def test_apply_pallas_tier_allclose(method):
    """Non-stockham kernels use different op orders — allclose, not
    bitwise."""
    n = 256
    re, im = tw.to_planar(_rand((4, n)))
    ref_out = methods.apply(re, im, method=method, kernel="reference")
    pal_out = methods.apply(re, im, method=method, kernel="pallas")
    for g, w in zip(pal_out, ref_out):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-3)


def test_apply_auto_tier_is_reference_on_cpu():
    n = 64
    re, im = tw.to_planar(_rand((3, n)))
    auto = jax.jit(functools.partial(methods.apply, method="stockham",
                                     kernel="auto"))(re, im)
    ref_out = jax.jit(functools.partial(methods.apply, method="stockham",
                                        kernel="reference"))(re, im)
    for g, w, nm in zip(auto, ref_out, ("re", "im")):
        _bitwise(g, w, f"apply auto==reference {nm}")


@pytest.mark.slow
def test_kernel_tier_16_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_kernel_tier_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert r.returncode == 0, (
        f"STDOUT:\n{r.stdout[-4000:]}\nSTDERR:\n{r.stderr[-4000:]}")
    assert "KERNEL_TIER_WORKER_OK" in r.stdout
    assert r.stdout.count("PASS") >= 18
