"""Ahead-of-time compiles for a described TPU v5e 2x2 (no chip needed):
the four Pallas kernels at the 512-point pencils and block sizes of the
512^3 plan, each lowered by Mosaic (``tpu_custom_call`` in the
program), the c2r epilogue and the r2c combine at the per-device shapes
of the sharded 512^3 real plan, and the 512^3 plans themselves on the
2x2 mesh.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""
import functools
import os
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N = 512
#: per-device block of a 512^3 array on the 2x2 mesh
LOCAL = (N // 2, N // 2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — any reason skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _reverse_fused_with_arithmetic(hlo: str) -> bool:
    """True when a fused computation of a compiled program (any but the
    entry, whose scalar index arithmetic is not fused) holds a reverse
    together with adds, subtracts or multiplies: a reverse fused into a
    Hermitian combine, which XLA:TPU computed wrongly at large batch."""
    return any(not comp.startswith("ENTRY") and "reverse(" in comp
               and any(f" {op}(" in comp
                       for op in ("add", "subtract", "multiply"))
               for comp in re.split(r"\n(?=\S)", hlo))


def _kernel_cases():
    from repro.kernels import fft_block, fft_fused, fft_matmul, fft_pencil
    pencils = LOCAL + (N,)
    return {
        "fft_pencil": (fft_pencil.fft_pencil, (pencils, pencils)),
        "fft_matmul": (fft_matmul.fft_matmul, (pencils, pencils)),
        "fft_block": (fft_block.fft_block, ((2,) + pencils,)),
        # the fused superstep with its inter-superstep twiddle
        "fft_fused": (fft_fused.fft_twiddle_transpose, (pencils,) * 4),
    }


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("kernel", ["fft_pencil", "fft_matmul", "fft_block",
                                    "fft_fused"])
def test_kernel_compiles_for_v5e(one_chip, kernel, inverse):
    fn, shapes = _kernel_cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    hlo = _compile(functools.partial(fn, inverse=inverse, interpret=False),
                   *args)
    assert "tpu_custom_call" in hlo, kernel


def test_c2r_epilogue_compiles_for_v5e(one_chip):
    """The last superstep of the sharded 512^3 irfft on one device: the
    padded half spectrum (256, 256, 258) -> the real (256, 256, 512).
    XLA:TPU computed a reverse of the pencil axis fused into this
    combine wrongly; the program must not fuse one."""
    from repro.fft import methods
    nh = N // 2 + 1

    def c2r(re, im):
        return methods.apply_real(re[..., :nh], im[..., :nh], axis=-1,
                                  inverse=True, method="four_step")

    spec = jax.ShapeDtypeStruct(LOCAL + (nh + 1,), jnp.float32,
                                sharding=one_chip)
    out = jax.eval_shape(c2r, spec, spec)
    assert out.shape == LOCAL + (N,)
    assert not _reverse_fused_with_arithmetic(_compile(c2r, spec, spec))


def test_r2c_combine_compiles_for_v5e(one_chip):
    """The first superstep of the sharded 512^3 rfft on one device: the
    real (256, 256, 512) -> the half spectrum (256, 256, 257). Its
    Hermitian combine reads the mirrored bins by a gather: the program
    holds no reverse at all."""
    from repro.fft import methods

    def r2c(x):
        return methods.apply_real(x, axis=-1, method="four_step")

    x = jax.ShapeDtypeStruct(LOCAL + (N,), jnp.float32, sharding=one_chip)
    assert jax.eval_shape(r2c, x)[0].shape == LOCAL + (N // 2 + 1,)
    assert "reverse(" not in _compile(r2c, x)


@pytest.mark.parametrize("real", [False, True])
def test_plans_compile_on_v5e_2x2(topo, monkeypatch, real):
    """The 512^3 c2c and r2c plans, forward and inverse, sharded over the
    four chips of the described host with the Pallas tier resolved."""
    import repro.fft as fft
    from repro.fft import methods
    from repro.launch.mesh import make_mesh
    # plans read the backend to pick the kernel tier: steer them to the
    # described chip's, as the compile targets it
    monkeypatch.setattr(methods, "backend", lambda: "tpu")
    mesh = make_mesh((2, 2), ("x", "y"), devices=topo.devices)
    shape = (N,) * 3
    p = (fft.rplan(shape, mesh) if real
         else fft.plan(shape, mesh, donate=False))
    assert p.resolved_kernel == "pallas"
    x = jax.ShapeDtypeStruct(shape, jnp.float32 if real else jnp.complex64,
                             sharding=p.in_sharding)
    y = jax.ShapeDtypeStruct(p.spectrum_shape if real else shape,
                             jnp.complex64, sharding=p.out_sharding)
    fwd = _compile(p.forward, x)
    inv = _compile(p.inverse, y)
    assert "tpu_custom_call" in fwd and "tpu_custom_call" in inv
    if real:      # the Hermitian combines: no reverse fused into them
        assert "reverse(" not in fwd
        assert not _reverse_fused_with_arithmetic(inv)
