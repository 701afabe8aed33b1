"""Worker script: repro.fft facade correctness on 16 fake host devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_fft_facade_worker.py
Exits 0 on success; prints PASS lines per case.

Covers the ISSUE acceptance matrix: ranks 1/2/3 through the one
``fft.plan`` signature, complex-array AND planar front-ends, at least
the 'four_step' and 'block' methods, exact inverse(forward(x)) round
trips, and the jit-executable cache.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro.core import twiddle as tw  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def check(name, got, want, tol):
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
    assert err < tol, f"{name}: rel err {err:.2e} > {tol}"
    print(f"PASS {name} rel_err={err:.2e}")


def npfft(x, rank):
    axes = tuple(range(-rank, 0))
    return np.fft.fftn(x, axes=axes)


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    rng = np.random.default_rng(7)
    shapes = {1: (1024,), 2: (32, 64), 3: (16, 16, 16)}

    for rank, shape in shapes.items():
        x = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
        want = npfft(x, rank)
        for method in ("four_step", "block"):
            # donate=False: this matrix re-feeds the same operands
            # (donation itself is covered below)
            p = fft.plan(shape, mesh, method=method, donate=False)

            # complex front-end
            xc = jax.device_put(jnp.asarray(x, jnp.complex64), p.in_sharding)
            y = p.forward(xc)
            assert y.dtype == jnp.complex64, y.dtype
            check(f"rank{rank} {method} complex fwd", np.asarray(y, np.complex128), want, 3e-4)
            back = p.inverse(y)
            check(f"rank{rank} {method} complex roundtrip",
                  np.asarray(back, np.complex128), x, 3e-4)

            # planar front-end returns the form it was given
            re, im = tw.to_planar(x)
            fr, fi = p.forward((re, im))
            check(f"rank{rank} {method} planar fwd",
                  tw.from_planar((fr, fi)), want, 3e-4)
            br, bi = p.inverse((fr, fi))
            check(f"rank{rank} {method} planar roundtrip",
                  tw.from_planar((br, bi)), x, 3e-4)

            # the jitted-executable cache is keyed (direction, batch, dtype, form)
            n_keys = len(p._exec_cache)
            p.forward(xc)
            p.inverse((fr, fi))
            assert len(p._exec_cache) == n_keys == 4, p._exec_cache.keys()
        print(f"PASS rank{rank} exec cache stable across repeat calls")

    # donation on the real mesh: the default consumes the operand even
    # across the sharding rotation; donate=False keeps it reusable
    pdon = fft.plan((16, 16, 16), mesh)
    xd = jax.device_put(jnp.asarray(
        rng.standard_normal((16, 16, 16)), jnp.complex64), pdon.in_sharding)
    yd = pdon.forward(xd)
    assert xd.is_deleted(), "donated input must be consumed"
    try:
        _ = xd + 1
        raise AssertionError("reuse after donate must raise")
    except RuntimeError:
        pass
    assert not yd.is_deleted()
    print("PASS donation consumes input; donate=False covered above")

    # leading batch dims (replicated) ride along for every rank
    for rank, shape in shapes.items():
        xb = rng.standard_normal((2,) + shape) + 1j * rng.standard_normal((2,) + shape)
        p = fft.plan(shape, mesh)
        yb = p.forward(jnp.asarray(xb, jnp.complex64))
        check(f"rank{rank} batched fwd", np.asarray(yb, np.complex128),
              npfft(xb, rank), 3e-4)
        bb = p.inverse(yb)
        check(f"rank{rank} batched roundtrip", np.asarray(bb, np.complex128), xb, 3e-4)

    # sharding metadata: forward output lands where inverse consumes it
    p = fft.plan((16, 16, 16), mesh)
    y = p.forward(jax.device_put(
        jnp.asarray(rng.standard_normal((16, 16, 16)), jnp.complex64),
        p.in_sharding))
    assert y.sharding.is_equivalent_to(p.out_sharding, 3), (
        y.sharding, p.out_sharding)
    print("PASS rank3 out_sharding matches produced array")

    # restore_layout keeps both directions on the input sharding
    pr = fft.plan((16, 16, 16), mesh, restore_layout=True)
    assert pr.in_sharding == pr.out_sharding
    x = rng.standard_normal((16, 16, 16)) + 1j * rng.standard_normal((16, 16, 16))
    back = pr.inverse(pr.forward(jnp.asarray(x, jnp.complex64)))
    check("rank3 restore_layout roundtrip", np.asarray(back, np.complex128), x, 3e-4)

    print("ALL FFT FACADE TESTS PASSED")


if __name__ == "__main__":
    main()
