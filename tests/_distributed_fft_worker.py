"""Worker script: distributed wsFFT correctness on 16 fake host devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_distributed_fft_worker.py
Exits 0 on success; prints PASS lines per case.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import distributed as dist  # noqa: E402
from repro.core import plan as planlib  # noqa: E402
from repro.core import twiddle as tw  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def check(name, got, want, tol):
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
    assert err < tol, f"{name}: rel err {err:.2e} > {tol}"
    print(f"PASS {name} rel_err={err:.2e}")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    rng = np.random.default_rng(42)

    # ---- 3D FFT, n^3 on 4x4 mesh (multi-pencil m = n/4) ----
    for n, method in [(8, "stockham"), (16, "four_step"), (16, "auto"),
                      (32, "auto")]:
        x = rng.standard_normal((n, n, n)) + 1j * rng.standard_normal((n, n, n))
        want = np.fft.fftn(x)
        plan = planlib.make_fft3d_plan(n, mesh, method=method)
        re, im = tw.to_planar(x)
        re = jax.device_put(re, plan.sharding())
        im = jax.device_put(im, plan.sharding())
        fwd, in_lay, out_lay = dist.make_fft(plan)
        yr, yi = jax.jit(fwd)(re, im)
        got = tw.from_planar((yr, yi))
        check(f"fft3d n={n} {method} out_layout={out_lay}", got, want, 3e-4)

        # inverse round trip (consumes forward layout, restores input layout)
        inv, _, _ = dist.make_fft(plan, inverse=True)
        br, bi = jax.jit(inv)(yr, yi)
        back = tw.from_planar((br, bi))
        check(f"ifft3d-roundtrip n={n} {method}", back, x, 3e-4)

    # ---- forward with restore_layout ----
    n = 16
    x = rng.standard_normal((n, n, n)) + 1j * rng.standard_normal((n, n, n))
    plan = planlib.make_fft3d_plan(n, mesh)
    re, im = (jax.device_put(a, plan.sharding()) for a in tw.to_planar(x))
    fwd, _, out_lay = dist.make_fft(plan, restore_layout=True)
    assert out_lay == plan.layout
    yr, yi = jax.jit(fwd)(re, im)
    check("fft3d restore_layout", tw.from_planar((yr, yi)), np.fft.fftn(x), 3e-4)

    # ---- overlap_chunks pipelined variant ----
    fwd, _, _ = dist.make_fft(plan, overlap_chunks=2)
    yr, yi = jax.jit(fwd)(re, im)
    check("fft3d overlap_chunks=2", tw.from_planar((yr, yi)), np.fft.fftn(x), 3e-4)

    # ---- batched 3D FFT (leading batch axis kept local per device) ----
    xb = rng.standard_normal((2, n, n, n)) + 1j * rng.standard_normal((2, n, n, n))
    fwdb, _, _ = dist.make_fft(plan, batch=True)
    reb, imb = tw.to_planar(xb)
    shb = jax.sharding.NamedSharding(mesh, P(None, "x", "y", None))
    reb, imb = jax.device_put(reb, shb), jax.device_put(imb, shb)
    yr, yi = jax.jit(fwdb)(reb, imb)
    wantb = np.fft.fftn(xb, axes=(1, 2, 3))
    check("fft3d batched", tw.from_planar((yr, yi)), wantb, 3e-4)

    # ---- 2D FFT on the flattened 16-device mesh ----
    for (n0, n1) in [(32, 64), (64, 64)]:
        x2 = rng.standard_normal((n0, n1)) + 1j * rng.standard_normal((n0, n1))
        plan2 = planlib.make_fft2d_plan(n0, n1, mesh)
        re, im = (jax.device_put(a, plan2.sharding()) for a in tw.to_planar(x2))
        fwd2, _, out_lay2 = dist.make_fft(plan2)
        yr, yi = jax.jit(fwd2)(re, im)
        check(f"fft2d {n0}x{n1} out_layout={out_lay2}",
              tw.from_planar((yr, yi)), np.fft.fft2(x2), 3e-4)
        inv2, _, _ = dist.make_fft(plan2, inverse=True)
        br, bi = jax.jit(inv2)(yr, yi)
        check(f"ifft2d-roundtrip {n0}x{n1}", tw.from_planar((br, bi)), x2, 3e-4)

    # ---- large 1D FFT via distributed four-step ----
    mesh_axes = ("x", "y")
    for (n1, n2) in [(64, 32), (64, 64)]:
        n = n1 * n2
        x1 = rng.standard_normal(n) + 1j * rng.standard_normal(n)
        want = np.fft.fft(x1)
        a = x1.reshape(n1, n2)
        re, im = tw.to_planar(a)
        sh = jax.sharding.NamedSharding(mesh, P(mesh_axes, None))
        re, im = jax.device_put(re, sh), jax.device_put(im, sh)
        f = dist.make_fft1d_large(n1, n2, mesh, mesh_axes)
        dr, di = jax.jit(f)(re, im)
        d = tw.from_planar((dr, di))
        # y[j1 + n1*j2] = D[j1, j2]  ->  natural y = D.flatten(order='F')
        got = d.flatten(order="F")
        check(f"fft1d_large n={n} ({n1}x{n2})", got, want, 3e-4)
        fnat = dist.make_fft1d_large(n1, n2, mesh, mesh_axes, natural_order=True)
        dr, di = jax.jit(fnat)(re, im)
        got = tw.from_planar((dr, di)).flatten()
        check(f"fft1d_large natural n={n}", got, want, 3e-4)

    # ---- bf16 compute-dtype path (loose tol) ----
    n = 16
    x = rng.standard_normal((n, n, n)) + 1j * rng.standard_normal((n, n, n))
    plan = planlib.make_fft3d_plan(n, mesh, method="four_step",
                                   compute_dtype=jnp.bfloat16)
    re, im = (jax.device_put(a, plan.sharding()) for a in tw.to_planar(x))
    fwd, _, _ = dist.make_fft(plan)
    yr, yi = jax.jit(fwd)(re, im)
    check("fft3d bf16-compute", tw.from_planar((yr, yi)), np.fft.fftn(x), 5e-2)

    # ---- Pallas kernels inside shard_map (interpret mode) ----
    n = 16
    x = rng.standard_normal((n, n, n)) + 1j * rng.standard_normal((n, n, n))
    plan = planlib.make_fft3d_plan(n, mesh, method="stockham",
                                   kernel="pallas")
    re, im = (jax.device_put(a, plan.sharding()) for a in tw.to_planar(x))
    fwd, _, _ = dist.make_fft(plan)
    yr, yi = jax.jit(fwd)(re, im)
    check("fft3d pallas-kernel", tw.from_planar((yr, yi)), np.fft.fftn(x), 3e-4)

    print("ALL DISTRIBUTED FFT TESTS PASSED")


if __name__ == "__main__":
    main()
