"""Worker script: kernel-tier equivalence on 16 fake host devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_kernel_tier_worker.py            # the whole matrix
    python tests/_kernel_tier_worker.py --no-fma   # real plans, no FMA
Exits 0 on success; prints PASS lines per case.

The contract under test: with everything jitted (plans always are),
``kernel='pallas'`` (interpret mode on this CPU host) and
``kernel='reference'`` produce BIT-IDENTICAL outputs for the Stockham
method across every comm strategy — the interpret-mode kernel runs the
same float ops in the same order as the jnp reference, and XLA's jit
rounding is deterministic. The one exception is a real plan's inverse on
a sharded mesh, which agrees to rounding: XLA:CPU fuses its c2r
Hermitian combine into the jnp Stockham's first stage on one tier and
into a fusion of its own on the other, and contracts the combine's
products into FMAs differently in the two. ``--no-fma`` caps XLA:CPU at
an instruction set without FMA (``--xla_cpu_max_isa=SSE4_2``), and
there the real plans are bitwise on every mesh, inverse included.
Likewise the fused twiddle+transpose
supersteps (the default) are a pure positional rearrangement around
identical float ops, so ``fused=False`` re-plans match bit for bit.
"""
import os
import sys

NO_FMA = "--no-fma" in sys.argv[1:]
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=16"
                           + (" --xla_cpu_max_isa=SSE4_2" if NO_FMA else ""))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro.fft import pencil as fpencil  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


STRATEGIES = ("all_to_all", "ppermute", "hierarchical",
              "pod_tree:x.2*x.2*y.4")


def check_bitwise(name, a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape, f"{name}: shape {a.shape} != {b.shape}"
    assert np.array_equal(a, b), (
        f"{name}: max abs diff {np.max(np.abs(a - b)):.3e} (not bitwise)")
    print(f"PASS {name} bitwise")


def check_real(mesh, tag, rng, *, bitwise_inverse, n=16):
    """A real plan on both tiers: the tier applies to every axis, the
    real axis's half-length FFT (between the Hermitian combines)
    included. The forward is bitwise. The inverse, from one spectrum,
    is bitwise where ``bitwise_inverse``, else equal to rounding."""
    xr = rng.standard_normal((n, n, n)).astype(np.float32)
    rplans = {tier: fft.rplan((n, n, n), mesh, method="stockham",
                              kernel=tier)
              for tier in ("reference", "pallas")}
    yr = {t: p.forward(jnp.asarray(xr)) for t, p in rplans.items()}
    check_bitwise(f"rfft3d {tag} pallas==reference",
                  yr["pallas"], yr["reference"])
    err = np.max(np.abs(np.asarray(yr["pallas"]) - np.fft.rfftn(xr)))
    assert err < 1e-3, f"rfft err {err:.2e}"
    xb = {t: np.asarray(p.inverse(yr["reference"]))
          for t, p in rplans.items()}
    err = np.max(np.abs(xb["pallas"] - xr))
    assert err < 1e-5, f"irfft3d roundtrip err {err:.2e}"
    if bitwise_inverse:
        check_bitwise(f"irfft3d {tag} pallas==reference",
                      xb["pallas"], xb["reference"])
        return
    diff = np.max(np.abs(xb["pallas"] - xb["reference"])) / np.max(np.abs(xr))
    assert diff < 1e-6, f"irfft3d pallas vs reference rel {diff:.2e}"
    print(f"PASS irfft3d {tag} pallas~reference rel_diff={diff:.2e}")


def check_engine_real(mesh, tag, rng):
    """Coalesced real requests through ``FFTEngine`` (width 4, two
    overlap chunks), forward and inverse, give the bits of per-request
    plan calls on both tiers."""
    from repro.serve import FFTEngine
    shape = (16, 16, 32)
    for tier in ("reference", "pallas"):
        eng = FFTEngine(shape, mesh, method="stockham", kernel=tier,
                        schedule_table=None)
        eng.set_schedule(4, 2, real=True)
        p = fft.rplan(shape, mesh, method="stockham", kernel=tier)
        xs = [rng.standard_normal(shape).astype(np.float32)
              for _ in range(5)]
        ys = eng.transform(xs)
        backs = eng.transform(ys, direction="inv")
        for i, (x, y, b) in enumerate(zip(xs, ys, backs)):
            check_bitwise(f"engine rfft3d {tag} {tier} batched==request {i}",
                          y, p.forward(x))
            check_bitwise(f"engine irfft3d {tag} {tier} batched==request {i}",
                          b, p.inverse(y))


def main_no_fma():
    rng = np.random.default_rng(11)
    for shape in ((2, 2), (4, 4)):
        check_real(make_mesh(shape, ("x", "y")), "x".join(map(str, shape)),
                   rng, bitwise_inverse=True)
    check_engine_real(make_mesh((2, 2), ("x", "y")), "2x2", rng)
    print("KERNEL_TIER_WORKER_OK")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    rng = np.random.default_rng(11)
    n = 16
    x = (rng.standard_normal((n, n, n))
         + 1j * rng.standard_normal((n, n, n))).astype(np.complex64)

    # ---- rank 3: pallas == reference, every strategy ----
    for comm in STRATEGIES:
        plans = {
            tier: fft.plan((n, n, n), mesh, method="stockham", comm=comm,
                           kernel=tier, donate=False)
            for tier in ("reference", "pallas")
        }
        ys = {t: p.forward(jnp.asarray(x)) for t, p in plans.items()}
        check_bitwise(f"fft3d {comm} pallas==reference",
                      ys["pallas"], ys["reference"])
        backs = {t: np.asarray(p.inverse(ys[t])) for t, p in plans.items()}
        check_bitwise(f"ifft3d {comm} pallas==reference",
                      backs["pallas"], backs["reference"])
        err = np.max(np.abs(backs["pallas"] - x))
        assert err < 1e-5, f"roundtrip err {err:.2e}"

    # ---- kernel='auto' resolves to 'reference' on CPU: bit-identical ----
    pa = fft.plan((n, n, n), mesh, method="stockham", donate=False)
    pr = fft.plan((n, n, n), mesh, method="stockham", kernel="reference",
                  donate=False)
    assert pa.resolved_kernel == "reference"
    check_bitwise("fft3d auto==reference (cpu)",
                  pa.forward(jnp.asarray(x)), pr.forward(jnp.asarray(x)))

    # ---- fused supersteps (default) == unfused re-plan, both tiers ----
    for tier in ("reference", "pallas"):
        plan3 = fft.plan((n, n, n), mesh, method="stockham", kernel=tier,
                         donate=False)
        fn_fused, _, _ = fpencil.make_fft(plan3._pplan, fused=True)
        fn_unfused, _, _ = fpencil.make_fft(plan3._pplan, fused=False)
        re = jax.device_put(jnp.asarray(x.real), plan3._pplan.sharding())
        im = jax.device_put(jnp.asarray(x.imag), plan3._pplan.sharding())
        yf = jax.jit(fn_fused)(re, im)
        yu = jax.jit(fn_unfused)(re, im)
        check_bitwise(f"fft3d fused==unfused ({tier})", yf[0], yu[0])
        check_bitwise(f"fft3d fused==unfused imag ({tier})", yf[1], yu[1])
        got = np.asarray(yf[0]) + 1j * np.asarray(yf[1])
        err = (np.max(np.abs(got - np.fft.fftn(x)))
               / np.max(np.abs(np.fft.fftn(x))))
        assert err < 3e-6, f"fused {tier} vs numpy rel err {err:.2e}"
        print(f"PASS fft3d fused-vs-numpy ({tier}) rel_err={err:.2e}")

    # ---- rank 1 (large1d four-step, fused columns-DFT) ----
    n1d = 4096
    x1 = (rng.standard_normal(n1d)
          + 1j * rng.standard_normal(n1d)).astype(np.complex64)
    for comm in ("all_to_all", "ppermute"):
        y1 = {
            tier: fft.plan((n1d,), mesh, method="stockham", comm=comm,
                           kernel=tier, donate=False).forward(jnp.asarray(x1))
            for tier in ("reference", "pallas")
        }
        check_bitwise(f"fft1d {comm} pallas==reference",
                      y1["pallas"], y1["reference"])
    err = (np.max(np.abs(np.asarray(y1["pallas"]) - np.fft.fft(x1)))
           / np.max(np.abs(np.fft.fft(x1))))
    assert err < 3e-6, f"fft1d rel err {err:.2e}"
    print(f"PASS fft1d-vs-numpy rel_err={err:.2e}")

    # ---- rank 2 ----
    x2 = (rng.standard_normal((64, 32))
          + 1j * rng.standard_normal((64, 32))).astype(np.complex64)
    y2 = {
        tier: fft.plan((64, 32), mesh, method="stockham", kernel=tier,
                       donate=False).forward(jnp.asarray(x2))
        for tier in ("reference", "pallas")
    }
    check_bitwise("fft2d pallas==reference", y2["pallas"], y2["reference"])

    # ---- real (rfft) plans on a sharded mesh and on one device ----
    check_real(mesh, "4x4", rng, bitwise_inverse=False)
    check_real(make_mesh((1, 1), ("x", "y")), "1x1", rng,
               bitwise_inverse=True)

    print("KERNEL_TIER_WORKER_OK")


if __name__ == "__main__":
    main_no_fma() if NO_FMA else main()
