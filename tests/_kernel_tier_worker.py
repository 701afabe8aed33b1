"""Worker script: kernel-tier equivalence on 16 fake host devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_kernel_tier_worker.py
Exits 0 on success; prints PASS lines per case.

The contract under test: with everything jitted (plans always are),
``kernel='pallas'`` (interpret mode on this CPU host) and
``kernel='reference'`` produce BIT-IDENTICAL outputs for the Stockham
method across every comm strategy — the interpret-mode kernel runs the
same float ops in the same order as the jnp reference, and XLA's jit
rounding is deterministic. Likewise the fused twiddle+transpose
supersteps (the default) are a pure positional rearrangement around
identical float ops, so ``fused=False`` re-plans match bit for bit.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

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

    # ---- real (rfft) plan: tier applies to the post-r2c supersteps ----
    xr = rng.standard_normal((n, n, n)).astype(np.float32)
    yr = {
        tier: fft.rplan((n, n, n), mesh, method="stockham",
                        kernel=tier).forward(jnp.asarray(xr))
        for tier in ("reference", "pallas")
    }
    check_bitwise("rfft3d pallas==reference", yr["pallas"], yr["reference"])
    err = np.max(np.abs(np.asarray(yr["pallas"]) - np.fft.rfftn(xr)))
    assert err < 1e-3, f"rfft err {err:.2e}"

    print("KERNEL_TIER_WORKER_OK")


if __name__ == "__main__":
    main()
