"""Worker script: half-precision wire-format accuracy gate, 16 devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_wire_accuracy_worker.py
Exits 0 on success; prints PASS lines per case.

On a 4x4 ('x', 'y') mesh, for ranks 1/2/3 under every registered
strategy plus parameterized pod trees:

  * ``wire_dtype='native'`` is BIT-IDENTICAL to a plan that never set
    the knob — the default path must not move;
  * fp16/bf16-wire transforms stay within per-shape max-relative-error
    bounds of the fp32 native-wire output of the SAME plan, forward
    and round trip;
  * real (rfft) plans meet the same gate (the single-real first swap
    and the half-spectrum pair swaps both cast).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import comm  # noqa: E402
import repro.fft as fft  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(23)

TREES = ('pod_tree:x.2*x.2*y.2*y.2', 'pod_tree:x.4*y.2*y.2')

#: max relative error of a compact-wire transform vs the fp32
#: native-wire output. fp16 keeps an 11-bit significand (~5e-4 per
#: cast, 2-4 casts per schedule); bf16 keeps 8 bits (~8x looser).
#: Observed on this seed: fp16 ~3-4e-4, bf16 ~2-3e-3.
BOUNDS = {
    (4096,): {'fp16': 1.5e-3, 'bf16': 1.2e-2},
    (32, 64): {'fp16': 1.0e-3, 'bf16': 8.0e-3},
    (32, 32, 32): {'fp16': 1.0e-3, 'bf16': 8.0e-3},
}


def relerr(got, want):
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def check_complex(mesh):
    for shape, bounds in BOUNDS.items():
        z = RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)
        zc = jnp.asarray(z, jnp.complex64)
        for strategy in comm.names() + TREES:
            # donate=False: the same operand feeds every plan below
            base = fft.plan(shape, mesh, comm=strategy, donate=False)
            pnat = fft.plan(shape, mesh, comm=strategy,
                            wire_dtype='native', donate=False)
            ref = np.asarray(base.forward(zc))
            assert np.array_equal(ref, np.asarray(pnat.forward(zc))), (
                shape, strategy, "wire_dtype='native' not bit-identical")
            for wd, bound in bounds.items():
                p = fft.plan(shape, mesh, comm=strategy, wire_dtype=wd,
                             donate=False)
                y = p.forward(zc)
                err = relerr(np.asarray(y, np.complex128), ref)
                assert err <= bound, (shape, strategy, wd, err, bound)
                back = np.asarray(p.inverse(y), np.complex128)
                rerr = relerr(back, z)
                assert rerr <= bound, (shape, strategy, wd,
                                       'roundtrip', rerr, bound)
                print(f"PASS wire {shape} {strategy} {wd} "
                      f"fwd={err:.2e} rt={rerr:.2e} (<= {bound:.0e})")


def check_real(mesh):
    for shape in ((4096,), (32, 32, 32)):
        bounds = BOUNDS[shape]
        x = RNG.standard_normal(shape).astype(np.float32)
        for strategy in ('all_to_all', 'hierarchical', TREES[1]):
            base = fft.rplan(shape, mesh, comm=strategy)
            pnat = fft.rplan(shape, mesh, comm=strategy,
                             wire_dtype='native')
            ref = np.asarray(base.forward(x))
            assert np.array_equal(ref, np.asarray(pnat.forward(x))), (
                shape, strategy, "real native wire not bit-identical")
            for wd, bound in bounds.items():
                p = fft.rplan(shape, mesh, comm=strategy, wire_dtype=wd)
                y = p.forward(x)
                err = relerr(np.asarray(y, np.complex128),
                             ref.astype(np.complex128))
                assert err <= bound, (shape, strategy, wd, err, bound)
                back = np.asarray(p.inverse(y), np.float64)
                rerr = np.max(np.abs(back - x)) / np.max(np.abs(x))
                assert rerr <= bound, (shape, strategy, wd,
                                       'roundtrip', rerr, bound)
                print(f"PASS wire real {shape} {strategy} {wd} "
                      f"fwd={err:.2e} rt={rerr:.2e}")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    check_complex(mesh)
    check_real(mesh)
    print("WIRE_WORKER_OK")


if __name__ == "__main__":
    main()
