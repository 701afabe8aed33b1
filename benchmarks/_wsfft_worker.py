"""Subprocess worker: time our distributed wsFFT on a fake-device mesh.

Usage: python -m benchmarks._wsfft_worker <ndev_x> <ndev_y> <n> <method>
Prints CSV rows (name,us_per_call,derived).
"""
import os
import sys

nx, ny = int(sys.argv[1]), int(sys.argv[2])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nx * ny}"

import numpy as np  # noqa: E402
import jax  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro.core import twiddle as tw  # noqa: E402
from repro.core import wse_model as wm  # noqa: E402
from benchmarks.common import emit, time_jax  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    n = int(sys.argv[3])
    method = sys.argv[4] if len(sys.argv) > 4 else "auto"
    mesh = make_mesh((nx, ny), ("x", "y"))
    # donate=False: the timing loop re-feeds the same planar buffers
    p = fft.plan((n, n, n), mesh, method=method, donate=False)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, n, n)) + 1j * rng.standard_normal((n, n, n))
    re, im = tw.to_planar(x)
    re = jax.device_put(re, p.in_sharding)
    im = jax.device_put(im, p.in_sharding)
    us = time_jax(lambda a, b: p.forward((a, b)), re, im)
    gf = wm.fft_flops_3d(n) / (us * 1e-6) / 1e9
    emit(f"wsfft_host/fft3d_n{n}_{method}_{nx}x{ny}", us,
         f"gflops={gf:.2f} (host-CPU emulation of {nx * ny} devices)")


if __name__ == "__main__":
    main()
