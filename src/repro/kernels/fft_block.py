"""Pallas TPU kernel: block-complex four-step pencil FFT.

The §Perf cell-A winner (EXPERIMENTS.md) as an MXU kernel: complex
arithmetic via ONE real matmul per factor against the 2x2 block DFT
matrix [[Fr, -Fi], [Fi, Fr]] — the VMEM-resident form of core/fft1d.
fft_four_step_block, which is its oracle. It is the four-step kernel of
:mod:`repro.kernels.fft_matmul` with step 3 as that single (2n1, 2n1)
@ (2n1, BLOCK_B) dot; pencils of n <= 128 are one dense DFT matmul.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import twiddle as tw
from repro.kernels.fft_matmul import (DEFAULT_BLOCK_B, default_factors,
                                      pallas_fft)


@functools.partial(jax.jit, static_argnames=('inverse', 'block_b', 'interpret'))
def fft_block(x: jnp.ndarray, *, inverse: bool = False,
              block_b: int = DEFAULT_BLOCK_B,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    """Batched block-complex pencil FFT. x: (2, ..., n) with the leading
    complex axis; transform along the last axis, natural order."""
    n = x.shape[-1]
    if not tw.is_pow2(n):
        raise ValueError(f"pencil length must be pow2, got {n}")
    batch_shape = x.shape[1:-1]
    b = int(np.prod(batch_shape)) if batch_shape else 1
    xr = x.reshape(2, b, n)
    yr, yi = pallas_fft(xr[0], xr[1], inverse=inverse,
                        factors=default_factors(n), block_b=block_b,
                        interpret=interpret, block_complex=True)
    return jnp.stack([yr, yi]).reshape((2,) + batch_shape + (n,))
