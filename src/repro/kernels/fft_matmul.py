"""Pallas TPU kernel: four-step pencil FFT in matmul form (MXU).

Beyond-paper TPU adaptation: the WSE pencil butterfly is VPU-class work
(elementwise FMAC streams); on TPU the compute peak lives in the 128x128
MXU. A length-n pencil is split n = n1 * n2 with n1 = 128 (the MXU edge)
and the index k = n1*k2 + k1:

  1. the n2-point DFTs across the n2 row blocks x[n1*k2 : n1*(k2+1)]
     (VPU, unrolled),
  2. the inter-factor twiddle w_n^{k1*j2} (VPU),
  3. the n1-point DFT of each block as ONE (n1, n1) @ (n1, BLOCK_B)
     matmul (MXU), and
  4. the natural-order emit y[n2*j1 + j2], a strided row store.

Mosaic layout: the (BLOCK_B, n) tile is transposed on entry so the
pencil runs down the rows and BLOCK_B pencils fill the lanes; steps 1-4
then only slice, reshape and stride the row axis, and one transpose
restores the tile on exit. Pencils of n <= 128 are one dense DFT matmul
on the untransposed tile. Complex = planar, 4 real matmuls per complex
matmul (paper's own real-arithmetic form); the block-complex variant
(:mod:`repro.kernels.fft_block`) folds them into one real matmul against
the 2x2 block DFT matrix. Every matmul runs at ``Precision.HIGHEST``
(fp32 accumulation of fp32 operands), like the jnp reference.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import twiddle as tw
from repro.fft.methods import default_interpret
from repro.kernels.fft_pencil import DEFAULT_BLOCK_B, block_rows

Planar = Tuple[jnp.ndarray, jnp.ndarray]

#: the MXU edge: the matmul factor n1 of every pencil longer than this
MXU = 128


def default_factors(n: int) -> Tuple[int, int]:
    """(n1, n2): the matmul factor n1 and the VPU factor n2 of a length-n
    pencil. n <= 128 is one dense DFT (n2 = 1)."""
    return (n, 1) if n <= MXU else (MXU, n // MXU)


def _dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _const_mul(xr, xi, c: complex):
    """(xr + i xi) * c for a host constant c, exact for c in {1, -1, i, -i}."""
    cr, ci = float(c.real), float(c.imag)
    if ci == 0.0:
        return (xr, xi) if cr == 1.0 else (-xr, -xi) if cr == -1.0 \
            else (xr * cr, xi * cr)
    if cr == 0.0:
        return (-xi, xr) if ci == 1.0 else (xi, -xr) if ci == -1.0 \
            else (-xi * ci, xr * ci)
    return xr * cr - xi * ci, xr * ci + xi * cr


def _small_dft(n2: int, inverse: bool) -> np.ndarray:
    """w_{n2}^{j*k}, with the quarter-turn values exact."""
    jk = np.outer(np.arange(n2), np.arange(n2)) % n2
    exact = {0: 1, n2 // 2: -1, n2 // 4: -1j, 3 * n2 // 4: 1j} \
        if n2 % 4 == 0 else {0: 1, n2 // 2: -1}
    sign = 1.0 if inverse else -1.0
    w = np.exp(sign * 2j * np.pi * jk / n2)
    for k, v in exact.items():
        w[jk == k] = np.conj(v) if inverse else v
    return w


def _four_step_rows(xr, xi, consts, sr_ref, si_ref, *, n1: int, n2: int,
                    inverse: bool, block_complex: bool):
    """Steps 1-4 on an (n, B) block whose rows are the pencil; writes
    the natural-order result rows into the (n, B) scratch pair."""
    f_ref, wr_ref, wi_ref = consts
    dft2 = _small_dft(n2, inverse)
    xs = [(xr[k * n1:(k + 1) * n1], xi[k * n1:(k + 1) * n1])
          for k in range(n2)]
    for j2 in range(n2):
        gr = gi = None
        for k2 in range(n2):                       # step 1 (VPU)
            tr, ti = _const_mul(*xs[k2], dft2[j2, k2])
            gr = tr if gr is None else gr + tr
            gi = ti if gi is None else gi + ti
        if j2:                                     # step 2 (row 0: w = 1)
            wr = wr_ref[j2 * n1:(j2 + 1) * n1, :]
            wi = wi_ref[j2 * n1:(j2 + 1) * n1, :]
            gr, gi = gr * wr - gi * wi, gr * wi + gi * wr
        if block_complex:                          # step 3 (MXU)
            y = _dot(f_ref[...], jnp.concatenate([gr, gi], axis=0))
            yr, yi = y[:n1], y[n1:]
        else:
            fr, fi = f_ref[0], f_ref[1]
            yr = _dot(fr, gr) - _dot(fi, gi)
            yi = _dot(fr, gi) + _dot(fi, gr)
        sr_ref[pl.ds(j2, n1, stride=n2), :] = yr   # step 4
        si_ref[pl.ds(j2, n1, stride=n2), :] = yi


def _dense_kernel(f_ref, xr_ref, xi_ref, yr_ref, yi_ref):
    xr, xi = xr_ref[...], xi_ref[...]
    fr, fi = f_ref[0], f_ref[1]
    yr_ref[...] = _dot(xr, fr) - _dot(xi, fi)
    yi_ref[...] = _dot(xr, fi) + _dot(xi, fr)


def _four_step_kernel(f_ref, wr_ref, wi_ref, xr_ref, xi_ref, yr_ref, yi_ref,
                      sr_ref, si_ref, **kw):
    _four_step_rows(xr_ref[...].T, xi_ref[...].T, (f_ref, wr_ref, wi_ref),
                    sr_ref, si_ref, **kw)
    yr_ref[...] = sr_ref[...].T
    yi_ref[...] = si_ref[...].T


def constants(n1: int, n2: int, inverse: bool, block_complex: bool, dtype):
    """Host constants of one plan: the n1-point DFT matrix with the
    inverse's 1/n folded in (exact: n is a power of two) — planar
    (2, n1, n1) or the (2n1, 2n1) block-complex form — and the step-2
    twiddle w_n^{k1*j2} as (n2*n1, 1) planar columns."""
    n = n1 * n2
    fr, fi = tw.dft_matrix_np(n1, inverse=inverse)
    if inverse:
        fr, fi = fr / n, fi / n
    if block_complex:
        f = np.block([[fr, -fi], [fi, fr]])
    else:
        f = np.stack([fr, fi])
    wr, wi = tw.four_step_twiddle_np(n2, n1, inverse=inverse)  # [j2, k1]
    return (jnp.asarray(f, dtype),
            jnp.asarray(wr.reshape(n, 1), dtype),
            jnp.asarray(wi.reshape(n, 1), dtype))


def pallas_fft(xr, xi, *, inverse: bool, factors: Tuple[int, int],
               block_b: int, interpret: Optional[bool],
               block_complex: bool = False) -> Planar:
    """The pallas_call of both four-step kernels on a (b, n) planar
    batch; ``block_complex`` picks the one-dot form of step 3."""
    b, n = xr.shape
    n1, n2 = factors
    if n1 * n2 != n:
        raise ValueError(f"factors {n1}*{n2} != {n}")
    bb = block_rows(b, block_b)
    pad = (-b) % bb
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, pad), (0, 0)))
    bp = b + pad
    dt = xr.dtype
    f, wr, wi = constants(n1, n2, inverse, block_complex and n2 > 1, dt)

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    tile = pl.BlockSpec((bb, n), lambda i: (i, 0))
    if n2 == 1:
        kernel, consts, scratch = _dense_kernel, [f], []
    else:
        kernel = functools.partial(_four_step_kernel, n1=n1, n2=n2,
                                   inverse=inverse,
                                   block_complex=block_complex)
        consts = [f, wr, wi]
        scratch = [pltpu.VMEM((n, bb), dt), pltpu.VMEM((n, bb), dt)]
    yr, yi = pl.pallas_call(
        kernel,
        grid=(bp // bb,),
        in_specs=[whole(c) for c in consts] + [tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((bp, n), dt)] * 2,
        scratch_shapes=scratch,
        interpret=default_interpret() if interpret is None else interpret,
    )(*consts, xr, xi)
    if pad:
        yr, yi = yr[:b], yi[:b]
    return yr, yi


@functools.partial(jax.jit, static_argnames=('inverse', 'block_b', 'interpret', 'factors'))
def fft_matmul(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
               factors: Optional[Tuple[int, int]] = None,
               block_b: int = DEFAULT_BLOCK_B,
               interpret: Optional[bool] = None) -> Planar:
    """Batched four-step pencil FFT via pl.pallas_call. Input (..., n).
    ``factors=(n1, n2)`` overrides :func:`default_factors`.

    VMEM per grid step (fp32, n=512, block_b=128): x+y tiles and the
    (n, block_b) scratch pair 6*256 KiB, DFT matrix 128 KiB."""
    n = re.shape[-1]
    if not tw.is_pow2(n):
        raise ValueError(f"pencil length must be pow2, got {n}")
    batch_shape = re.shape[:-1]
    b = int(np.prod(batch_shape)) if batch_shape else 1
    yr, yi = pallas_fft(re.reshape(b, n), im.reshape(b, n), inverse=inverse,
                        factors=factors or default_factors(n),
                        block_b=block_b, interpret=interpret)
    return yr.reshape(batch_shape + (n,)), yi.reshape(batch_shape + (n,))
