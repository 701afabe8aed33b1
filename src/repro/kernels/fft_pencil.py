"""Pallas TPU kernel: batched radix-2 Stockham pencil FFT (paper-faithful).

The paper's Listing 1 runs an iterative radix-2 Cooley-Tukey on one PE
with SIMD array-descriptor operations and an explicit reshape phase that
keeps even/odd elements contiguous. The TPU analogue of a WSE PE block is
one VMEM-resident tile: a (BLOCK_B, n) batch of pencils is staged
HBM->VMEM by the BlockSpec, all log2(n) stages run in-register/VMEM on
the VPU, and the result streams back. The Stockham indexing keeps
even/odd contiguity *by construction* — it is the vectorized form of the
paper's reshape trick.

Mosaic layout: a (BLOCK_B, n) tile arrives with the pencil along the
lanes; the kernel transposes it so the pencil runs down the rows and
BLOCK_B pencils fill the lanes. Every Stockham reshape then splits only
the row axis (which Mosaic supports for any power-of-two split), the
butterfly partners are the two row halves, and the stage's twiddles
w_{2L}^j are one strided row load from the packed master table
w_n^k, k in [0, n/2) — the paper's single ``roots_of_unity`` array in PE
memory. Same float ops in the same order as the jnp reference
(``core.fft1d.fft_stockham``), so both tiers round alike.

In interpret mode the kernel body is compiled by XLA next to the jnp
reference, and it carries the reference's contraction pin (see
``fft_stockham``) so the tiers stay bit-identical there. Mosaic does
not share XLA's fusion-dependent contraction, so the compiled kernel
runs the plain butterfly.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import scopes
from repro.core import twiddle as tw
from repro.fft.methods import default_interpret

Planar = Tuple[jnp.ndarray, jnp.ndarray]

#: pencils per grid step: a full 128-lane row once the pencil is
#: transposed into the rows
DEFAULT_BLOCK_B = 128


def block_rows(b: int, block_b: int) -> int:
    """Rows of one batch tile: ``block_b``, or the whole batch rounded
    up to a sublane multiple when it is smaller (a block that spans the
    whole padded axis is always a legal Mosaic block)."""
    return block_b if b >= block_b else -(-b // 8) * 8


def _stockham_rows(xr, xi, wr_ref, wi_ref, *, n: int, inverse: bool,
                   pin: bool):
    """All log2(n) Stockham stages on an (n, B) block whose ROWS are the
    pencil (B pencils side by side in the lanes).

    ``wr_ref``/``wi_ref`` hold the master table w_n^k, k in [0, n/2), as
    (n/2, 1) columns for the requested DIRECTION
    (``tw.roots_of_unity_np(n, inverse=...)``): negating in the host
    table instead of per stage keeps the op sequence identical to the
    jnp reference path, so XLA's FMA fusion rounds both tiers the same
    way and plan outputs stay bit-identical. ``pin`` adds the
    reference's contraction pin (interpret mode only)."""
    stages = tw.log2i(n)
    b = xr.shape[-1]
    for s in range(stages):
        L = 1 << s
        c = n >> s
        stride = n // (2 * L)          # master-table stride for w_{2L}^j
        wr = wr_ref[pl.ds(0, L, stride=stride), :]     # (L, 1)
        wi = wi_ref[pl.ds(0, L, stride=stride), :]
        vr = xr.reshape(2, c // 2, L, b)
        vi = xi.reshape(2, c // 2, L, b)
        ar, ai = vr[0], vi[0]
        br, bi = vr[1], vi[1]
        if pin:
            one = (ar - ar) + 1.0
            tr = (br * wr) * one - (bi * wi) * one
            ti = (br * wi) * one + (bi * wr) * one
        else:
            tr = br * wr - bi * wi
            ti = br * wi + bi * wr
        xr = jnp.concatenate([ar + tr, ar - tr], axis=1).reshape(n, b)
        xi = jnp.concatenate([ai + ti, ai - ti], axis=1).reshape(n, b)
    if inverse:
        xr = xr * (1.0 / n)
        xi = xi * (1.0 / n)
    return xr, xi


def master_table(n: int, inverse: bool, dtype):
    """The packed twiddle table w_n^k, k in [0, n/2), as (n/2, 1) planar
    columns, and its BlockSpec (broadcast to every grid step)."""
    wr_np, wi_np = tw.roots_of_unity_np(n, inverse=inverse)
    h = max(n // 2, 1)
    wr = jnp.asarray(wr_np[:h].reshape(h, 1), dtype=dtype)
    wi = jnp.asarray(wi_np[:h].reshape(h, 1), dtype=dtype)
    return wr, wi, pl.BlockSpec((h, 1), lambda *_: (0, 0))


def _kernel(wr_ref, wi_ref, xr_ref, xi_ref, yr_ref, yi_ref, *, n: int,
            inverse: bool, pin: bool):
    yr, yi = _stockham_rows(xr_ref[...].T, xi_ref[...].T, wr_ref, wi_ref,
                            n=n, inverse=inverse, pin=pin)
    yr_ref[...] = yr.T
    yi_ref[...] = yi.T


@functools.partial(jax.jit, static_argnames=('inverse', 'block_b', 'interpret'))
def fft_pencil(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
               block_b: int = DEFAULT_BLOCK_B,
               interpret: Optional[bool] = None) -> Planar:
    """Batched pencil FFT via pl.pallas_call. Input (..., n) planar.

    VMEM working set per grid step: 2 arrays * block_b * n * 4 B in and
    out, plus the stage temporaries and the (n/2, 1) twiddle table.
    block_b=128, n=512 -> 256 KiB per array.
    """
    n = re.shape[-1]
    if not tw.is_pow2(n):
        raise ValueError(f"pencil length must be pow2, got {n}")
    batch_shape = re.shape[:-1]
    b = int(np.prod(batch_shape)) if batch_shape else 1
    bb = block_rows(b, block_b)
    pad = (-b) % bb
    with scopes.scope(scopes.RELAYOUT):
        xr = re.reshape(b, n)
        xi = im.reshape(b, n)
        if pad:
            xr = jnp.pad(xr, ((0, pad), (0, 0)))
            xi = jnp.pad(xi, ((0, pad), (0, 0)))
    bp = b + pad

    interpret = default_interpret() if interpret is None else interpret
    wr, wi, table = master_table(n, inverse, re.dtype)
    tile = pl.BlockSpec((bb, n), lambda i: (i, 0))
    yr, yi = pl.pallas_call(
        functools.partial(_kernel, n=n, inverse=inverse, pin=interpret),
        grid=(bp // bb,),
        in_specs=[table, table, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((bp, n), re.dtype),
                   jax.ShapeDtypeStruct((bp, n), im.dtype)],
        interpret=interpret,
    )(wr, wi, xr, xi)
    with scopes.scope(scopes.RELAYOUT):
        if pad:
            yr, yi = yr[:b], yi[:b]
        return yr.reshape(batch_shape + (n,)), yi.reshape(batch_shape + (n,))
