"""Pallas kernel: fused pencil FFT + twiddle rotation + transposed emit.

The distributed supersteps (``fft/pencil.py``, ``fft/large1d.py``) used
to run three separate XLA ops between two swaps: the local FFT, the
inter-superstep twiddle multiply, and the transpose that puts the
just-transformed axis where the collective splits it. Each materialized
an HBM-round-trip intermediate. This kernel is the whole superstep
producer in one pass: a (BLOCK_B, n) tile of pencils is staged into
VMEM, all log2(n) Stockham stages run in place (the same
``_stockham_rows`` the plain pencil kernel uses, so outputs stay
bit-identical to the unfused tier), the twiddle tile is applied in
registers, and the BlockSpec writes the tile *transposed* — the swap
reads pre-rotated, pre-transposed data and XLA never emits the
intermediate. The stages already run with the pencil down the rows, so
the transposed emit costs nothing extra: it is the stages' own layout.

Grid: 2-D over (leading slices, batch tiles). The master twiddle table
w_n^k, k in [0, n/2) is broadcast to every step exactly as in
``fft_pencil``; the optional inter-superstep twiddle (wr, wi) rides in
with the same BlockSpec as the data. The emitted (n, BLOCK_B) tile is
lane-dense: BLOCK_B is 128, or the whole (padded) batch when smaller.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import twiddle as tw
from repro.fft.methods import default_interpret
from repro.kernels.fft_pencil import (DEFAULT_BLOCK_B, _stockham_rows,
                                      block_rows, master_table)

Planar = Tuple[jnp.ndarray, jnp.ndarray]


def _kernel(mr_ref, mi_ref, xr_ref, xi_ref, *rest,
            n: int, inverse: bool, has_w: bool, pin: bool):
    if has_w:
        wr_ref, wi_ref, yr_ref, yi_ref = rest
    else:
        yr_ref, yi_ref = rest
    yr, yi = _stockham_rows(xr_ref[0].T, xi_ref[0].T, mr_ref, mi_ref,
                            n=n, inverse=inverse, pin=pin)
    if has_w:
        wr = wr_ref[0].T
        wi = wi_ref[0].T
        yr, yi = yr * wr - yi * wi, yr * wi + yi * wr
    yr_ref[0] = yr
    yi_ref[0] = yi


@functools.partial(jax.jit,
                   static_argnames=('inverse', 'block_b', 'interpret'))
def fft_twiddle_transpose(re: jnp.ndarray, im: jnp.ndarray,
                          wr: Optional[jnp.ndarray] = None,
                          wi: Optional[jnp.ndarray] = None, *,
                          inverse: bool = False,
                          block_b: int = DEFAULT_BLOCK_B,
                          interpret: Optional[bool] = None) -> Planar:
    """Fused superstep via pl.pallas_call. Input (..., b, n) planar;
    output (..., n, b): ``out[..., k, j] = (W * FFT(x))[..., j, k]``
    with the FFT along the last axis and W = (wr, wi) an optional planar
    twiddle broadcastable against the pre-transpose output (..., b, n).

    VMEM working set per grid step: 4-6 arrays * block_b * n * 4 B plus
    the (n/2, 1) master table — same envelope as ``fft_pencil`` with one
    extra tile pair when the twiddle is present.
    """
    if re.ndim < 2:
        raise ValueError("fused superstep needs a batch axis next to "
                         f"the pencil axis, got shape {re.shape}")
    n = re.shape[-1]
    if not tw.is_pow2(n):
        raise ValueError(f"pencil length must be pow2, got {n}")
    b = re.shape[-2]
    lead = re.shape[:-2]
    nl = int(np.prod(lead)) if lead else 1
    has_w = wr is not None
    xr = re.reshape(nl, b, n)
    xi = im.reshape(nl, b, n)
    if has_w:
        twr = jnp.broadcast_to(jnp.asarray(wr, re.dtype),
                               re.shape).reshape(nl, b, n)
        twi = jnp.broadcast_to(jnp.asarray(wi, re.dtype),
                               re.shape).reshape(nl, b, n)

    bb = block_rows(b, block_b)
    pad = (-b) % bb
    if pad:
        xr = jnp.pad(xr, ((0, 0), (0, pad), (0, 0)))
        xi = jnp.pad(xi, ((0, 0), (0, pad), (0, 0)))
        if has_w:
            twr = jnp.pad(twr, ((0, 0), (0, pad), (0, 0)))
            twi = jnp.pad(twi, ((0, 0), (0, pad), (0, 0)))
    bp = b + pad

    interpret = default_interpret() if interpret is None else interpret
    mr, mi, table = master_table(n, inverse, re.dtype)
    tile_in = pl.BlockSpec((1, bb, n), lambda l, i: (l, i, 0))
    in_specs = [table, table, tile_in, tile_in]
    ops = [mr, mi, xr, xi]
    if has_w:
        in_specs += [tile_in, tile_in]                  # superstep twiddle
        ops += [twr, twi]
    tile_out = pl.BlockSpec((1, n, bb), lambda l, i: (l, 0, i))
    out_shape = [jax.ShapeDtypeStruct((nl, n, bp), re.dtype),
                 jax.ShapeDtypeStruct((nl, n, bp), im.dtype)]
    yr, yi = pl.pallas_call(
        functools.partial(_kernel, n=n, inverse=inverse, has_w=has_w,
                          pin=interpret),
        grid=(nl, bp // bb),
        in_specs=in_specs,
        out_specs=[tile_out, tile_out],
        out_shape=out_shape,
        interpret=interpret,
    )(*ops)
    if pad:
        yr, yi = yr[:, :, :b], yi[:, :, :b]
    return yr.reshape(lead + (n, b)), yi.reshape(lead + (n, b))
