"""The lower-precision control: the reference's transform, put in the
program's place and computed in bfloat16.

Each axis is one dense DFT matmul with bfloat16 operands and float32
accumulation, which is what a TPU matmul at default precision does: the
step that would tempt a later change to the kernels (their matmuls run
at ``Precision.HIGHEST``). The object has the plan's surface that the
harness drives (``forward``, ``inverse``, ``in_sharding``), so a run
with the control in place goes through the same window and the same
check. Its ``correct`` has to come out false. With ``dtype='float32'``
(``Precision.HIGHEST``) the same code is a float32 DFT, which the tests
use to show that the control fails by its precision and not by a fault.

The plan's own ``compute_dtype=bfloat16`` cannot serve: its complex
entries refuse bfloat16 results (``lax.complex``), and its Pallas tier
ignores the option.
"""
from __future__ import annotations

import numpy as np


class Control:
    def __init__(self, cfg: dict, mesh, dtype: str = 'bfloat16'):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from bench.harness import real_input
        self.shape = tuple(cfg['shape'])
        self.real = real_input(cfg)
        self.in_sharding = NamedSharding(mesh, P(*cfg['mesh_axes'], None))
        self._dt = jnp.dtype(dtype)
        self._prec = (jax.lax.Precision.HIGHEST if dtype == 'float32'
                      else jax.lax.Precision.DEFAULT)
        self.forward = jax.jit(self._forward)
        self.inverse = jax.jit(self._inverse)

    # -- dense DFT matrices, float64 on the host, cast to the operand dtype

    def _mat(self, rows, cols, sign, scale=1.0, weights=None):
        import jax.numpy as jnp
        n = cols if sign < 0 else rows
        k = np.arange(rows)[:, None] * np.arange(cols)[None, :]
        w = np.exp(sign * 2j * np.pi * (k % n) / n) * scale
        if weights is not None:
            w = w * weights[None, :]
        return (jnp.asarray(w.real, self._dt), jnp.asarray(w.imag, self._dt))

    def _axis(self, re, im, axis, mat):
        """(re + i im) transformed along ``axis`` by the complex matrix
        (rows = output bins)."""
        import jax.numpy as jnp
        mr, mi = mat

        def mm(m, x):
            y = jnp.tensordot(m, x.astype(self._dt), axes=([1], [axis]),
                              precision=self._prec,
                              preferred_element_type=jnp.float32)
            return jnp.moveaxis(y, 0, axis)
        if im is None:
            return mm(mr, re), mm(mi, re)
        return mm(mr, re) - mm(mi, im), mm(mr, im) + mm(mi, re)

    def _forward(self, x):
        import jax.numpy as jnp
        n0, n1, n2 = self.shape
        if self.real:
            re, im = self._axis(x, None, 2, self._mat(n2 // 2 + 1, n2, -1))
        else:
            re, im = self._axis(x.real, x.imag, 2, self._mat(n2, n2, -1))
        re, im = self._axis(re, im, 1, self._mat(n1, n1, -1))
        re, im = self._axis(re, im, 0, self._mat(n0, n0, -1))
        return jnp.asarray(re + 1j * im, jnp.complex64)

    def _inverse(self, y):
        import jax.numpy as jnp
        n0, n1, n2 = self.shape
        re, im = self._axis(y.real, y.imag, 0, self._mat(n0, n0, 1, 1 / n0))
        re, im = self._axis(re, im, 1, self._mat(n1, n1, 1, 1 / n1))
        if not self.real:
            re, im = self._axis(re, im, 2, self._mat(n2, n2, 1, 1 / n2))
            return jnp.asarray(re + 1j * im, jnp.complex64)
        # c2r: x[j] = Re(sum_k c_k Y_k e^{2 pi i jk/n}) / n, c_k = 2 but
        # 1 at k = 0 and n/2 (numpy's irfft drops their imaginary parts)
        nh = n2 // 2 + 1
        c = np.full(nh, 2.0)
        c[0] = c[-1] = 1.0
        mr, mi = self._mat(n2, nh, 1, 1 / n2, weights=c)
        xr, _ = self._axis(re, im, 2, (mr, mi))
        return xr
