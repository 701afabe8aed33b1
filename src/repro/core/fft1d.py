"""Local (single-device) pencil FFTs, planar complex, batched.

Two algorithms:

* ``fft_stockham`` — radix-2 iterative Cooley-Tukey in Stockham autosort
  form. This is the **paper-faithful** pencil: identical 5*n*log2(n) real
  flop count and the same even/odd recombination schedule as the paper's
  Listing 1; the Stockham indexing keeps even/odd elements contiguous *by
  construction*, which is exactly what the paper's explicit ``reshape``
  phase re-establishes after each iteration on the WSE.

* ``fft_four_step`` — Bailey four-step: the pencil is reshaped (n1, n2)
  and each factor's DFT becomes a dense matmul against a precomputed DFT
  matrix, with the inter-factor twiddle fused in between. This is the
  **TPU-adapted** pencil: it moves the work from the VPU (butterflies)
  onto the MXU (matmuls) — beyond-paper, recorded separately in
  EXPERIMENTS.md. The same adaptation is cited by the paper itself as
  Google's TPU approach [17]; here it is applied *per pencil inside* the
  paper's pencil decomposition.

All functions map over arbitrary leading batch dims; the transform runs
along the trailing axis.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.strategies import dbarrier
from repro.core import twiddle as tw
from repro.core.twiddle import Planar


# ---------------------------------------------------------------------------
# Stockham radix-2 (paper-faithful)
# ---------------------------------------------------------------------------

def fft_stockham(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
                 compute_dtype: Optional[jnp.dtype] = None) -> Planar:
    """Batched radix-2 Stockham FFT along the last axis.

    Invariant maintained: after the stage with subproblem size L, the
    array viewed as (c, L) rows holds X[k, :] = DFT_L(x[k::c]) with
    c = n / L. Start L=1 (natural order input), end L=n (natural order
    output) — no bit reversal.
    """
    n = re.shape[-1]
    stages = tw.log2i(n)
    batch = re.shape[:-1]
    if compute_dtype is not None:
        re, im = re.astype(compute_dtype), im.astype(compute_dtype)
    acc_dtype = re.dtype

    twids = tw.stage_twiddles_np(n, inverse=inverse)
    # view (c, L); combine rows k and k + c/2.
    for s in range(stages):
        L = 1 << s
        c = n >> s
        wr = jnp.asarray(twids[s][0], dtype=acc_dtype)   # (L,)
        wi = jnp.asarray(twids[s][1], dtype=acc_dtype)
        xr = re.reshape(batch + (2, c // 2, L))
        xi = im.reshape(batch + (2, c // 2, L))
        ar, ai = xr[..., 0, :, :], xi[..., 0, :, :]
        br, bi = xr[..., 1, :, :], xi[..., 1, :, :]
        # t = w * b   (4 mul + 2 add — paper Listing 1 l.36-42). Each
        # product is multiplied by a data-derived exact one, so it rounds
        # before the add: XLA:CPU contracts a*b - c*d into an FMA, and
        # which product it fuses depends on the fusion around the stage
        # (a complex packing, an operator plan's pointwise stage), so
        # unpinned butterflies give different bits in different programs
        # (the trick of ``fft.spectral_mul``)
        one = (ar - ar) + jnp.asarray(1.0, acc_dtype)
        tr = (br * wr) * one - (bi * wi) * one
        ti = (br * wi) * one + (bi * wr) * one
        re = jnp.concatenate([ar + tr, ar - tr], axis=-1).reshape(batch + (n,))
        im = jnp.concatenate([ai + ti, ai - ti], axis=-1).reshape(batch + (n,))
    if inverse:
        scale = jnp.asarray(1.0 / n, dtype=acc_dtype)
        re, im = re * scale, im * scale
    return re, im


# ---------------------------------------------------------------------------
# Bailey four-step (MXU matmul form, beyond-paper)
# ---------------------------------------------------------------------------

def fft_four_step(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
                  factors: Optional[Tuple[int, int]] = None,
                  compute_dtype: Optional[jnp.dtype] = None,
                  precision=jax.lax.Precision.HIGHEST) -> Planar:
    """Batched four-step FFT along the last axis.

    x[k], k = n2*k1 + k2  ->  y[j], j = j1 + n1*j2:
      1. A[k1, k2]  = x.reshape(n1, n2)
      2. B = F_{n1} @ A            (columns DFT, contraction dim n1)
      3. C = B * W, W[j1,k2] = w_n^{j1 k2}
      4. D = C @ F_{n2}            (rows DFT, contraction dim n2)
      5. y = D.T.reshape(n)
    Complex arithmetic is planar: 4 real matmuls per complex matmul.
    Matmul inputs may be cast to ``compute_dtype`` (e.g. bf16) while the
    twiddle scaling and accumulation stay fp32.
    """
    n = re.shape[-1]
    n1, n2 = factors if factors is not None else tw.four_step_factors(n)
    if n1 * n2 != n:
        raise ValueError(f"factors {n1}*{n2} != {n}")
    batch = re.shape[:-1]
    out_dtype = re.dtype
    md = compute_dtype or re.dtype

    f1r, f1i = (jnp.asarray(a, dtype=md) for a in tw.dft_matrix_np(n1, inverse=inverse))
    f2r, f2i = (jnp.asarray(a, dtype=md) for a in tw.dft_matrix_np(n2, inverse=inverse))
    wr, wi = (jnp.asarray(a, dtype=out_dtype) for a in
              tw.four_step_twiddle_np(n1, n2, inverse=inverse))

    ar = re.reshape(batch + (n1, n2)).astype(md)
    ai = im.reshape(batch + (n1, n2)).astype(md)

    dot = functools.partial(jnp.einsum, precision=precision,
                            preferred_element_type=jnp.float32)
    # step 2: B = F1 @ A  (planar)
    br = dot('jk,...kl->...jl', f1r, ar) - dot('jk,...kl->...jl', f1i, ai)
    bi = dot('jk,...kl->...jl', f1r, ai) + dot('jk,...kl->...jl', f1i, ar)
    # step 3: twiddle (elementwise, fp32)
    cr = br * wr - bi * wi
    ci = br * wi + bi * wr
    cr, ci = cr.astype(md), ci.astype(md)
    # step 4: D = C @ F2
    dr = dot('...jk,kl->...jl', cr, f2r) - dot('...jk,kl->...jl', ci, f2i)
    di = dot('...jk,kl->...jl', cr, f2i) + dot('...jk,kl->...jl', ci, f2r)
    # step 5: transpose + flatten
    yr = jnp.swapaxes(dr, -1, -2).reshape(batch + (n,)).astype(out_dtype)
    yi = jnp.swapaxes(di, -1, -2).reshape(batch + (n,)).astype(out_dtype)
    if inverse:
        yr, yi = yr / n, yi / n
    return yr, yi


def fft_four_step_axis(re: jnp.ndarray, im: jnp.ndarray, axis: int, *,
                       inverse: bool = False,
                       compute_dtype: Optional[jnp.dtype] = None,
                       precision=jax.lax.Precision.HIGHEST) -> Planar:
    """Four-step FFT along an arbitrary axis with NO moveaxis copies.

    Perf iteration on the memory roofline term (EXPERIMENTS.md §Perf):
    the axis is reshaped in place to (n1, n2) — free when the split is
    of one axis in row-major order — and both factor DFTs contract the
    target axis directly via einsum, so XLA feeds the MXU without a
    separate HBM transpose pass. Output remains in natural order along
    ``axis`` (the final factor transpose is fused into the second
    einsum's output indices).
    """
    axis = axis % re.ndim
    n = re.shape[axis]
    n1, n2 = tw.four_step_factors(n)
    pre = re.shape[:axis]
    post = re.shape[axis + 1:]
    out_dtype = re.dtype
    md = compute_dtype or re.dtype

    f1r, f1i = (jnp.asarray(a, dtype=md) for a in tw.dft_matrix_np(n1, inverse=inverse))
    f2r, f2i = (jnp.asarray(a, dtype=md) for a in tw.dft_matrix_np(n2, inverse=inverse))
    wr, wi = (jnp.asarray(a, dtype=jnp.float32) for a in
              tw.four_step_twiddle_np(n1, n2, inverse=inverse))

    shp = pre + (n1, n2) + post
    ar = re.reshape(shp).astype(md)
    ai = im.reshape(shp).astype(md)
    # index letters: a..e pre-axes, then (j=n1 out / k=n1 in, l=n2 in,
    # m=n2 out), then w.. post-axes
    na, nb = len(pre), len(post)
    A = ''.join(chr(ord('a') + i) for i in range(na))
    Z = ''.join(chr(ord('u') + i) for i in range(nb))
    dot = functools.partial(jnp.einsum, precision=precision,
                            preferred_element_type=jnp.float32)
    s2 = f'jk,{A}kl{Z}->{A}jl{Z}'
    # step 2: B[j1, k2] = sum_k1 F1[j1, k1] A[k1, k2]
    br = dot(s2, f1r, ar) - dot(s2, f1i, ai)
    bi = dot(s2, f1r, ai) + dot(s2, f1i, ar)
    # step 3: twiddle W[j1, k2] (fp32), broadcast over pre/post axes
    wsh = (1,) * na + (n1, n2) + (1,) * nb
    wr_, wi_ = wr.reshape(wsh), wi.reshape(wsh)
    cr = br * wr_ - bi * wi_
    ci = br * wi_ + bi * wr_
    cr, ci = cr.astype(md), ci.astype(md)
    # step 4 (+ fused factor transpose): D[j2, j1] = sum_k2 C[j1,k2] F2[k2,j2]
    s4 = f'{A}jl{Z},lm->{A}mj{Z}'
    dr = dot(s4, cr, f2r) - dot(s4, ci, f2i)
    di = dot(s4, cr, f2i) + dot(s4, ci, f2r)
    yr = dr.reshape(pre + (n,) + post).astype(out_dtype)
    yi = di.reshape(pre + (n,) + post).astype(out_dtype)
    if inverse:
        scale = jnp.asarray(1.0 / n, out_dtype)
        yr, yi = yr * scale, yi * scale
    return yr, yi


@functools.lru_cache(maxsize=None)
def _block_consts_np(n1: int, n2: int, inverse: bool):
    """Constants for the block-complex four-step (§Perf iteration 2).

    F1b[c, j, d, k]  — one real matmul computes both complex components:
        [yr; yi] = [[Fr, -Fi], [Fi, Fr]] @ [xr; xi]
    G[c, m, j, d, l] — twiddle FOLDED into the second factor DFT:
        D[j1, j2] = sum_k2 B[j1, k2] * (W[j1, k2] F2[k2, j2])
    so steps 3+4 are ONE batched matmul and no elementwise twiddle pass
    ever touches HBM. G is (2, n2, n1, 2, n2) ~ tiny constant.
    """
    f1r, f1i = tw.dft_matrix_np(n1, inverse=inverse)
    f2r, f2i = tw.dft_matrix_np(n2, inverse=inverse)
    wr, wi = tw.four_step_twiddle_np(n1, n2, inverse=inverse)
    f1b = np.zeros((2, n1, 2, n1))
    f1b[0, :, 0, :], f1b[0, :, 1, :] = f1r, -f1i
    f1b[1, :, 0, :], f1b[1, :, 1, :] = f1i, f1r
    # complex G[j, l, m] = W[j, l] * F2[l, m]
    gr = wr[:, :, None] * f2r[None] - wi[:, :, None] * f2i[None]
    gi = wr[:, :, None] * f2i[None] + wi[:, :, None] * f2r[None]
    g = np.zeros((2, n2, n1, 2, n2))          # [c, m, j, d, l]
    g[0, :, :, 0, :] = gr.transpose(2, 0, 1)
    g[0, :, :, 1, :] = -gi.transpose(2, 0, 1)
    g[1, :, :, 0, :] = gi.transpose(2, 0, 1)
    g[1, :, :, 1, :] = gr.transpose(2, 0, 1)
    return f1b, g


def fft_four_step_block(x: jnp.ndarray, axis: int, *, inverse: bool = False,
                        compute_dtype: Optional[jnp.dtype] = None,
                        precision=None) -> jnp.ndarray:
    """Block-complex four-step FFT along ``axis`` of x, where x carries
    a leading complex axis of size 2 (x[0]=re, x[1]=im). Two dots total,
    zero planar elementwise passes. Natural-order output.

    bf16 inputs keep bf16 *operands* (MXU-native, fp32 accumulation via
    preferred_element_type) — forcing HIGHEST precision would upcast the
    whole array to f32 and XLA then cancels the bf16 converts around the
    transpose all_to_alls, silently doubling wire bytes (measured)."""
    axis = axis % x.ndim
    n = x.shape[axis]
    n1, n2 = tw.four_step_factors(n)
    pre = x.shape[1:axis]                   # between complex axis and target
    post = x.shape[axis + 1:]
    out_dtype = x.dtype
    md = compute_dtype or x.dtype
    if precision is None:
        precision = (jax.lax.Precision.DEFAULT if md == jnp.bfloat16
                     else jax.lax.Precision.HIGHEST)
    f1b_np, g_np = _block_consts_np(n1, n2, inverse)
    f1b = jnp.asarray(f1b_np, md)
    g = jnp.asarray(g_np, md)

    a = x.reshape((2,) + pre + (n1, n2) + post).astype(md)
    na, nb = len(pre), len(post)
    # index letters must avoid the specials (c, d, j, l, m) — with 3+
    # leading batch dims 'abc...' would collide with the complex axis
    A = 'abefgh'[:na]
    Z = 'wxyz'[:nb]
    assert len(A) == na and len(Z) == nb, (pre, post)
    dot = functools.partial(jnp.einsum, precision=precision,
                            preferred_element_type=jnp.float32)
    # step 2 (complex matmul as one real dot over (d, k)):
    b = dot(f'cjdk,d{A}kl{Z}->c{A}jl{Z}', f1b, a).astype(md)
    # steps 3+4 fused (+ factor transpose into output index order (m, j)):
    d = dot(f'cmjdl,d{A}jl{Z}->c{A}mj{Z}', g, b)
    y = d.reshape((2,) + pre + (n,) + post).astype(out_dtype)
    if inverse:
        y = y * jnp.asarray(1.0 / n, out_dtype)
    return y


# ---------------------------------------------------------------------------
# Fused superstep reference: FFT + twiddle rotation + transposed emit
# ---------------------------------------------------------------------------

def fft_twiddle_transpose(re: jnp.ndarray, im: jnp.ndarray,
                          wr=None, wi=None, *, inverse: bool = False,
                          fft_fn=None,
                          compute_dtype: Optional[jnp.dtype] = None) -> Planar:
    """Reference (pure-jnp) fused superstep: FFT along the LAST axis,
    optional planar twiddle multiply, and emit with the last two axes
    exchanged — ``out[..., k, j] = (W * FFT(x))[..., j, k]``.

    This is the jnp twin of the Pallas kernel in
    :mod:`repro.kernels.fft_fused`: the distributed supersteps hand its
    output straight to the swap, so the rotation and the transpose that
    XLA previously materialized as separate HBM passes between the local
    FFT and the collective become one fused emit. ``wr``/``wi`` must
    broadcast against the pre-transpose FFT output (..., b, n); pass
    None for a transpose-only superstep (the 3-D pencil path, which has
    no inter-superstep twiddle)."""
    fft_fn = fft_stockham if fft_fn is None else fft_fn
    yr, yi = fft_fn(re, im, inverse=inverse, compute_dtype=compute_dtype)
    if wr is not None:
        yr, yi = yr * wr - yi * wi, yr * wi + yi * wr
    return jnp.swapaxes(yr, -1, -2), jnp.swapaxes(yi, -1, -2)


# ---------------------------------------------------------------------------
# Real-input pencils: pack-two-reals-as-one-complex rfft / irfft
# ---------------------------------------------------------------------------
#
# The classic halving trick: a length-n real FFT costs one length-n/2
# *complex* FFT plus an O(n) Hermitian post-combine. Pack c[t] = a[2t] +
# i*a[2t+1], C = FFT_{n/2}(c); with Cm[k] = C[(n/2-k) mod n/2] the even/
# odd half-spectra are E = (C + conj(Cm))/2, O = (C - conj(Cm))/(2i) and
# the half spectrum is A[k] = E[k] + w_n^k O[k] (k < n/2), A[n/2] =
# E[0] - O[0]. These are the generic ``real_fn`` fallbacks the method
# registry wraps around any complex pencil implementation.

def rfft_pencil(x: jnp.ndarray, *, cfft, dtype=None) -> Planar:
    """Half-spectrum rfft of a real array along the last axis.

    ``cfft(re, im) -> (re, im)`` is any length-n/2 *forward* complex FFT
    (one of the registry pencils). Output planar, last axis n//2 + 1 —
    exactly ``np.fft.rfft``'s layout. Imaginary parts of bins 0 and n/2
    are exactly zero by construction (not just numerically)."""
    n = x.shape[-1]
    if n % 2:
        raise ValueError(f"rfft pencil needs an even length, got {n}")
    h = n // 2
    if dtype is not None:
        x = x.astype(dtype)
    cr, ci = cfft(x[..., 0::2], x[..., 1::2])
    # Cm[k] = C[(h - k) mod h], read as a gather with constant indices:
    # XLA:TPU computed a reverse of the pencil axis fused into the
    # inverse's combine wrongly (see irfft_pencil), and of the forms
    # measured on TPU v5e the gather is exact and the fastest here
    mirror = (h - np.arange(h)) % h
    cmr = jnp.take(cr, mirror, axis=-1)
    cmi = jnp.take(ci, mirror, axis=-1)
    er, ei = (cr + cmr) * 0.5, (ci - cmi) * 0.5
    our, oui = (ci + cmi) * 0.5, (cmr - cr) * 0.5
    wr, wi = (jnp.asarray(a, cr.dtype) for a in tw.rfft_split_twiddle_np(n))
    ar = er + (our * wr - oui * wi)
    ai = ei + (our * wi + oui * wr)
    # A[n/2] = E[0] - O[0]; E[0], O[0] are exactly real (Cm[0] == C[0])
    edge_r = er[..., :1] - our[..., :1]
    return (jnp.concatenate([ar, edge_r], axis=-1),
            jnp.concatenate([ai, jnp.zeros_like(edge_r)], axis=-1))


def irfft_pencil(re: jnp.ndarray, im: jnp.ndarray, *, cifft) -> jnp.ndarray:
    """Exact inverse of :func:`rfft_pencil`: planar half spectrum (last
    axis n//2 + 1) -> real array (last axis n). ``cifft`` is any
    length-n/2 *inverse* complex FFT (with its 1/(n/2) scaling), so the
    1/n normalization of ``np.fft.irfft`` comes out exactly."""
    nh = re.shape[-1]
    h = nh - 1
    n = 2 * h
    if h < 1:
        raise ValueError(f"irfft pencil needs >= 2 spectrum bins, got {nh}")
    ar, ai = re[..., :h], im[..., :h]
    # Am[k] = A[h - k], k in [0, h). The barrier keeps the reverse out of
    # the combine's fusion: fused into it at large batch (>= 2^16
    # pencils of 257 or 513 bins, as in the per-device (256, 256, 258)
    # of the sharded 512^3 irfft), XLA:TPU computed it wrongly. (A
    # gather, as in rfft_pencil, is exact there too, but on XLA:CPU it
    # fuses differently per batch shape and batched executions stop
    # being bit-identical to per-request ones.)
    amr, ami = dbarrier((jnp.flip(re[..., 1:], -1),
                         jnp.flip(im[..., 1:], -1)))
    er, ei = (ar + amr) * 0.5, (ai - ami) * 0.5
    # w^k O[k] = (A[k] - conj(Am[k])) / 2, then rotate by w^{-k}
    tr, ti = (ar - amr) * 0.5, (ai + ami) * 0.5
    wr, wi = (jnp.asarray(a, ar.dtype) for a in tw.rfft_split_twiddle_np(n))
    our = tr * wr + ti * wi
    oui = ti * wr - tr * wi
    cr, ci = cifft(er - oui, ei + our)
    return jnp.stack([cr, ci], axis=-1).reshape(re.shape[:-1] + (n,))


def rfft_via(pencil_fn):
    """Generic ``real_fn`` for the method registry: wrap a registered
    complex pencil (``(re, im, *, inverse, compute_dtype) -> (re, im)``)
    with the pack/combine halving trick. Forward maps a real array to
    the planar half spectrum; inverse maps it back."""
    def real_fn(x, im=None, *, inverse=False, compute_dtype=None):
        if inverse:
            return irfft_pencil(
                x, im, cifft=lambda r, i: pencil_fn(
                    r, i, inverse=True, compute_dtype=compute_dtype))
        return rfft_pencil(
            x, cfft=lambda r, i: pencil_fn(
                r, i, inverse=False, compute_dtype=compute_dtype))
    return real_fn


# ---------------------------------------------------------------------------
# Direct DFT (oracle-grade for tiny sizes, also used for non-pow2 factors)
# ---------------------------------------------------------------------------

def dft_direct(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False) -> Planar:
    n = re.shape[-1]
    fr, fi = (jnp.asarray(a, dtype=re.dtype) for a in tw.dft_matrix_np(n, inverse=inverse))
    yr = jnp.einsum('jk,...k->...j', fr, re) - jnp.einsum('jk,...k->...j', fi, im)
    yi = jnp.einsum('jk,...k->...j', fr, im) + jnp.einsum('jk,...k->...j', fi, re)
    if inverse:
        yr, yi = yr / n, yi / n
    return yr, yi


# ---------------------------------------------------------------------------
# Dispatch — deprecated shim over the single registry (repro.fft.methods)
# ---------------------------------------------------------------------------

def fft1d(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
          method: str = 'auto', compute_dtype=None) -> Planar:
    """DEPRECATED: delegate to :func:`repro.fft.methods.apply`, the one
    method registry. ``auto`` resolution (MXU four-step for n >= 64,
    Stockham below, direct for non-pow2) lives there."""
    from repro.core._deprecated import warn_once
    warn_once('repro.core.fft1d.fft1d', 'repro.fft.methods.apply')
    from repro.fft import methods
    return methods.apply(re, im, inverse=inverse, method=method,
                         compute_dtype=compute_dtype)


def __getattr__(name):
    # METHODS is derived from the registry so there is exactly one list
    # of method names in the codebase (lazy to avoid an import cycle:
    # repro.fft.methods imports this module's implementations).
    if name == 'METHODS':
        from repro.fft import methods
        return methods.names() + ('auto',)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
