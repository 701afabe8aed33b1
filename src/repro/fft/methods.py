"""The single pencil-method registry of the FFT stack.

Every local (per-device) pencil transform in the codebase dispatches
through here: the facade (`repro.fft.plan`), the distributed pencil
machinery (`repro.fft.pencil`), the large-1D four-step
(`repro.fft.large1d`), and the legacy shims (`core.fft1d.fft1d`,
`kernels.ops.pencil_fft`). There is exactly one method->implementation
table and one ``'auto'`` resolution rule in the repo — this module.

A method owns up to four callables:

* ``pencil_fn``  — pure-jnp transform along the LAST axis
                   ``(re, im, *, inverse, compute_dtype) -> (re, im)``
* ``axis_fn``    — optional pure-jnp transform along an ARBITRARY axis
                   with no moveaxis HBM passes (the §Perf in-place axis
                   contraction); same signature plus ``axis``
* ``kernel_fn``  — optional Pallas kernel form along the last axis
                   ``(re, im, *, inverse, interpret) -> (re, im)``
* ``real_fn``    — real-input transform along the LAST axis:
                   ``real_fn(x, *, compute_dtype)`` maps a real array to
                   the planar half spectrum (n -> n//2 + 1 bins) and
                   ``real_fn(re, im, inverse=True, ...)`` back. Every
                   built-in gets one via the generic pack-two-reals
                   halving trick (:func:`repro.core.fft1d.rfft_via`),
                   so an rfft superstep costs one length-n/2 complex
                   pencil plus an O(n) combine.

``'block'`` (block-complex four-step: complex carried as a leading
size-2 axis, two real dots per pencil) is a first-class method here —
previously it was reachable only through ``make_fft``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fft1d as _f1
from repro.core import scopes
from repro.core import twiddle as tw

Planar = Tuple[jnp.ndarray, jnp.ndarray]

#: below this pencil length the matmul form cannot feed the MXU; the
#: ``'auto'`` rule falls back to Stockham butterflies (or the direct
#: O(n^2) DFT for non-power-of-two sizes).
AUTO_MATMUL_MIN = 64

#: valid ``kernel=`` plan-option values. 'auto' resolves per backend
#: (Pallas where it lowers natively, the pure-jnp reference elsewhere);
#: 'pallas' forces the hand-written kernel tier (interpret mode on
#: backends with no native lowering); 'reference' forces pure jnp.
KERNEL_TIERS: Tuple[str, ...] = ('auto', 'pallas', 'reference')

#: how ``pl.pallas_call`` lowers per jax backend: 'mosaic' (TPU) and
#: 'triton' (GPU) compile to real hardware kernels; 'interpret' means
#: the kernel only runs op-by-op under ``interpret=True`` (CPU).
PALLAS_LOWERING: Dict[str, str] = {
    'cpu': 'interpret',
    'gpu': 'triton',
    'cuda': 'triton',
    'rocm': 'triton',
    'tpu': 'mosaic',
}

def backend() -> str:
    """The active jax backend name ('cpu' | 'gpu' | 'tpu') — the key of
    every per-backend kernel/cost table (generalizes the old TPU-only
    ``on_tpu`` heuristic)."""
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == 'tpu'


def pallas_lowering(bk: Optional[str] = None) -> str:
    """'mosaic' | 'triton' | 'interpret' for backend ``bk`` (default:
    the active backend). Unknown backends are assumed interpret-only —
    the safe direction (correct everywhere, never silently slow on a
    backend we know lowers natively)."""
    return PALLAS_LOWERING.get(backend() if bk is None else bk, 'interpret')


def default_interpret(bk: Optional[str] = None) -> bool:
    """Interpret-mode default for Pallas calls: True exactly where the
    backend has no native Pallas lowering (the CPU). There is no
    override: on the TPU the kernels always compile with Mosaic."""
    return pallas_lowering(bk) == 'interpret'


def validate_kernel(kernel: str) -> str:
    """Check ``kernel`` is a known tier name; returns it."""
    if kernel not in KERNEL_TIERS:
        raise ValueError(
            f"unknown kernel tier {kernel!r}; known: {KERNEL_TIERS}")
    return kernel


#: longest pencil the Mosaic kernels hold in VMEM (AOT-compiled for
#: v5e); ``'auto'`` runs longer pencils on the reference tier
PALLAS_MAX_N = 1024


def resolve_kernel(kernel: str, method: Optional['Method'] = None,
                   bk: Optional[str] = None,
                   n: Optional[int] = None) -> str:
    """Resolve a kernel-tier option to the tier that will actually run:
    'pallas' or 'reference'.

    'auto' picks the Pallas tier only where the backend lowers it
    natively (the xformers dispatcher rule: hand kernels where they are
    hardware kernels, reference fallback elsewhere) — so CPU 'auto'
    plans are bit-identical to 'reference' plans by construction. An
    explicit 'pallas' runs everywhere (interpret mode where needed). A
    method with no kernel for this backend always falls back to
    'reference', matching the old ``use_kernel`` behavior, and so does
    'auto' for a pencil of length ``n`` > :data:`PALLAS_MAX_N`."""
    validate_kernel(kernel)
    if kernel == 'reference':
        return 'reference'
    bk = backend() if bk is None else bk
    if method is not None and method.kernel_for(bk) is None:
        return 'reference'
    if kernel == 'pallas':
        return 'pallas'
    if n is not None and n > PALLAS_MAX_N:
        return 'reference'
    return 'pallas' if pallas_lowering(bk) != 'interpret' else 'reference'


@dataclasses.dataclass(frozen=True)
class Method:
    """One registered local pencil algorithm.

    ``kernel_fns`` is the per-backend kernel table: backend name ->
    Pallas form (``None`` entries disable the kernel tier on that
    backend). Backends the table does not name fall back to the
    generic ``kernel_fn``. The built-ins register single-source Pallas
    kernels that lower per backend (cpu-interpret / gpu-triton /
    tpu-mosaic, see :data:`PALLAS_LOWERING`); the table is the
    extension point for backend-specialized variants."""
    name: str
    pencil_fn: Callable
    axis_fn: Optional[Callable] = None
    kernel_fn: Optional[Callable] = None
    kernel_fns: Optional[Mapping[str, Optional[Callable]]] = None
    real_fn: Optional[Callable] = None
    pow2_only: bool = True
    description: str = ''

    def kernel_for(self, bk: Optional[str] = None) -> Optional[Callable]:
        """The kernel serving backend ``bk`` (default: active backend),
        or None when this method has no kernel tier there."""
        bk = backend() if bk is None else bk
        if self.kernel_fns is not None and bk in self.kernel_fns:
            return self.kernel_fns[bk]
        return self.kernel_fn


_REGISTRY: Dict[str, Method] = {}


def register(method: Method) -> Method:
    if method.name in _REGISTRY:
        raise ValueError(f"method {method.name!r} already registered")
    _REGISTRY[method.name] = method
    return method


def names() -> Tuple[str, ...]:
    """Registered concrete method names (excludes the 'auto' alias)."""
    return tuple(_REGISTRY)


def get(name: str) -> Method:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown FFT method {name!r}; known: {names() + ('auto',)}"
        ) from None


def validate(name: str) -> str:
    """Check ``name`` is 'auto' or a registered method; returns it."""
    if name != 'auto':
        get(name)
    return name


def resolve(name: str, n: int) -> Method:
    """Resolve a method name (including 'auto') for pencil length n.

    The single 'auto' rule: MXU matmul four-step once the pencil is long
    enough to feed the systolic array, Stockham butterflies for smaller
    powers of two, dense DFT otherwise.
    """
    if name == 'auto':
        if n >= AUTO_MATMUL_MIN and tw.is_pow2(n):
            return _REGISTRY['four_step']
        return _REGISTRY['stockham' if tw.is_pow2(n) else 'direct']
    return get(name)


def _merge_kernel_arg(kernel: str, use_kernel: bool) -> str:
    """Fold the legacy ``use_kernel`` boolean into the kernel-tier
    option (True forces 'pallas' when ``kernel`` was left at 'auto').
    The one-time DeprecationWarning lives at the public plan surface
    (``fft.plan`` / ``FFT.with_options``), not in this hot path."""
    if use_kernel and kernel == 'auto':
        return 'pallas'
    return kernel


def apply(re: jnp.ndarray, im: jnp.ndarray, *, axis: int = -1,
          inverse: bool = False, method: str = 'auto',
          compute_dtype=None, kernel: str = 'auto',
          use_kernel: bool = False,
          interpret: Optional[bool] = None) -> Planar:
    """Run a registered pencil method along ``axis`` of planar (re, im).

    ``kernel`` picks the tier: 'pallas' routes to the method's
    per-backend Pallas kernel (interpret mode per
    :func:`default_interpret`), 'reference' the pure-jnp path, 'auto'
    resolves per backend (:func:`resolve_kernel`). The reference path
    prefers the axis-general form (no moveaxis) when the method
    provides one. ``use_kernel`` is the deprecated boolean alias.
    """
    axis = axis % re.ndim
    n = re.shape[axis]
    m = resolve(method, n)
    if m.pow2_only and not tw.is_pow2(n):
        raise ValueError(
            f"method {m.name!r} requires a power-of-two pencil length, "
            f"got {n} (use method='direct' or 'auto')")
    last = axis == re.ndim - 1
    with scopes.scope(scopes.PASS):
        if resolve_kernel(_merge_kernel_arg(kernel, use_kernel), m,
                          n=n) == 'pallas':
            kfn = m.kernel_for()
            if not last:
                re, im = _to_last(axis, re, im)
            yr, yi = kfn(re, im, inverse=inverse, interpret=interpret)
            if not last:
                yr, yi = _from_last(axis, yr, yi)
            return yr, yi
        if m.axis_fn is not None and not last:
            return m.axis_fn(re, im, axis, inverse=inverse,
                             compute_dtype=compute_dtype)
        if not last:
            re, im = _to_last(axis, re, im)
        yr, yi = m.pencil_fn(re, im, inverse=inverse,
                             compute_dtype=compute_dtype)
        if not last:
            yr, yi = _from_last(axis, yr, yi)
        return yr, yi


def _to_last(axis: int, *arrays):
    """``arrays`` with ``axis`` moved last, under the relayout scope."""
    with scopes.scope(scopes.RELAYOUT):
        return tuple(jnp.moveaxis(a, axis, -1) for a in arrays)


def _from_last(axis: int, *arrays):
    """Inverse of :func:`_to_last`."""
    with scopes.scope(scopes.RELAYOUT):
        return tuple(jnp.moveaxis(a, -1, axis) for a in arrays)


def apply_real(x: jnp.ndarray, im: Optional[jnp.ndarray] = None, *,
               axis: int = -1, inverse: bool = False, method: str = 'auto',
               compute_dtype=None, kernel: str = 'auto') -> object:
    """Run a method's real-input transform along ``axis``.

    Forward (``im is None``): real array -> planar half spectrum, the
    ``axis`` extent going n -> n//2 + 1 (``np.fft.rfft`` layout).
    Inverse: planar half spectrum ``(x, im)`` -> real array, n//2 + 1
    -> n. The ``'auto'`` method and kernel-tier rules resolve by the
    length of the underlying *complex* sub-pencil (n//2) — that is
    where the flops go. On the Pallas tier that sub-pencil runs on the
    method's kernel, inside the same Hermitian combines as the
    reference tier's ``real_fn``.
    """
    axis = axis % x.ndim
    if inverse:
        if im is None:
            raise ValueError("inverse real transform takes a planar "
                             "(re, im) half spectrum")
        n = 2 * (x.shape[axis] - 1)
    else:
        if im is not None:
            raise ValueError("forward real transform takes ONE real array")
        n = x.shape[axis]
    if n % 2:
        raise ValueError(f"real transforms need an even length, got {n}")
    h = max(n // 2, 1)
    m = resolve(method, h)
    if m.pow2_only and not tw.is_pow2(h):
        raise ValueError(
            f"method {m.name!r} requires a power-of-two half length, "
            f"got n={n} (use method='direct' or 'auto')")
    if m.real_fn is None:
        raise ValueError(f"method {m.name!r} has no real-input form")
    real_fn = m.real_fn
    if resolve_kernel(kernel, m, n=h) == 'pallas':
        real_fn = _kernel_real_fn(m.kernel_for())
    last = axis == x.ndim - 1
    with scopes.scope(scopes.PASS):
        if inverse:
            if not last:
                x, im = _to_last(axis, x, im)
            y = real_fn(x, im, inverse=True, compute_dtype=compute_dtype)
            return y if last else _from_last(axis, y)[0]
        if not last:
            (x,) = _to_last(axis, x)
        yr, yi = real_fn(x, compute_dtype=compute_dtype)
        if not last:
            yr, yi = _from_last(axis, yr, yi)
        return yr, yi


def _kernel_real_fn(kfn: Callable) -> Callable:
    """``real_fn`` of the Pallas tier: the pack-two-reals halving trick
    around kernel ``kfn`` as the half-length complex FFT (its inverse
    folds in the 1/(n/2) that :func:`repro.core.fft1d.irfft_pencil`
    expects; interpret mode per :func:`default_interpret`)."""
    def pencil(re, im, *, inverse, compute_dtype=None):
        return kfn(re, im, inverse=inverse, interpret=None)
    return _f1.rfft_via(pencil)


def apply_block(x: jnp.ndarray, *, axis: int, inverse: bool = False,
                compute_dtype=None, kernel: str = 'auto',
                use_kernel: bool = False,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Block-complex form of the 'block' method: ``x`` carries a leading
    size-2 complex axis (x[0]=re, x[1]=im) and is transformed along
    ``axis`` (counted over x's own dims). This is the representation the
    distributed block execution path threads through every superstep, so
    it dispatches here without unstacking."""
    axis = axis % x.ndim
    n = x.shape[axis]
    if not tw.is_pow2(n):
        raise ValueError(
            f"method 'block' requires a power-of-two pencil length, got {n}")
    tier = resolve_kernel(_merge_kernel_arg(kernel, use_kernel),
                          _REGISTRY.get('block'), n=n)
    with scopes.scope(scopes.PASS):
        if tier == 'pallas':
            from repro.kernels import fft_block as _kb
            last = axis == x.ndim - 1
            if not last:
                (x,) = _to_last(axis, x)
            y = _kb.fft_block(x, inverse=inverse, interpret=interpret)
            return y if last else _from_last(axis, y)[0]
        return _f1.fft_four_step_block(x, axis, inverse=inverse,
                                       compute_dtype=compute_dtype)


def apply_fused(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
                method: str = 'auto', compute_dtype=None,
                kernel: str = 'auto', use_kernel: bool = False,
                interpret: Optional[bool] = None,
                wr=None, wi=None) -> Planar:
    """One fused superstep: FFT along the LAST axis, an optional planar
    twiddle multiply (``wr``/``wi`` broadcastable to the FFT output),
    and an emit with the last two axes exchanged —
    ``out[..., k, j] = (W * FFT(x))[..., j, k]``.

    This is the op the distributed supersteps hand straight to the
    swap: the rotation and the transpose that XLA previously
    materialized as separate passes between ``apply`` and
    ``swap_axes_wire`` happen in the producer (in-kernel on the Pallas
    tier, one fused emit on the reference tier). Both tiers run the
    same float ops in the same order for the Stockham method, so plan
    outputs stay bit-identical across tiers.
    """
    if re.ndim < 2:
        raise ValueError("apply_fused needs a batch axis next to the "
                         f"pencil axis, got shape {re.shape}")
    n = re.shape[-1]
    m = resolve(method, n)
    if m.pow2_only and not tw.is_pow2(n):
        raise ValueError(
            f"method {m.name!r} requires a power-of-two pencil length, "
            f"got {n} (use method='direct' or 'auto')")
    tier = resolve_kernel(_merge_kernel_arg(kernel, use_kernel), m, n=n)
    with scopes.scope(scopes.PASS):
        if tier == 'pallas':
            if m.name == 'stockham':
                from repro.kernels import fft_fused as _kf
                return _kf.fft_twiddle_transpose(
                    re, im, wr, wi, inverse=inverse, interpret=interpret)
            yr, yi = m.kernel_for()(re, im, inverse=inverse,
                                    interpret=interpret)
            if wr is not None:
                yr, yi = yr * wr - yi * wi, yr * wi + yi * wr
            with scopes.scope(scopes.RELAYOUT):
                return jnp.swapaxes(yr, -1, -2), jnp.swapaxes(yi, -1, -2)
        return _f1.fft_twiddle_transpose(
            re, im, wr, wi, inverse=inverse, fft_fn=m.pencil_fn,
            compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# Built-in methods
# ---------------------------------------------------------------------------

def _stockham_kernel(re, im, *, inverse, interpret):
    from repro.kernels import fft_pencil as _kp
    return _kp.fft_pencil(re, im, inverse=inverse, interpret=interpret)


def _four_step_kernel(re, im, *, inverse, interpret):
    from repro.kernels import fft_matmul as _km
    return _km.fft_matmul(re, im, inverse=inverse, interpret=interpret)


def _direct(re, im, *, inverse=False, compute_dtype=None):
    return _f1.dft_direct(re, im, inverse=inverse)


def _block_pencil(re, im, *, inverse=False, compute_dtype=None):
    y = apply_block(jnp.stack([re, im]), axis=re.ndim, inverse=inverse,
                    compute_dtype=compute_dtype)
    return y[0], y[1]


def _block_axis(re, im, axis, *, inverse=False, compute_dtype=None):
    y = apply_block(jnp.stack([re, im]), axis=axis + 1, inverse=inverse,
                    compute_dtype=compute_dtype)
    return y[0], y[1]


def _block_kernel(re, im, *, inverse, interpret):
    y = apply_block(jnp.stack([re, im]), axis=re.ndim, inverse=inverse,
                    kernel='pallas', interpret=interpret)
    return y[0], y[1]


def _backed(kfn: Callable) -> Dict[str, Callable]:
    """Per-backend kernel table for a single-source Pallas kernel: the
    same callable lowers per backend (cpu-interpret / gpu-triton /
    tpu-mosaic, :data:`PALLAS_LOWERING` decides the mode). A
    backend-specialized variant replaces its entry here."""
    return {bk: kfn for bk in PALLAS_LOWERING}


register(Method(
    name='stockham',
    pencil_fn=_f1.fft_stockham,
    kernel_fn=_stockham_kernel,
    kernel_fns=_backed(_stockham_kernel),
    real_fn=_f1.rfft_via(_f1.fft_stockham),
    description='radix-2 Stockham autosort butterflies (paper-faithful)'))

register(Method(
    name='four_step',
    pencil_fn=_f1.fft_four_step,
    axis_fn=_f1.fft_four_step_axis,
    kernel_fn=_four_step_kernel,
    kernel_fns=_backed(_four_step_kernel),
    real_fn=_f1.rfft_via(_f1.fft_four_step),
    description='Bailey four-step as dense matmuls (MXU form)'))

register(Method(
    name='block',
    pencil_fn=_block_pencil,
    axis_fn=_block_axis,
    kernel_fn=_block_kernel,
    kernel_fns=_backed(_block_kernel),
    real_fn=_f1.rfft_via(_block_pencil),
    description='block-complex four-step: two real dots, fused twiddle'))

register(Method(
    name='direct',
    pencil_fn=_direct,
    real_fn=_f1.rfft_via(_direct),
    pow2_only=False,
    description='dense O(n^2) DFT matrix (oracle / non-pow2 sizes)'))
