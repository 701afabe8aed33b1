"""The names the FFT program gives its work, on the device and the host.

Device scopes (``jax.named_scope``) land in each XLA op's metadata
(``op_name``), which the profiler's device trace carries as the op's
``tf_op``. Every superstep of a plan runs under one ``fft.step<i>``
scope, ``i`` counting the supersteps of the direction being run (see
:func:`repro.fft.pencil.superstep_ids`), and the work inside it under
one leaf:

* ``fft.pass``: the axis transform and its twiddles;
* ``fft.relayout``: layout changes that materialise as copies (the
  kernel wrappers' reshapes, moveaxis and transposed emits, chunk
  splits and joins);
* ``fft.swap``: the ownership exchange, its packing and its wire cast;
* ``fft.combine``: the Hermitian combines and mirrors of real pencils.

``fft.boundary`` holds what the plan facade does around the supersteps:
the complex split and combine, the real plans' pad, slice and reshape.

Host spans (``jax.profiler.TraceAnnotation``) share the profiler's clock
with the device planes: ``fft.apply.fwd``/``fft.apply.inv`` around each
plan call, ``fft.compile`` around the first call of an executable the
plan has just built.

Both are metadata: the compiled program is the same without them.
"""
from __future__ import annotations

import jax

BOUNDARY = 'fft.boundary'
PASS = 'fft.pass'
RELAYOUT = 'fft.relayout'
SWAP = 'fft.swap'
COMBINE = 'fft.combine'
STEP = 'fft.step'
COMPILE = 'fft.compile'
APPLY = 'fft.apply'


def step(i: int) -> str:
    """Scope name of superstep ``i``."""
    return f'{STEP}{i}'


def scope(name: str):
    """A device scope: every op traced inside carries ``name``."""
    return jax.named_scope(name)


def span(name: str):
    """A host span on the profiler's trace (a no-op while no profile is
    being taken)."""
    return jax.profiler.TraceAnnotation(name)
