"""The public plan/execute facade: ``repro.fft.plan(...)`` -> ``FFT``.

One signature covers every rank the machinery supports:

* rank 1 — the distributed four-step over the flattened mesh
  (length n factored n1*n2; the (n,) <-> (n1, n2) view and the
  natural-order output are handled here, so forward/inverse are a
  plain FFT/IFFT pair on 1-D arrays),
* rank 2 — rows sharded over the flattened mesh, one transpose,
* rank 3 — the paper's pencil decomposition on the 2-D mesh.

The returned :class:`FFT` is an FFTW-style plan object: build once,
execute many times. ``forward``/``inverse`` accept either a complex
array (``complex64``/``complex128``) or a planar ``(re, im)`` pair and
return the same form they were given; jitted executables are cached per
``(direction, batch_shape, dtype, form)`` so repeated calls never
re-trace.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import comm as commlib
from repro.core import twiddle as tw
from repro.core.plan import Layout, PencilPlan
from repro.fft import large1d, methods, pencil

Planar = Tuple[jnp.ndarray, jnp.ndarray]


def _default_axes(mesh: Mesh, batch_spec) -> Tuple[str, ...]:
    axes = tuple(a for a in mesh.axis_names if a != batch_spec)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no FFT axes left "
                         f"after reserving batch_spec={batch_spec!r}")
    return axes


def plan(shape: Sequence[int], mesh: Mesh, *, method: str = 'auto',
         compute_dtype=None, kernel: str = 'auto',
         use_kernel: bool = False,
         mesh_axes: Optional[Tuple[str, ...]] = None,
         layout: Optional[Layout] = None,
         comm: str = 'auto', overlap_chunks: Optional[int] = None,
         wire_dtype: str = 'native',
         restore_layout: bool = False,
         batch_spec: Optional[str] = None,
         real: bool = False, padded_spectrum: bool = False,
         donate: bool = True) -> 'FFT':
    """Plan a distributed FFT of a ``len(shape)``-dimensional array.

    Args:
      shape: global transform shape — rank 1, 2 or 3.
      mesh: the jax device mesh the data lives on. A
        ``jax.sharding.AbstractMesh`` also works for cost-only plans
        (``.cost_report()``) — execution then needs real devices.
      method: local pencil algorithm from the method registry
        ('auto' | 'stockham' | 'four_step' | 'block' | 'direct').
      compute_dtype: matmul operand dtype for the matmul-form pencils
        (e.g. ``jnp.bfloat16`` for the paper's half-precision study).
      kernel: local-compute tier ('auto' | 'pallas' | 'reference').
        ``'auto'`` resolves per backend — the hand-written Pallas
        kernels where they lower natively (TPU Mosaic, GPU Triton), the
        pure-jnp reference tier elsewhere (CPU interpret mode is a
        debugging aid, not a fast path). ``'pallas'`` forces the
        kernels everywhere (interpret mode where no native lowering
        exists); ``'reference'`` forces pure jnp. All tiers are
        bit-identical under jit on the same backend.
      use_kernel: DEPRECATED boolean alias for ``kernel='pallas'``
        (ignored unless ``kernel`` is left at 'auto'); warns once.
      mesh_axes: mesh axis names to transform over. Rank 3: the
        (row, col) pair; ranks 1/2: axes flattened into one group.
        Defaults to every mesh axis except ``batch_spec``.
      layout: explicit initial ownership per array axis (ranks 2/3
        only); overrides ``mesh_axes``.
      comm: redistribution strategy from the :mod:`repro.comm` registry
        ('auto' | 'all_to_all' | 'ppermute' | 'hierarchical' |
        ``'pod_tree:<spec>'``, e.g. ``'pod_tree:x.4*y.2*y.2'``).
        ``'auto'`` prices the whole schedule with the paper's cycle
        model (:mod:`repro.comm.cost`, under the plan's ``wire_dtype``)
        and picks the strategy — including any pod trees benchmarked on
        this mesh — the pipelining depth, and — when ``method`` is
        also 'auto' — the local pencil algorithm. All strategies are
        bit-exact equivalent; only the schedule on the wire changes.
      overlap_chunks: pipeline local compute with the transpose
        collectives (beyond-paper; rank 1 overlaps over a leading
        batch axis). Default: cost-model choice under ``comm='auto'``,
        else 1.
      wire_dtype: wire format of the swap collectives
        ('native' | 'fp16' | 'bf16'). Compact formats cast each planar
        component to 16 bits immediately before every redistribution
        and restore the request dtype right after — half the wire
        bytes; ALL compute (twiddles, pencil FFTs, Hermitian combines)
        stays in the request precision. ``'native'`` is bit-identical
        to not setting the knob. ``comm='auto'`` prices the schedule
        under the chosen wire format.
      restore_layout: make forward/inverse consume AND produce the input
        sharding instead of the rotated one (extra transposes).
      batch_spec: mesh axis name a single leading batch dimension is
        sharded over (each transform instance stays inside one slice of
        that axis). Replicated batch dims need no declaration — any
        leading dims on the operand are batched automatically.
      real: plan an rfft/irfft pair (``np.fft.rfftn`` semantics):
        ``forward`` consumes a REAL array of ``shape`` and returns the
        conjugate-symmetric half spectrum — last axis truncated to
        ``shape[-1]//2 + 1`` — and ``inverse`` round-trips it back to
        the real array. The first superstep transforms real pencils
        (one length-n/2 complex pencil + an O(n) Hermitian combine per
        pencil), so every later superstep and every transpose moves
        roughly HALF the bytes and flops of the matching complex plan;
        ``comm='auto'`` prices that halved schedule. See also
        :func:`rplan`.
      padded_spectrum: real ranks 2/3 only. The truncated half axis
        (odd extent n//2 + 1) cannot shard evenly, so the default
        ``np.fft.rfftn``-layout output gathers it into memory — one
        boundary collective the cost report prices as a 'gather' step.
        With ``padded_spectrum=True`` the plan instead exposes its
        NATIVE spectrum: last axis zero-padded to the even on-wire
        extent, fully distributed in the rotated layout, no boundary
        collective at all — the pure half-wire pipeline. Spectral
        elementwise updates work unchanged (pad bins are dropped by the
        inverse before the c2r step) — use this for in-situ
        forward/update/inverse loops and large meshes.
      donate: donate the input operand buffer to every cached
        executable (``jax.jit`` ``donate_argnums``) so XLA reuses it
        for the output — the input and output of a complex plan have
        identical byte layout per device even though the sharding
        rotates, so each in-flight transform holds ONE operand-sized
        buffer instead of two. The donated array is CONSUMED: touching
        it after ``forward``/``inverse`` raises; pass ``donate=False``
        (the escape hatch) to keep FFTW-style reusable input buffers.
        Real plans never donate — the r2c/c2r boundary changes the
        buffer size, so XLA could not alias the pair anyway.

    Returns an :class:`FFT` plan with ``forward``/``inverse``/
    ``in_sharding``/``out_sharding``/``cost_report``.
    """
    shape = tuple(int(s) for s in shape)
    rank = len(shape)
    if rank not in (1, 2, 3):
        raise ValueError(f"repro.fft.plan supports ranks 1-3, got shape {shape}")
    if real and shape[-1] % 2:
        raise ValueError(f"real plans need an even last axis, got {shape}")
    if padded_spectrum and (not real or rank == 1):
        raise ValueError("padded_spectrum applies to real plans of "
                         "rank 2/3 only")
    methods.validate(method)
    methods.validate_kernel(kernel)
    if use_kernel:
        from repro.core import _deprecated
        _deprecated.warn_once('repro.fft.plan(use_kernel=)',
                              "kernel='pallas'")
        kernel = methods._merge_kernel_arg(kernel, use_kernel)
    # canonical spelling: pod-tree specs normalize (sorted axes) so
    # equal trees share one plan-cache / measured-table key
    comm = commlib.validate(comm)
    commlib.strategies.validate_wire_dtype(wire_dtype)
    if batch_spec is not None and batch_spec not in mesh.axis_names:
        raise ValueError(f"batch_spec {batch_spec!r} not a mesh axis "
                         f"of {mesh.axis_names}")
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)

    if rank == 1:
        if layout is not None:
            raise ValueError("layout applies to ranks 2/3 only; rank-1 "
                             "plans take mesh_axes")
        axes = mesh_axes if mesh_axes is not None else _default_axes(mesh, batch_spec)
        n = shape[0]
        n1, n2 = tw.four_step_factors(n)
        psize = 1
        for a in axes:
            psize *= mesh.shape[a]
        if n1 % psize or n2 % psize:
            raise ValueError(
                f"rank-1 FFT of n={n} factors as {n1}x{n2}; the {psize} "
                f"devices of mesh axes {axes} must divide both factors")
        strategy, oc, meth = _resolve_comm_1d(
            (n1, n2), axes, dict(mesh.shape), comm, overlap_chunks, method,
            real, wire_dtype)
        return FFT(shape=shape, mesh=mesh, method=meth,
                   compute_dtype=compute_dtype, kernel=kernel,
                   comm=strategy, overlap_chunks=oc, wire_dtype=wire_dtype,
                   restore_layout=restore_layout, real=real,
                   batch_spec=batch_spec, donate=donate,
                   axes1d=axes, factors=(n1, n2))

    if layout is None:
        if rank == 2:
            axes = mesh_axes if mesh_axes is not None else _default_axes(mesh, batch_spec)
            layout = (tuple(axes) if len(axes) > 1 else axes[0], None)
        else:
            if mesh_axes is not None:
                if len(mesh_axes) != 2:
                    raise ValueError(
                        f"rank-3 mesh_axes must be a (row, col) pair of "
                        f"mesh axis names, got {mesh_axes!r}")
                row, col = mesh_axes
            else:
                cand = _default_axes(mesh, batch_spec)
                if 'x' in cand and 'y' in cand:
                    row, col = 'x', 'y'
                elif len(cand) >= 2:
                    row, col = cand[0], cand[1]
                else:
                    raise ValueError(
                        f"rank-3 FFT needs two mesh axes, mesh has {cand}")
            layout = (row, col, None)
    strategy, oc, meth = _resolve_comm(
        shape, layout, dict(mesh.shape), comm, overlap_chunks, method, real,
        wire_dtype)
    pplan = PencilPlan(shape=shape, mesh=mesh, layout=layout, method=meth,
                       kernel=kernel, compute_dtype=compute_dtype,
                       comm=strategy, real=real, wire_dtype=wire_dtype)
    pplan.validate()
    return FFT(shape=shape, mesh=mesh, method=meth,
               compute_dtype=compute_dtype, kernel=kernel,
               comm=strategy, overlap_chunks=oc, wire_dtype=wire_dtype,
               restore_layout=restore_layout, real=real,
               padded_spectrum=padded_spectrum,
               batch_spec=batch_spec, donate=donate, pplan=pplan)


def rplan(shape: Sequence[int], mesh: Mesh, **kw) -> 'FFT':
    """Sugar for :func:`plan` with ``real=True``: an rfft/irfft plan
    whose forward consumes a real array and produces the half spectrum
    (last axis ``n//2 + 1``), at ~half the wire bytes and pencil flops
    of the complex plan."""
    return plan(shape, mesh, real=True, **kw)


def spectral_mul(ar, ai, k):
    """The complex spectral product ``(ar + i*ai) * (kr + i*ki)`` with
    contraction-pinned arithmetic. XLA contracts ``a*b - c*d`` into an
    FMA — and WHICH product it fuses depends on the surrounding program
    (optimization barriers and bitcasts are stripped before fusion), so
    a fused operator plan and the unfused forward/pointwise/inverse
    composition would disagree by a few ulps on a raw multiply. Here
    each partial product is multiplied by a data-derived exact one
    (``(x - x) + 1``, which the compiler cannot constant-fold away):
    mul-mul pairs never contract, so the product must round to its
    storage dtype first, and any FMA the backend then forms multiplies
    by exactly 1 — every compilation context yields the same bits.
    Ops built from this helper (or from single multiplies, selects, and
    other one-rounding primitives) make fused == unfused BITWISE; a raw
    ``ar * kr - ai * ki`` saves three elementwise ops per bin but only
    agrees to float tolerance. Conjugation-equivariant (negation is
    exact), so it is safe for the rank-1 real half-plane form.
    Non-finite spectrum bins come out NaN (the pin is exact only for
    finite values). ``k`` is a planar ``(kr, ki)`` pair, as handed to
    operator-plan pointwise stages."""
    kr, ki = k
    one = (ar - ar) + jnp.asarray(1.0, dtype=jnp.result_type(ar))

    def pin(p):
        return p * one

    return (pin(ar * kr) - pin(ai * ki),
            pin(ar * ki) + pin(ai * kr))


def _resolve_comm(shape, layout, mesh_shape, comm, overlap_chunks, method,
                  real=False, wire_dtype='native'):
    """Cost-model resolution of (strategy, overlap_chunks, method) for
    the pencil ranks. Explicit user choices always win; the selector
    runs only under comm='auto' (an explicit strategy keeps the
    documented overlap_chunks default of 1). The selector prices the
    schedule under the plan's wire format and considers any pod trees
    the measured table has benchmarked on this mesh."""
    if comm != 'auto':
        return comm, 1 if overlap_chunks is None else overlap_chunks, method
    sel = commlib.cost.select(shape, layout, mesh_shape, method=method,
                              real=real, wire_dtype=wire_dtype)
    oc = overlap_chunks if overlap_chunks is not None else sel.overlap_chunks
    meth = sel.method if method == 'auto' else method
    return sel.strategy, oc, meth


def _resolve_comm_1d(factors, axes, mesh_shape, comm, overlap_chunks, method,
                     real=False, wire_dtype='native'):
    """Rank-1 resolution: strategy by the four-step schedule's cost;
    overlap stays 1 unless the caller asks (it needs a batch axis only
    present at execution time); method per the two factor lengths."""
    oc = 1 if overlap_chunks is None else overlap_chunks
    mesh_axes = tuple(axes) if len(axes) > 1 else axes[0]
    if comm == 'auto':
        n1, n2 = factors
        cand = commlib.names() + tuple(
            t for t in commlib.cost._tree_candidates(mesh_shape, 'auto', None)
            if t not in commlib.names())
        costs = {
            name: commlib.cost.large1d_plan_cost(
                n1, n2, mesh_axes, mesh_shape, method=method, strategy=name,
                real=real, wire_dtype=wire_dtype)
            for name in cand}
        comm = min(costs, key=lambda k: costs[k].cycles)
        if method == 'auto':
            lens = (max(factors[0] // 2, 1), factors[1]) if real else factors
            picks = {commlib.cost.select_method(n) for n in lens}
            method = picks.pop() if len(picks) == 1 else 'auto'
    return comm, oc, method


class FFT:
    """A planned distributed FFT: build once, execute many times.

    ``forward(x)`` / ``inverse(x)`` accept a complex array or a planar
    ``(re, im)`` pair — with any number of leading (replicated) batch
    dimensions, or exactly one when the plan has ``batch_spec`` — and
    return the same form. ``inverse(forward(x))`` is an exact round trip:
    the inverse consumes the forward's output sharding and restores the
    input sharding with no extra redistribution.

    Real (rfft) plans change the boundary types only: ``forward`` takes
    a REAL array of the planned shape and returns the complex half
    spectrum (:attr:`spectrum_shape` — last axis ``n//2 + 1``, exactly
    ``np.fft.rfftn``'s layout); ``inverse`` takes the half spectrum
    (complex or planar) and returns the real array.

    By default (``donate=True``) complex plans CONSUME their operand:
    the executable donates the input buffer to XLA, which reuses it for
    the output (:attr:`donates_input`). Reusing a jax array after
    passing it in raises; plan with ``donate=False`` for FFTW-style
    reusable buffers. numpy operands are unaffected (they are copied to
    device per call anyway).
    """

    def __init__(self, *, shape, mesh, method, compute_dtype,
                 kernel: str = 'auto',
                 comm, overlap_chunks, restore_layout, batch_spec,
                 real: bool = False, padded_spectrum: bool = False,
                 donate: bool = True, wire_dtype: str = 'native',
                 pplan: Optional[PencilPlan] = None,
                 axes1d: Optional[Tuple[str, ...]] = None,
                 factors: Optional[Tuple[int, int]] = None):
        self.shape = shape
        self.rank = len(shape)
        self.mesh = mesh
        self.method = method
        self.compute_dtype = compute_dtype
        self.kernel = kernel
        self.comm = comm
        self.overlap_chunks = overlap_chunks
        self.wire_dtype = wire_dtype
        self.restore_layout = restore_layout
        self.batch_spec = batch_spec
        self.real = real
        self.padded_spectrum = padded_spectrum
        self.donate = donate
        self._pplan = pplan
        self._axes1d = axes1d
        self._factors = factors
        self._raw_cache = {}    # (direction, batched) -> planar global fn
        self._exec_cache = {}   # (direction, batch_shape, dtype, form) -> jitted

    @property
    def resolved_kernel(self) -> str:
        """The kernel tier this plan's supersteps run on the CURRENT
        backend ('pallas' | 'reference') — the 'auto' option resolved
        at query time against :data:`methods.PALLAS_LOWERING` and the
        method's per-backend kernel table. Each pencil length resolves
        on its own, as :func:`methods.apply` does per axis; the plan is
        'pallas' only when every one of them is."""
        lengths = set(self._factors if self.rank == 1 else self.shape)
        tiers = {methods.resolve_kernel(self.kernel,
                                        methods.resolve(self.method, n), n=n)
                 for n in lengths}
        return 'pallas' if tiers == {'pallas'} else 'reference'

    @property
    def donates_input(self) -> bool:
        """True when this plan's executables consume their input buffer
        (``donate`` requested AND the aliasing is structurally possible
        — complex plans only; the r2c/c2r boundary of a real plan
        changes the buffer size, so donation would be a silent no-op)."""
        return self.donate and not self.real

    def _options(self) -> dict:
        """Every resolved option a re-plan needs to reproduce this plan.
        Subclasses (operator plans) EXTEND this dict with their own
        options, so :meth:`with_options` round-trips new plan kinds the
        same way it round-trips wire/comm/kernel — no option silently
        resets on re-plan."""
        kw = dict(method=self.method, compute_dtype=self.compute_dtype,
                  kernel=self.kernel, comm=self.comm,
                  overlap_chunks=self.overlap_chunks,
                  wire_dtype=self.wire_dtype,
                  restore_layout=self.restore_layout,
                  batch_spec=self.batch_spec, real=self.real,
                  padded_spectrum=self.padded_spectrum, donate=self.donate)
        if self.rank == 1:
            kw['mesh_axes'] = self._axes1d
        else:
            kw['layout'] = self._pplan.layout
        return kw

    def _replan(self, kw: dict) -> 'FFT':
        """Build the re-planned object from a full option dict;
        subclasses route to their own planner."""
        if not kw['real']:
            # padded_spectrum is a real-plan-only knob; a real -> complex
            # re-plan must not carry it into plan() validation
            kw['padded_spectrum'] = False
        return plan(self.shape, self.mesh, **kw)

    def with_options(self, **overrides) -> 'FFT':
        """Re-plan this FFT with some options changed (e.g.
        ``overlap_chunks``, ``donate``, ``comm``) — everything not
        overridden carries over already *resolved*, so no 'auto' choice
        is re-made. The new plan has its own executable caches.
        Operator plans (:func:`plan_op`) round-trip their op/pointwise
        options the same way."""
        kw = self._options()
        kw.update(overrides)
        return self._replan(kw)

    @property
    def _real_pad(self) -> int:
        """On-wire (padded) extent of the truncated half axis."""
        return pencil.real_padded_extent(
            self.shape, self._pplan.layout, dict(self.mesh.shape),
            restore_layout=self.restore_layout)

    @property
    def spectrum_shape(self) -> Tuple[int, ...]:
        """Global shape of the forward output: ``shape`` for complex
        plans; for real plans the half spectrum — last axis n//2 + 1
        (``np.fft.rfftn``'s layout), or its padded on-wire extent under
        ``padded_spectrum``."""
        if not self.real:
            return self.shape
        if self.padded_spectrum:
            return self.shape[:-1] + (self._real_pad,)
        return self.shape[:-1] + (self.shape[-1] // 2 + 1,)

    # -- layouts / shardings ------------------------------------------------

    @property
    def in_layout(self) -> Layout:
        if self.rank == 1:
            return (self._axes1d if len(self._axes1d) > 1 else self._axes1d[0],)
        return self._pplan.layout

    @property
    def out_layout(self) -> Layout:
        if self.real and not self.padded_spectrum:
            # np.rfftn layout: the odd-extent half axis cannot shard
            # evenly, so it is gathered into memory at the boundary
            if self.rank == 1:
                return (None,)
            lay = (self.in_layout if self.restore_layout else
                   pencil.forward_schedule(self._pplan.layout,
                                           self._pplan.real_axis)[1])
            return lay[:-1] + (None,)
        if self.rank == 1 or self.restore_layout:
            return self.in_layout
        return pencil.forward_schedule(self._pplan.layout,
                                       self._pplan.real_axis)[1]

    def _sharding(self, layout: Layout) -> NamedSharding:
        lead = (self.batch_spec,) if self.batch_spec is not None else ()
        return NamedSharding(self.mesh, P(*(lead + tuple(layout))))

    @property
    def in_sharding(self) -> NamedSharding:
        """Sharding forward() consumes (and inverse() produces) for an
        operand of exactly the planned shape — plus the one leading
        batch dim when ``batch_spec`` is set. Replicated leading batch
        dims are not covered: a NamedSharding binds its spec to the
        leading axes, so ``device_put`` a batched operand with
        ``P(*([None] * nbatch), *spec)`` instead."""
        return self._sharding(self.in_layout)

    @property
    def out_sharding(self) -> NamedSharding:
        """Sharding forward() produces (and inverse() consumes); same
        operand-shape caveat as :attr:`in_sharding`."""
        return self._sharding(self.out_layout)

    # -- execution ----------------------------------------------------------

    def forward(self, x):
        """FFT of ``x`` (complex array or planar (re, im) pair; a REAL
        array for real plans, which return the half spectrum)."""
        return self._apply('fwd', x)

    def inverse(self, x):
        """IFFT of ``x``; exact round trip with :meth:`forward`. Real
        plans take the half spectrum and return the real array."""
        return self._apply('inv', x)

    def _apply(self, direction, x):
        planar = isinstance(x, (tuple, list))
        if planar and self.real and direction == 'fwd':
            raise ValueError(
                "real plan forward takes ONE real array, not a planar pair")
        if planar:
            # always coerce: operands may arrive as numpy arrays OR plain
            # (nested) Python lists — `.shape` exists on neither
            re, im = x
            re, im = jnp.asarray(re), jnp.asarray(im)
            if im.shape != re.shape or im.dtype != re.dtype:
                raise ValueError(
                    f"planar operand mismatch: re is {re.dtype}{re.shape}, "
                    f"im is {im.dtype}{im.shape}")
            shape, dtype = re.shape, re.dtype
        else:
            x = jnp.asarray(x)
            shape, dtype = x.shape, x.dtype
        core = (self.spectrum_shape if self.real and direction == 'inv'
                else self.shape)
        if (len(shape) < self.rank
                or tuple(shape[len(shape) - self.rank:]) != core):
            raise ValueError(
                f"operand shape {tuple(shape)} does not end with the "
                f"planned transform shape {core}")
        if (self.real and direction == 'fwd'
                and jnp.issubdtype(dtype, jnp.complexfloating)):
            raise ValueError(
                f"real plan forward takes a REAL array, got {dtype}")
        batch_shape = tuple(shape[:len(shape) - self.rank])
        if self.batch_spec is not None and len(batch_shape) != 1:
            raise ValueError(
                f"plan with batch_spec={self.batch_spec!r} takes exactly one "
                f"leading batch dim, got batch shape {batch_shape}")
        key = (direction, batch_shape, jnp.dtype(dtype).name, planar)
        fn = self._exec_cache.get(key)
        if fn is None:
            fn = self._build(direction, batch_shape, planar)
            self._exec_cache[key] = fn
        return fn(re, im) if planar else fn(x)

    def _raw(self, direction, batched):
        key = (direction, batched)
        fn = self._raw_cache.get(key)
        if fn is not None:
            return fn
        inverse = direction == 'inv'
        batch = batched and self.batch_spec is None
        if self.rank == 1:
            n1, n2 = self._factors
            if self.real:
                # the real four-step mirrors itself on the same (n1, n2)
                # view — no factor flip, the facade owns the ordering
                fn = large1d.make_rfft1d_large(
                    n1, n2, self.mesh, self._axes1d, inverse=inverse,
                    method=self.method, kernel=self.kernel,
                    compute_dtype=self.compute_dtype, batch=batch,
                    batch_spec=self.batch_spec, comm=self.comm,
                    overlap_chunks=self.overlap_chunks,
                    wire_dtype=self.wire_dtype)
                self._raw_cache[key] = fn
                return fn
            f1, f2 = ((n2, n1) if inverse else (n1, n2))
            fn = large1d.make_fft1d_large(
                f1, f2, self.mesh, self._axes1d, inverse=inverse,
                natural_order=True, method=self.method,
                kernel=self.kernel, compute_dtype=self.compute_dtype,
                batch=batch, batch_spec=self.batch_spec, comm=self.comm,
                overlap_chunks=self.overlap_chunks,
                wire_dtype=self.wire_dtype)
        else:
            fn, _, _ = pencil.make_fft(
                self._pplan, inverse=inverse,
                restore_layout=self.restore_layout, batch=batch,
                batch_spec=self.batch_spec,
                overlap_chunks=self.overlap_chunks)
        self._raw_cache[key] = fn
        return fn

    def _build(self, direction, batch_shape, planar):
        raw = self._raw(direction, batched=len(batch_shape) > 0)
        nb = len(batch_shape)
        flatb = (int(np.prod(batch_shape)),) if nb else ()
        if self.real:
            return self._build_real(direction, raw, batch_shape, flatb,
                                    planar)
        if self.rank == 1:
            n1, n2 = self._factors
            # the four-step works on the (n1, n2) row-major view; its
            # natural-order output is the (n2, n1) view of y (and the
            # inverse consumes exactly that form)
            in_core = (n2, n1) if direction == 'inv' else (n1, n2)
        else:
            in_core = self.shape
        out_shape = batch_shape + self.shape
        collapse = nb > 1 or self.rank == 1

        def run_planar(re, im):
            if collapse:
                re = re.reshape(flatb + in_core)
                im = im.reshape(flatb + in_core)
            yr, yi = raw(re, im)
            if collapse:
                yr = yr.reshape(out_shape)
                yi = yi.reshape(out_shape)
            return yr, yi

        # donated inputs: same global shape/dtype in and out, so XLA
        # aliases the buffers even across the layout rotation — one
        # live operand per in-flight transform
        dn = self.donates_input
        if planar:
            return jax.jit(run_planar, donate_argnums=(0, 1) if dn else ())

        def run_complex(x):
            yr, yi = run_planar(x.real, x.imag)
            return jax.lax.complex(yr, yi)

        return jax.jit(run_complex, donate_argnums=(0,) if dn else ())

    def _build_real(self, direction, raw, batch_shape, flatb, planar):
        """Executable wrappers for real plans: the raw pipeline speaks
        the padded half spectrum; the boundary pad/slice lives here. The
        slice is alignment-preserving — the pad sits entirely in the
        trailing shards of the truncated axis — so it costs no
        redistribution."""
        nb = len(batch_shape)

        def shard(layout):
            # pin the jit output's (uneven) sharding: XLA's propagation
            # gives up across the non-divisible boundary slice and would
            # replicate — i.e. all-gather — the whole spectrum otherwise
            lead = ((self.batch_spec,) if self.batch_spec is not None
                    else (None,) * nb)
            return NamedSharding(self.mesh, P(*(lead + tuple(layout))))

        if self.rank == 1:
            return self._build_real_1d(direction, raw, batch_shape, flatb,
                                       planar, shard)
        collapse = nb > 1
        nh_pad = self._real_pad
        nh_out = self.spectrum_shape[-1]    # nh, or nh_pad when padded
        if direction == 'fwd':
            out_shape = batch_shape + self.spectrum_shape

            def run_fwd(x):
                if collapse:
                    x = x.reshape(flatb + self.shape)
                yr, yi = raw(x)
                if nh_out != nh_pad:
                    yr, yi = yr[..., :nh_out], yi[..., :nh_out]
                if collapse:
                    yr, yi = yr.reshape(out_shape), yi.reshape(out_shape)
                return jax.lax.complex(yr, yi)

            return jax.jit(run_fwd, out_shardings=shard(self.out_layout))

        out_shape = batch_shape + self.shape

        def run_inv_planar(re, im):
            if collapse:
                re = re.reshape(flatb + self.spectrum_shape)
                im = im.reshape(flatb + self.spectrum_shape)
            if nh_out != nh_pad:
                pw = [(0, 0)] * re.ndim
                pw[-1] = (0, nh_pad - nh_out)
                re, im = jnp.pad(re, pw), jnp.pad(im, pw)
            x = raw(re, im)
            return x.reshape(out_shape) if collapse else x

        out_sh = shard(self.in_layout)
        if planar:
            return jax.jit(run_inv_planar, out_shardings=out_sh)
        return jax.jit(lambda y: run_inv_planar(y.real, y.imag),
                       out_shardings=out_sh)

    def _build_real_1d(self, direction, raw, batch_shape, flatb, planar,
                       shard):
        """Rank-1 real wrappers: the raw half-plane four-step computes
        rows j1 <= n1//2 of D[j1, j2] (y[j1 + n1*j2]); this assembles
        ``np.fft.rfft`` order from it — n - k = (n1-j1) + n1*(n2-1-j2),
        so bins with j1 > n1//2 are the Hermitian mirror
        conj(D[n1-j1, n2-1-j2]) — and its exact transpose feeds the
        inverse."""
        n1, n2 = self._factors
        n = n1 * n2
        nh = n // 2 + 1
        nh1 = n1 // 2 + 1
        psize = 1
        for a in self._axes1d:
            psize *= self.mesh.shape[a]
        nh1p = -(-nh1 // psize) * psize

        if direction == 'fwd':
            out_shape = batch_shape + (nh,)

            def run_fwd(x):
                x = x.reshape(flatb + (n1, n2))
                dr, di = raw(x)
                dr, di = dr[..., :nh1, :], di[..., :nh1, :]
                # rows n1//2+1 .. n1-1 of the full plane, Hermitian-mirrored
                br = jnp.flip(jnp.flip(dr[..., 1:n1 // 2, :], -2), -1)
                bi = -jnp.flip(jnp.flip(di[..., 1:n1 // 2, :], -2), -1)
                fr = jnp.concatenate([dr, br], -2)
                fi = jnp.concatenate([di, bi], -2)
                yr = jnp.swapaxes(fr, -1, -2).reshape(flatb + (n,))[..., :nh]
                yi = jnp.swapaxes(fi, -1, -2).reshape(flatb + (n,))[..., :nh]
                return jax.lax.complex(yr.reshape(out_shape),
                                       yi.reshape(out_shape))

            return jax.jit(run_fwd, out_shardings=shard(self.out_layout))

        out_shape = batch_shape + (n,)

        def run_inv_planar(re, im):
            re = re.reshape(flatb + (nh,))
            im = im.reshape(flatb + (nh,))
            # Hermitian-extend to the full spectrum, view as D rows
            fr = jnp.concatenate([re, jnp.flip(re[..., 1:n // 2], -1)], -1)
            fi = jnp.concatenate([im, -jnp.flip(im[..., 1:n // 2], -1)], -1)
            dr = jnp.swapaxes(fr.reshape(flatb + (n2, n1)), -1, -2)
            di = jnp.swapaxes(fi.reshape(flatb + (n2, n1)), -1, -2)
            dr, di = dr[..., :nh1, :], di[..., :nh1, :]
            pw = [(0, 0)] * dr.ndim
            pw[-2] = (0, nh1p - nh1)
            x = raw(jnp.pad(dr, pw), jnp.pad(di, pw))
            return x.reshape(out_shape)

        out_sh = shard(self.in_layout)
        if planar:
            return jax.jit(run_inv_planar, out_shardings=out_sh)
        return jax.jit(lambda y: run_inv_planar(y.real, y.imag),
                       out_shardings=out_sh)

    # -- cache sizing hooks (serve-engine plan cache accounting) ------------

    def operand_nbytes(self, dtype=None, *, spectrum: bool = False) -> int:
        """Global bytes of ONE operand of this plan: the planned array
        (real for rfft plans), or — with ``spectrum=True`` — the
        forward output (:attr:`spectrum_shape`, complex). The serve
        engine's byte-budgeted plan cache sizes each compiled group
        executable from these estimates (inputs + outputs dominate a
        jitted FFT's footprint; the twiddle constants are shared across
        widths)."""
        shape = self.spectrum_shape if spectrum else self.shape
        if dtype is None:
            dtype = (np.complex64 if spectrum or not self.real
                     else np.float32)
        return int(np.prod(shape)) * np.dtype(dtype).itemsize

    @property
    def cached_executables(self) -> int:
        """Number of jitted executables this plan currently holds, one
        per (direction, batch_shape, dtype, form) it has served."""
        return len(self._exec_cache)

    def clear_cache(self) -> None:
        """Drop every cached executable (and the underlying traced
        pipelines). The plan stays usable — the next call re-traces.
        The serve engine's LRU eviction hook calls this so an evicted
        plan releases its compiled state even while the plan object
        itself is still referenced elsewhere."""
        self._exec_cache.clear()
        self._raw_cache.clear()

    # -- cost model ---------------------------------------------------------

    def plan_cost(self, precision: str = 'fp32', *, measured='auto'):
        """The paper's cycle model (Eqs. 1-12, extended) applied to this
        plan's schedule under its resolved strategy/method/overlap:
        returns a :class:`repro.comm.cost.PlanCost`. ``measured=None``
        forces the pure analytic model (ignoring any measured swap-us
        table)."""
        mesh_shape = dict(self.mesh.shape)
        if self.rank == 1:
            n1, n2 = self._factors
            ax = self._axes1d
            return commlib.cost.large1d_plan_cost(
                n1, n2, tuple(ax) if len(ax) > 1 else ax[0], mesh_shape,
                precision=precision, method=self.method, strategy=self.comm,
                overlap_chunks=self.overlap_chunks, real=self.real,
                measured=measured, wire_dtype=self.wire_dtype,
                kernel=self.resolved_kernel)
        return commlib.cost.pencil_plan_cost(
            self.shape, self._pplan.layout, mesh_shape, precision=precision,
            method=self.method, strategy=self.comm,
            overlap_chunks=self.overlap_chunks, real=self.real,
            padded_spectrum=self.padded_spectrum or not self.real,
            measured=measured, wire_dtype=self.wire_dtype,
            kernel=self.resolved_kernel)

    def cost_report(self, precision: str = 'fp32') -> str:
        """Predicted cycles per superstep/transpose, formatted next to
        the paper's Table-1 entries when the config matches a measured
        one (n^3 cube, m-pencil mesh). Works on AbstractMesh plans, so
        the paper's 512^3 / 512x512 config can be priced without
        devices."""
        return commlib.cost.format_report(self.plan_cost(precision),
                                          self.shape, dict(self.mesh.shape))

    def __repr__(self):
        return (f"FFT(shape={self.shape}, rank={self.rank}, "
                f"real={self.real}, "
                f"method={self.method!r}, comm={self.comm!r}, "
                f"kernel={self.kernel!r}, "
                f"wire_dtype={self.wire_dtype!r}, "
                f"mesh={dict(self.mesh.shape)}, "
                f"batch_spec={self.batch_spec!r})")


def plan_op(shape: Sequence[int], mesh: Mesh, *, op,
            op_name: Optional[str] = None, real: bool = True,
            n_spectra: int = 0, spectra=None,
            spectra_form: str = 'plan', **kw) -> 'SpectralOp':
    """Plan a fused spectral OPERATOR: rfft -> ``op`` -> irfft as ONE
    plan object whose interior spectrum stays in its native distributed
    layout — the truncated-axis boundary gather of a real plan (and its
    inverse scatter) is elided entirely, so a convolution costs one
    dispatch and roughly half the wire bytes of two back-to-back plans.

    Args:
      shape, mesh: as :func:`plan`. All of :func:`plan`'s options
        (``method``/``kernel``/``comm``/``wire_dtype``/
        ``overlap_chunks``/``compute_dtype``/``donate``/``mesh_axes``/
        ``layout``) pass through ``**kw``; ``batch_spec`` and
        ``restore_layout`` do not apply to operator plans.
      op: the pointwise spectral stage, ``op(re, im, *spectra) ->
        (re, im)``: called with LOCAL shards of the planar spectrum
        plus one planar ``(re, im)`` pair per extra spectrum (runtime
        operands first, then baked ``spectra`` in order). It MUST be
        elementwise in the spectrum bins — it runs under whatever
        sharding the schedule produced, never on the gathered array —
        and, for real plans, conjugation-equivariant (true of any
        multiplicative factor: convolution, correlation with a
        conjugated factor, a solver's Green's function). Leading batch
        dims broadcast numpy-style across operands, e.g. a ``(B, d,
        n)`` signal against a ``(d, n)`` kernel.
      op_name: tag for serving-schedule rows and reports (defaults to
        ``op.__name__``).
      real: plan the real (rfft/irfft) chain — the input and output of
        ``apply`` are REAL arrays of ``shape``. ``False`` fuses a
        complex fft -> op -> ifft.
      n_spectra: number of extra RUNTIME operands ``apply`` takes after
        the main one; each is forward-transformed inside the same fused
        executable (still one dispatch) — the training-time path where
        the factor changes every step.
      spectra: static spectra baked into the plan as constants —
        transformed ONCE at first use (:attr:`SpectralOp.bake_count`),
        stored as distributed device arrays in the native spectrum
        layout, and handed to ``op`` after the runtime operands. The
        inference path: the conv kernel's FFT is never recomputed.
      spectra_form: how to read ``spectra``: ``'plan'`` — operand-space
        arrays (real arrays for real plans) transformed by this plan's
        own forward; ``'spectrum'`` — already-transformed spectral
        arrays in ``np.fft.rfftn`` order (complex plans: ``np.fft.fftn``
        order), e.g. an analytically known Green's function.

    Returns a :class:`SpectralOp` — an :class:`FFT` subclass whose
    :meth:`SpectralOp.apply` runs the whole fused chain; ``forward``/
    ``inverse`` still run the plain transforms (they are what bakes
    ``spectra``).
    """
    if not callable(op):
        raise ValueError(f"op must be callable, got {type(op).__name__}")
    if spectra_form not in ('plan', 'spectrum'):
        raise ValueError(f"spectra_form must be 'plan' or 'spectrum', "
                         f"got {spectra_form!r}")
    n_spectra = int(n_spectra)
    if n_spectra < 0:
        raise ValueError(f"n_spectra must be >= 0, got {n_spectra}")
    if kw.pop('restore_layout', False):
        raise ValueError("operator plans fuse forward and inverse back to "
                         "the input layout; restore_layout does not apply")
    if kw.pop('batch_spec', None) is not None:
        raise ValueError("operator plans batch over replicated leading "
                         "dims; batch_spec is not supported")
    kw.pop('padded_spectrum', None)   # derived: the fused interior is
    # ALWAYS the native padded spectrum — that is the whole point
    base = plan(shape, mesh, real=real,
                padded_spectrum=real and len(tuple(shape)) > 1, **kw)
    return SpectralOp(shape=base.shape, mesh=mesh, method=base.method,
                      compute_dtype=base.compute_dtype, kernel=base.kernel,
                      comm=base.comm, overlap_chunks=base.overlap_chunks,
                      wire_dtype=base.wire_dtype, restore_layout=False,
                      batch_spec=None, real=real,
                      padded_spectrum=base.padded_spectrum,
                      donate=base.donate, pplan=base._pplan,
                      axes1d=base._axes1d, factors=base._factors,
                      op=op, op_name=op_name, n_spectra=n_spectra,
                      spectra=spectra, spectra_form=spectra_form)


class SpectralOp(FFT):
    """A fused spectral-operator plan (see :func:`plan_op`).

    :meth:`apply` executes rfft -> op -> irfft as one cached jitted
    executable per operand signature; the interior spectrum never hits
    a boundary gather. Inherited ``forward``/``inverse`` still run the
    plain transforms of the underlying plan (used to bake static
    spectra, and handy for debugging the unfused composition).
    Unlike real transform plans, a real OPERATOR plan donates its main
    operand when ``donate`` is set: the fused chain returns to the
    input's exact shape, dtype and layout, so XLA can alias the pair.
    """

    def __init__(self, *, op, op_name=None, n_spectra=0, spectra=None,
                 spectra_form='plan', **kw):
        super().__init__(**kw)
        self.op = op
        self.op_name = op_name or getattr(op, '__name__', 'op') or 'op'
        self.n_spectra = n_spectra
        self.spectra_form = spectra_form
        self._spectra_raw = (None if spectra is None
                             else tuple(spectra))
        self._baked = None        # flat (re, im, re, im, ...) device arrays
        self._baked_bnd = ()      # leading batch rank per baked spectrum
        #: how many times the static spectra were transformed — the
        #: once-per-plan contract the fftconv regression test pins
        self.bake_count = 0

    @property
    def n_baked(self) -> int:
        return 0 if self._spectra_raw is None else len(self._spectra_raw)

    @property
    def donates_input(self) -> bool:
        """Operator plans can donate even when real: the fused chain's
        output has the input's exact global shape, dtype AND layout
        (r2c -> ... -> c2r round trip), so XLA aliases the pair."""
        return self.donate

    # -- with_options round-trip (the PR 7/8 resolved-options contract) -----

    def _options(self) -> dict:
        kw = super()._options()
        kw.update(op=self.op, op_name=self.op_name,
                  n_spectra=self.n_spectra, spectra=self._spectra_raw,
                  spectra_form=self.spectra_form)
        return kw

    def _replan(self, kw: dict) -> 'SpectralOp':
        kw.pop('padded_spectrum', None)   # plan_op derives it
        return plan_op(self.shape, self.mesh, **kw)

    # -- execution ----------------------------------------------------------

    def __call__(self, x, *extras):
        return self.apply(x, *extras)

    def apply(self, x, *extras):
        """Run the fused operator: ``apply(x, *runtime_spectra)`` ->
        the operated array, same shape/dtype/sharding as ``x``. Real
        plans take (and return) real arrays; complex plans accept a
        complex array or a planar ``(re, im)`` pair per operand and
        return the main operand's form. Any leading dims batch
        (replicated), broadcasting across operands inside ``op``."""
        if len(extras) != self.n_spectra:
            raise ValueError(
                f"operator plan takes {self.n_spectra} runtime spectra, "
                f"got {len(extras)}")
        baked = self._ensure_baked()
        ops, planars, batch_shapes, dtypes = [], [], [], []
        for a in (x,) + tuple(extras):
            planar = isinstance(a, (tuple, list))
            if self.real:
                if planar:
                    raise ValueError("real operator plan operands are "
                                     "single real arrays")
                a = jnp.asarray(a)
                if jnp.issubdtype(a.dtype, jnp.complexfloating):
                    raise ValueError(
                        f"real operator plan takes real arrays, got "
                        f"{a.dtype}")
                shape, dtype = a.shape, a.dtype
            elif planar:
                re, im = a
                re, im = jnp.asarray(re), jnp.asarray(im)
                if im.shape != re.shape or im.dtype != re.dtype:
                    raise ValueError(
                        f"planar operand mismatch: re is "
                        f"{re.dtype}{re.shape}, im is {im.dtype}{im.shape}")
                a, shape, dtype = (re, im), re.shape, re.dtype
            else:
                a = jnp.asarray(a)
                shape, dtype = a.shape, a.dtype
            if (len(shape) < self.rank
                    or tuple(shape[len(shape) - self.rank:]) != self.shape):
                raise ValueError(
                    f"operand shape {tuple(shape)} does not end with the "
                    f"planned transform shape {self.shape}")
            ops.append(a)
            planars.append(planar)
            batch_shapes.append(tuple(shape[:len(shape) - self.rank]))
            dtypes.append(jnp.dtype(dtype).name)
        key = ('op', tuple(batch_shapes), tuple(dtypes), tuple(planars))
        fn = self._exec_cache.get(key)
        if fn is None:
            fn = self._build_op(tuple(len(b) for b in batch_shapes),
                                tuple(planars))
            self._exec_cache[key] = fn
        flat = []
        for a, planar in zip(ops, planars):
            if self.real or planar:
                flat.extend(a if planar else (a,))
            else:
                flat.append(a)
        return fn(*flat, *baked)

    def _ensure_baked(self):
        if self._baked is None:
            self._bake()
        return self._baked

    def _bake(self):
        # the first apply() may run inside someone else's trace (e.g.
        # the serve engine's coalesced-group jit), but the baked
        # spectra are PLAN STATE and must come out as concrete device
        # arrays, not tracers of that enclosing trace. The inputs are
        # concrete, so run the transforms where no ambient trace
        # exists: trace state is thread-local in jax, and
        # ensure_compile_time_eval cannot be used here — its eval
        # trace unbinds the shard_map axis names the distributed
        # forward needs.
        if jax.core.trace_ctx.is_top_level():
            self._bake_now()
        else:
            box = []

            def run():
                try:
                    self._bake_now()
                except BaseException as e:   # noqa: BLE001 — reraised
                    box.append(e)
            t = threading.Thread(target=run, name='spectral-op-bake')
            t.start()
            t.join()
            if box:
                raise box[0]

    def _bake_now(self):
        flat, bnds = [], []
        for s in (self._spectra_raw or ()):
            re, im, nb = self._bake_one(s)
            flat += [re, im]
            bnds.append(nb)
        self._baked = tuple(flat)
        self._baked_bnd = tuple(bnds)
        self.bake_count += 1

    def _bake_one(self, s):
        """One static spectrum -> a planar pair of device arrays in the
        native distributed spectrum form (the padded rotated layout for
        ranks 2/3, the rank-1 half-plane / factor-transposed D-form)."""
        if self.spectra_form == 'plan':
            y = self.forward(jnp.asarray(s))
        else:
            y = jnp.asarray(s)
            want = self.shape[:-1] + (self.shape[-1] // 2 + 1,) \
                if self.real else self.shape
            if (y.ndim < self.rank
                    or tuple(y.shape[y.ndim - self.rank:]) != want):
                raise ValueError(
                    f"spectra_form='spectrum' arrays must end with the "
                    f"{'rfftn' if self.real else 'fftn'}-order spectrum "
                    f"shape {want}, got {tuple(y.shape)}")
        nb = y.ndim - self.rank
        if self.rank == 1:
            d = self._spectrum_to_native_1d(np.asarray(y))
            sh = NamedSharding(self.mesh, P(*(((None,) * nb)
                                              + self._spec1d)))
            return (jax.device_put(jnp.asarray(d.real), sh),
                    jax.device_put(jnp.asarray(d.imag), sh), nb)
        if self.real and self.spectra_form == 'spectrum':
            nh_pad = self._real_pad
            pw = [(0, 0)] * y.ndim
            pw[-1] = (0, nh_pad - y.shape[-1])
            y = jnp.pad(y, pw)
        sh = NamedSharding(self.mesh, P(*(((None,) * nb)
                                          + tuple(self._spec_layout))))
        return (jax.device_put(jnp.real(y), sh),
                jax.device_put(jnp.imag(y), sh), nb)

    @property
    def _spec_layout(self) -> Layout:
        """Layout of the native (padded) interior spectrum, ranks 2/3."""
        return pencil.forward_schedule(self._pplan.layout,
                                       self._pplan.real_axis)[1]

    @property
    def _spec1d(self):
        ax = self._axes1d
        return ((ax if len(ax) > 1 else ax[0]), None)

    def _spectrum_to_native_1d(self, y: np.ndarray) -> np.ndarray:
        """np.fft.rfft/fft-order bins -> the four-step's native
        distributed form: the rows-halved half plane (real) or the
        factor-transposed D matrix (complex), pad rows zeroed. The
        mapping is pure indexing + conjugation, so a spectrum baked
        from :meth:`forward` lands bitwise where the fused forward
        would have computed it."""
        n1, n2 = self._factors
        n = n1 * n2
        if not self.real:
            return np.swapaxes(y.reshape(y.shape[:-1] + (n2, n1)), -1, -2)
        nh1 = n1 // 2 + 1
        psize = 1
        for a in self._axes1d:
            psize *= self.mesh.shape[a]
        nh1p = -(-nh1 // psize) * psize
        full = np.concatenate(
            [y, np.conj(y[..., 1:n // 2][..., ::-1])], axis=-1)
        d = np.swapaxes(full.reshape(y.shape[:-1] + (n2, n1)), -1, -2)
        d = d[..., :nh1, :]
        pad = [(0, 0)] * d.ndim
        pad[-2] = (0, nh1p - nh1)
        return np.pad(d, pad)

    def _build_op(self, batch_ndims, planars):
        nb0 = batch_ndims[0]
        if self.rank == 1:
            n1, n2 = self._factors
            raw = large1d.make_fourstep_op(
                n1, n2, self.mesh, self._axes1d, self.op, real=self.real,
                batch_ndims=batch_ndims, baked_batch_ndims=self._baked_bnd,
                method=self.method, kernel=self.kernel,
                compute_dtype=self.compute_dtype, comm=self.comm,
                wire_dtype=self.wire_dtype)

            def view(a):
                return a.reshape(a.shape[:-1] + (n1, n2))
        else:
            raw, _, _ = pencil.make_fused_op(
                self._pplan, self.op, batch_ndims=batch_ndims,
                baked_batch_ndims=self._baked_bnd,
                overlap_chunks=self.overlap_chunks)

            def view(a):
                return a
        out_sh = NamedSharding(
            self.mesh, P(*(((None,) * nb0) + tuple(self.in_layout))))
        dn = self.donates_input

        if self.real:
            def run(*args):
                k = len(batch_ndims)
                mains = [view(a) for a in args[:k]]
                y = raw(*mains, *args[k:])
                return y.reshape(y.shape[:-2] + (n1 * n2,)) \
                    if self.rank == 1 else y

            return jax.jit(run, out_shardings=out_sh,
                           donate_argnums=(0,) if dn else ())

        # complex plans: per-operand complex-array or planar form; the
        # raw fn speaks flat planar pairs throughout
        def run_c(*args):
            flat, i = [], 0
            for planar in planars:
                if planar:
                    flat += [view(args[i]), view(args[i + 1])]
                    i += 2
                else:
                    flat += [view(args[i].real), view(args[i].imag)]
                    i += 1
            yr, yi = raw(*flat, *args[i:])
            if self.rank == 1:
                yr = yr.reshape(yr.shape[:-2] + (n1 * n2,))
                yi = yi.reshape(yi.shape[:-2] + (n1 * n2,))
            if planars[0]:
                return yr, yi
            return jax.lax.complex(yr, yi)

        donate = ((0, 1) if planars[0] else (0,)) if dn else ()
        if planars[0]:
            return jax.jit(run_c, out_shardings=(out_sh, out_sh),
                           donate_argnums=donate)
        return jax.jit(run_c, out_shardings=out_sh, donate_argnums=donate)

    # -- cost model ---------------------------------------------------------

    def plan_cost(self, precision: str = 'fp32', *, measured='auto'):
        """The fused chain priced per superstep — forward, one chain
        per runtime spectrum, the pointwise stage, the mirrored
        inverse — with the elided boundary gather shown as a
        zero-cycle 'elided' step (:func:`repro.comm.cost.
        spectral_op_cost`)."""
        mesh_shape = dict(self.mesh.shape)
        if self.rank == 1:
            ax = self._axes1d
            layout = tuple(ax) if len(ax) > 1 else ax[0]
            factors = self._factors
        else:
            layout, factors = self._pplan.layout, None
        return commlib.cost.spectral_op_cost(
            self.shape, layout, mesh_shape, factors=factors,
            precision=precision, method=self.method, strategy=self.comm,
            overlap_chunks=self.overlap_chunks, real=self.real,
            n_spectra=self.n_spectra, n_baked=self.n_baked,
            measured=measured, wire_dtype=self.wire_dtype,
            kernel=self.resolved_kernel)

    def __repr__(self):
        return (f"SpectralOp(op={self.op_name!r}, shape={self.shape}, "
                f"real={self.real}, n_spectra={self.n_spectra}, "
                f"n_baked={self.n_baked}, "
                f"method={self.method!r}, comm={self.comm!r}, "
                f"kernel={self.kernel!r}, "
                f"wire_dtype={self.wire_dtype!r}, "
                f"mesh={dict(self.mesh.shape)})")
