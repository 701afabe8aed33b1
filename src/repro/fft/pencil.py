"""wsFFT pencil machinery: distributed multidimensional FFT over a mesh.

Faithful to the paper's schedule (§4.2/§4.3): for a 3-D transform the
input A[x, y, z] lives with (x, y) mapped to the two mesh axes and z in
memory; each superstep FFTs the in-memory axis (every device transforms
its m^2 local pencils), and between supersteps one all_to_all along one
mesh dimension exchanges the in-memory axis with a mesh-resident axis
(row transpose z<->x, then column transpose x<->y). The semantic (x,y,z)
axis order of the global array never changes — only the PartitionSpec
rotates: P('x','y',None) -> P('y',None,'x') after a forward 3-D FFT.

Beyond the paper: ``overlap_chunks`` splits the local pencil batch so
chunk i+1's compute can overlap chunk i's collective (XLA latency-hiding
scheduler materializes the overlap on TPU) — the chunking machinery
lives in :mod:`repro.comm.overlap` so it composes with any registered
redistribution strategy (``plan.comm``); the local pencil algorithm
comes from the single method registry (`repro.fft.methods`), including
the MXU matmul form and the block-complex state.

This module is internal to the ``repro.fft`` package — users should go
through ``repro.fft.plan``.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.comm import overlap as ov
from repro.core import plan as planlib
from repro.core.plan import Layout, PencilPlan
from repro.fft import methods

Planar = Tuple[jnp.ndarray, jnp.ndarray]

#: env toggle for the fused twiddle+transpose superstep ('1'/'0'). The
#: fused path runs the same float ops on the same values — only the op
#: order and the collective's axis positions change — so it is on by
#: default; the toggle exists for A/B benchmarking (bench_kernels.py)
#: and bisection.
FUSE_ENV = 'REPRO_FUSE_SUPERSTEP'


def default_fused() -> bool:
    env = os.environ.get(FUSE_ENV)
    if env not in (None, ''):
        return env.lower() not in ('0', 'false', 'no')
    return True


def pin_rounding(*arrays):
    """Force each array to round to its storage dtype at this point.

    ``jax.lax.optimization_barrier`` does NOT do this: the CPU backend
    strips barriers before fusion, so a trailing multiply feeding a
    consumer add across a program splice can still FMA-contract — and
    WHICH product contracts depends on the surrounding program, making
    a fused operator plan and the standalone plan differ by a few ulps.
    Multiplying by a data-derived exact one (``(a - a) + 1``, which the
    compiler cannot constant-fold) pins the rounding instead: mul-mul
    pairs never contract, so the producer must round first, and any FMA
    the backend then forms multiplies by exactly 1. Exact only for
    finite values (non-finite entries come out NaN), matching
    :func:`repro.fft.api.spectral_mul`.
    """
    out = []
    for a in arrays:
        one = (a - a) + jnp.asarray(1.0, dtype=a.dtype)
        out.append(a * one)
    return out[0] if len(out) == 1 else tuple(out)


# ---------------------------------------------------------------------------
# Schedule derivation (pure layout algebra — no data)
# ---------------------------------------------------------------------------

def forward_schedule(layout: Layout,
                     first_mem: Optional[int] = None) -> Tuple[Tuple, Layout]:
    """Returns (steps, final_layout). Each step is ('fft', mem_pos) or
    ('swap', mesh_axis, mem_pos). ``first_mem`` forces that memory axis
    into the first superstep — real plans need the r2c axis transformed
    before any exchange so everything on the wire is half-spectrum."""
    steps: List[Tuple] = []
    lay = layout
    transformed = set()
    ndim = len(layout)
    while len(transformed) < ndim:
        mems = [p for p in planlib.memory_axes(lay) if p not in transformed]
        if not mems:
            raise ValueError(f"no untransformed memory axis in {lay}")
        if first_mem is not None and first_mem not in transformed:
            if first_mem not in mems:
                raise ValueError(
                    f"axis {first_mem} must start in memory to be the "
                    f"first superstep of {layout}")
            mem = first_mem
        else:
            mem = mems[0]
        steps.append(('fft', mem))
        transformed.add(mem)
        # swap with the first untransformed mesh-owned axis, position order
        pend = [(p, o) for p, o in enumerate(lay) if o is not None and p not in transformed]
        if pend:
            _, owner = pend[0]
            steps.append(('swap', owner, mem))
            lay = planlib.swap(lay, owner, mem)
    return tuple(steps), lay


def inverse_schedule(layout: Layout,
                     first_mem: Optional[int] = None) -> Tuple[Tuple, Layout]:
    """Mirror of forward_schedule starting from the forward's *final*
    layout: reverses each swap (split/concat positions exchanged) and
    IFFTs in reverse superstep order, ending at the original layout."""
    fwd, final = forward_schedule(layout, first_mem)
    pre_layouts = []
    lay = layout
    for step in fwd:
        pre_layouts.append(lay)
        if step[0] == 'swap':
            lay = planlib.swap(lay, step[1], step[2])
    assert lay == final
    steps: List[Tuple] = []
    for step, pre in zip(reversed(fwd), reversed(pre_layouts)):
        if step[0] == 'fft':
            steps.append(step)
        else:
            _, mesh_axis, _ = step
            # the position that was sharded before the forward swap is the
            # memory position of the inverse swap
            steps.append(('swap', mesh_axis, planlib.owner_pos(pre, mesh_axis)))
    return tuple(steps), layout


# ---------------------------------------------------------------------------
# Half-spectrum extent bookkeeping (real plans)
# ---------------------------------------------------------------------------

def real_half_extent(n: int) -> int:
    """Logical half-spectrum length of a length-n real transform."""
    return n // 2 + 1


def real_padded_extent(shape, layout: Layout, mesh_shape, *,
                       restore_layout: bool = False) -> int:
    """On-wire extent of the truncated (half-spectrum) last axis.

    n//2 + 1 is odd, so it cannot shard evenly; the schedule therefore
    carries it zero-padded to the smallest multiple of every mesh-group
    size that ever owns it (walked off the actual swap sequence,
    including the restore_layout swaps). The pad rides every later
    superstep/swap and the facade slices it off — the slice is
    alignment-preserving because the pad lives entirely in the trailing
    shards. Works off a plain ``{axis: extent}`` mapping so cost-only
    (AbstractMesh) plans price the same extent the executor moves.
    """
    ra = len(shape) - 1
    nh = real_half_extent(shape[-1])
    steps, final = forward_schedule(tuple(layout), first_mem=ra)
    lay = tuple(layout)
    lcm = 1
    for step in steps:
        if step[0] == 'swap':
            lay = planlib.swap(lay, step[1], step[2])
            if lay[ra] is not None:
                lcm = math.lcm(lcm, comm.strategies.static_group_size(
                    lay[ra], mesh_shape))
    if restore_layout:
        for ax, mp in planlib.plan_swaps(final, tuple(layout)):
            lay = planlib.swap(lay, ax, mp)
            if lay[ra] is not None:
                lcm = math.lcm(lcm, comm.strategies.static_group_size(
                    lay[ra], mesh_shape))
    return -(-nh // lcm) * lcm


def packed_plan(plan: PencilPlan, nh_pad: int) -> PencilPlan:
    """The complex-plan view of a real plan's post-r2c supersteps: same
    mesh/layout/method, last axis at its padded half-spectrum extent."""
    return dataclasses.replace(plan, shape=plan.shape[:-1] + (nh_pad,),
                               real=False)


# ---------------------------------------------------------------------------
# Local execution of a schedule (inside shard_map)
# ---------------------------------------------------------------------------

def _fft_along(re, im, axis: int, *, inverse: bool, plan: PencilPlan) -> Planar:
    return methods.apply(re, im, axis=axis, inverse=inverse,
                         method=plan.method, compute_dtype=plan.compute_dtype,
                         kernel=plan.kernel_tier)


def _fused_pair(re, im, *, a: int, s: int, mesh_axis, inverse: bool,
                plan: PencilPlan, strategy, wire: str) -> Planar:
    """One fused superstep: FFT along local axis ``a`` and the swap that
    exchanges it with the mesh axis at local position ``s``, with the
    pre-collective transpose emitted BY the FFT (in-kernel on the Pallas
    tier, one fused emit on the reference tier) instead of XLA
    materializing it between ``apply`` and the collective.

    The fft axis is arranged last, the fused op emits the last two axes
    exchanged, the collective runs at the permuted positions, and the
    final transpose restores the original axis order — adjacent
    restore/arrange transposes of consecutive supersteps fold into one
    XLA op. Pure positional rearrangement around identical float ops, so
    outputs are bit-identical to the unfused path."""
    nd = re.ndim
    re1 = jnp.moveaxis(re, a, -1)
    im1 = jnp.moveaxis(im, a, -1)
    fr, fi = methods.apply_fused(re1, im1, inverse=inverse,
                                 method=plan.method,
                                 compute_dtype=plan.compute_dtype,
                                 kernel=plan.kernel_tier)
    # net arrange+emit permutation: order[i] = original axis at new pos i
    order = [p for p in range(nd) if p != a]
    order = order[:-1] + [a] + order[-1:]
    s_new = order.index(s)
    fr = comm.strategies.swap_axes_wire(
        strategy, fr, mesh_axis, shard_pos=s_new, mem_pos=nd - 2,
        wire_dtype=wire)
    fi = comm.strategies.swap_axes_wire(
        strategy, fi, mesh_axis, shard_pos=s_new, mem_pos=nd - 2,
        wire_dtype=wire)
    inv = [0] * nd
    for i2, p in enumerate(order):
        inv[p] = i2
    return jnp.transpose(fr, inv), jnp.transpose(fi, inv)


def _execute(re, im, layout: Layout, steps, *, inverse: bool, plan: PencilPlan,
             batch_ndim: int, overlap_chunks: int,
             fused: bool = True) -> Planar:
    """Run fft/swap steps, threading the layout. When overlap_chunks > 1
    each (fft, swap) pair is pipelined (via repro.comm.overlap) over
    chunks of a free local axis so compute of chunk i+1 overlaps the
    collective of chunk i (beyond-paper); serial (fft, swap) pairs run
    as one fused twiddle+transpose superstep when ``fused``; swaps
    dispatch through the plan's registered comm strategy."""
    off = batch_ndim
    lay = layout
    strategy = comm.resolve(plan.comm)
    wire = plan.wire_dtype
    i = 0
    while i < len(steps):
        step = steps[i]
        nxt = steps[i + 1] if i + 1 < len(steps) else None
        if (overlap_chunks > 1 and step[0] == 'fft' and nxt is not None
                and nxt[0] == 'swap'):
            mem = step[1]
            _, mesh_axis, mem_pos = nxt
            sp = planlib.owner_pos(lay, mesh_axis)
            # chunk axis: any local axis — leading batch axes included,
            # which is what pipelines a coalesced request batch — that
            # is neither the fft axis nor the swap axes; fall back to
            # no overlap if none exists.
            ck = ov.pick_chunk_axis(re.shape,
                                    (off + mem, off + mem_pos, off + sp),
                                    overlap_chunks)
            if ck is not None:
                re, im = ov.overlapped_fft_swap(
                    re, im,
                    fft_fn=lambda r, i_, m=mem: _fft_along(
                        r, i_, off + m, inverse=inverse, plan=plan),
                    swap_fn=lambda a, ma=mesh_axis, s=sp, mp=mem_pos:
                        strategy.swap_axes(a, ma, shard_pos=off + s,
                                           mem_pos=off + mp),
                    chunk_axis=ck, n_chunks=overlap_chunks,
                    wire_dtype=wire)
                lay = planlib.swap(lay, mesh_axis, mem_pos)
                i += 2
                continue
        if (fused and step[0] == 'fft' and nxt is not None
                and nxt[0] == 'swap' and nxt[2] == step[1]
                and re.ndim >= 2):
            # serial fused superstep: the swap reads the fft axis it is
            # about to split (mem_pos == the just-transformed axis — the
            # schedule invariant in both directions)
            _, mesh_axis, _ = nxt
            re, im = _fused_pair(
                re, im, a=off + step[1],
                s=off + planlib.owner_pos(lay, mesh_axis),
                mesh_axis=mesh_axis, inverse=inverse, plan=plan,
                strategy=strategy, wire=wire)
            lay = planlib.swap(lay, mesh_axis, nxt[2])
            i += 2
            continue
        if step[0] == 'fft':
            re, im = _fft_along(re, im, off + step[1], inverse=inverse, plan=plan)
        else:
            _, mesh_axis, mem_pos = step
            sp = planlib.owner_pos(lay, mesh_axis)
            re = comm.strategies.swap_axes_wire(
                strategy, re, mesh_axis, shard_pos=off + sp,
                mem_pos=off + mem_pos, wire_dtype=wire)
            im = comm.strategies.swap_axes_wire(
                strategy, im, mesh_axis, shard_pos=off + sp,
                mem_pos=off + mem_pos, wire_dtype=wire)
            lay = planlib.swap(lay, mesh_axis, mem_pos)
        i += 1
    return re, im


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

def make_fft(plan: PencilPlan, *, inverse: bool = False,
             restore_layout: bool = False, batch: bool = False,
             batch_spec=None, overlap_chunks: int = 1,
             fused: Optional[bool] = None) -> Tuple[Callable, Layout, Layout]:
    """Build a jit-able distributed FFT.

    Returns (fn, in_layout, out_layout); fn maps planar global arrays
    (re, im) -> (re, im). Real plans differ only at the r2c boundary:
    forward consumes ONE real array and returns the planar padded half
    spectrum (last axis ``real_padded_extent``); inverse consumes that
    and returns the real array. For ``inverse=True`` the function *consumes*
    the forward's output layout and returns the original input layout —
    ifft(fft(x)) is an exact round trip with no extra redistribution, the
    paper's forward+inverse loop (§5: "ran forward and inverse Fourier
    transforms consecutively"). With ``restore_layout`` both directions
    consume AND produce the plan's initial layout (extra swaps pay for
    the layout stability). ``fused`` controls the fused twiddle+
    transpose superstep (default: :func:`default_fused`, i.e. on unless
    ``REPRO_FUSE_SUPERSTEP=0``).
    """
    if fused is None:
        fused = default_fused()
    plan.validate()
    methods.validate(plan.method)
    comm.validate(plan.comm)
    first = plan.real_axis
    if inverse:
        steps, _ = inverse_schedule(plan.layout, first)
        in_layout, out_layout = (forward_schedule(plan.layout, first)[1],
                                 plan.layout)
        if restore_layout:
            # consume the plan layout: pre-rotate into the forward's final
            # layout, then run the mirrored schedule back
            steps = tuple(('swap', ax, mp) for ax, mp
                          in planlib.plan_swaps(plan.layout, in_layout)) + steps
            in_layout = plan.layout
    else:
        steps, out_layout = forward_schedule(plan.layout, first)
        in_layout = plan.layout
        if restore_layout:
            steps = steps + tuple(('swap', ax, mp) for ax, mp
                                  in planlib.plan_swaps(out_layout, plan.layout))
            out_layout = plan.layout

    batch_ndim = 1 if (batch or batch_spec is not None) else 0
    in_spec = P(*(((batch_spec,) if batch_ndim else ()) + tuple(in_layout)))
    out_spec = P(*(((batch_spec,) if batch_ndim else ()) + tuple(out_layout)))

    if plan.real:
        # the r2c superstep is first (forward) / last (inverse) by the
        # first_mem scheduling rule; everything between runs on the
        # padded half spectrum as an ordinary complex sub-plan
        ra = first
        nh = real_half_extent(plan.shape[-1])
        nh_pad = real_padded_extent(plan.shape, plan.layout,
                                    dict(plan.mesh.shape),
                                    restore_layout=restore_layout)
        packed = packed_plan(plan, nh_pad)
        off = batch_ndim
        strategy = comm.resolve(plan.comm)

        def r2c(x):
            re, im = methods.apply_real(x, axis=off + ra,
                                        method=plan.method,
                                        compute_dtype=plan.compute_dtype)
            if nh_pad != nh:
                pw = [(0, 0)] * re.ndim
                pw[off + ra] = (0, nh_pad - nh)
                re, im = jnp.pad(re, pw), jnp.pad(im, pw)
            # pin the fusion boundary between the Hermitian combine and
            # the following collective: without it XLA contracts the
            # combine's mul/add chains differently per batch shape, and
            # batched (serving) executions stop being bit-identical to
            # per-request ones (measured at 32^3; the complex pipeline
            # has no such epilogue and is stable without help)
            return comm.strategies.dbarrier((re, im))

        def c2r(re, im):
            re, im = comm.strategies.dbarrier((re, im))
            re = jax.lax.slice_in_dim(re, 0, nh, axis=off + ra)
            im = jax.lax.slice_in_dim(im, 0, nh, axis=off + ra)
            return methods.apply_real(re, im, axis=off + ra, inverse=True,
                                      method=plan.method,
                                      compute_dtype=plan.compute_dtype)

        def local_real_fwd(x):
            assert steps[0] == ('fft', ra), steps
            rest = steps[1:]
            # split-combine overlap of the r2c superstep: the extent
            # change (n -> nh_pad) happens per chunk of a free axis of
            # the REAL input, so r2c + pad + swap pipeline like any
            # other (fft, swap) pair; chunk i+1's half-spectrum build
            # overlaps chunk i's exchange. Fall back to the whole-array
            # path when no free axis divides.
            if overlap_chunks > 1 and rest and rest[0][0] == 'swap':
                _, mesh_axis, mem_pos = rest[0]
                sp = planlib.owner_pos(in_layout, mesh_axis)
                ck = ov.pick_chunk_axis(x.shape,
                                        (off + ra, off + mem_pos, off + sp),
                                        overlap_chunks)
                if ck is not None:
                    def stage(xc):
                        cr, ci = r2c(xc)
                        return (comm.strategies.swap_axes_wire(
                                    strategy, cr, mesh_axis,
                                    shard_pos=off + sp, mem_pos=off + mem_pos,
                                    wire_dtype=plan.wire_dtype),
                                comm.strategies.swap_axes_wire(
                                    strategy, ci, mesh_axis,
                                    shard_pos=off + sp, mem_pos=off + mem_pos,
                                    wire_dtype=plan.wire_dtype))
                    re, im = ov.pipelined(overlap_chunks, ck, stage, x)
                    lay = planlib.swap(in_layout, mesh_axis, mem_pos)
                    return _execute(re, im, lay, rest[1:], inverse=False,
                                    plan=packed, batch_ndim=batch_ndim,
                                    overlap_chunks=overlap_chunks,
                                    fused=fused)
            re, im = r2c(x)
            return _execute(re, im, in_layout, rest, inverse=False,
                            plan=packed, batch_ndim=batch_ndim,
                            overlap_chunks=overlap_chunks, fused=fused)

        def local_real_inv(re, im):
            assert steps[-1] == ('fft', ra), steps
            head, tail = steps[:-1], None
            # mirror split-combine: the final (swap, c2r) pair chunks a
            # free axis, so chunk i+1's exchange overlaps chunk i's c2r
            if (overlap_chunks > 1 and len(head) >= 1
                    and head[-1][0] == 'swap'):
                lay = in_layout
                for st in head[:-1]:
                    if st[0] == 'swap':
                        lay = planlib.swap(lay, st[1], st[2])
                _, mesh_axis, mem_pos = head[-1]
                sp = planlib.owner_pos(lay, mesh_axis)
                # feasibility on the local shape the pair will SEE —
                # after the head steps, not the entry shape
                pre = tuple(re.shape[:off]) + tuple(packed.local_shape(lay))
                ck = ov.pick_chunk_axis(pre,
                                        (off + ra, off + mem_pos, off + sp),
                                        overlap_chunks)
                if ck is not None:
                    tail = (mesh_axis, mem_pos, sp, ck)
                    head = head[:-1]
            re, im = _execute(re, im, in_layout, head, inverse=True,
                              plan=packed, batch_ndim=batch_ndim,
                              overlap_chunks=overlap_chunks, fused=fused)
            if tail is not None:
                mesh_axis, mem_pos, sp, ck = tail

                def stage_inv(cr, ci):
                    cr = comm.strategies.swap_axes_wire(
                        strategy, cr, mesh_axis, shard_pos=off + sp,
                        mem_pos=off + mem_pos, wire_dtype=plan.wire_dtype)
                    ci = comm.strategies.swap_axes_wire(
                        strategy, ci, mesh_axis, shard_pos=off + sp,
                        mem_pos=off + mem_pos, wire_dtype=plan.wire_dtype)
                    return c2r(cr, ci)
                return ov.pipelined(overlap_chunks, ck, stage_inv, re, im)
            return c2r(re, im)

        if inverse:
            fn = jax.shard_map(local_real_inv, mesh=plan.mesh,
                               in_specs=(in_spec, in_spec), out_specs=out_spec,
                               check_vma=False)
        else:
            fn = jax.shard_map(local_real_fwd, mesh=plan.mesh,
                               in_specs=(in_spec,),
                               out_specs=(out_spec, out_spec), check_vma=False)
        return fn, in_layout, out_layout

    def local(re, im):
        if plan.method == 'block':
            # §Perf iteration 2: block-complex state (leading axis 2) —
            # each superstep is two dots, the transposes move one array
            x = jnp.stack([re, im])
            off = batch_ndim + 1
            lay = in_layout
            strategy = comm.resolve(plan.comm)
            for step in steps:
                if step[0] == 'fft':
                    x = methods.apply_block(
                        x, axis=off + step[1], inverse=inverse,
                        compute_dtype=plan.compute_dtype,
                        kernel=plan.kernel_tier)
                else:
                    _, mesh_axis, mem_pos = step
                    sp = planlib.owner_pos(lay, mesh_axis)
                    narrow = x.dtype == jnp.bfloat16
                    if narrow:
                        # pin the narrow dtype ON the wire: without the
                        # barriers XLA hoists the consumer's f32 upcast
                        # across the all_to_all, doubling transpose
                        # bytes (measured; CPU-backend dots upcast bf16)
                        x = comm.strategies.dbarrier(x)
                        x = strategy.swap_axes(x, mesh_axis,
                                               shard_pos=off + sp,
                                               mem_pos=off + mem_pos)
                        x = comm.strategies.dbarrier(x)
                    else:
                        x = comm.strategies.swap_axes_wire(
                            strategy, x, mesh_axis, shard_pos=off + sp,
                            mem_pos=off + mem_pos,
                            wire_dtype=plan.wire_dtype)
                    lay = planlib.swap(lay, mesh_axis, mem_pos)
            return x[0], x[1]
        return _execute(re, im, in_layout, steps, inverse=inverse, plan=plan,
                        batch_ndim=batch_ndim, overlap_chunks=overlap_chunks,
                        fused=fused)

    fn = jax.shard_map(local, mesh=plan.mesh,
                       in_specs=(in_spec, in_spec),
                       out_specs=(out_spec, out_spec), check_vma=False)
    return fn, in_layout, out_layout


def make_fused_op(plan: PencilPlan, pointwise, *,
                  batch_ndims: Tuple[int, ...] = (0,),
                  baked_batch_ndims: Tuple[int, ...] = (),
                  overlap_chunks: int = 1,
                  fused: Optional[bool] = None):
    """Fused spectral-operator executor: the forward schedule spliced to
    the reversed inverse schedule at the spectrum midpoint, with
    ``pointwise`` applied in whatever sharding the spectrum lands in.

    One shard_map runs rfft -> pointwise -> irfft; the interior spectrum
    stays in its native distributed (padded) layout, so the truncated-
    axis boundary gather of a real plan — and its inverse scatter — are
    elided entirely. ``pointwise(re, im, *extras)`` receives LOCAL
    shards of the planar spectrum (plus one planar ``(re, im)`` pair per
    extra operand / baked spectrum) and must be elementwise in the
    spectrum bins — it runs under whatever sharding the schedule
    produced, so any cross-bin mixing would silently read only the
    local shard.

    ``batch_ndims[0]`` is the main operand's leading batch rank;
    ``batch_ndims[1:]`` describe extra operands forward-transformed
    inside the same executable (one fused dispatch still);
    ``baked_batch_ndims`` describe pre-transformed planar spectra
    appended as trailing ``(re, im)`` argument pairs already in the
    spectrum layout. Real plans: ``fn(x, *extras, *baked) -> y`` (all
    real, input layout preserved). Complex plans: every operand is a
    planar pair: ``fn(re, im, *extra_pairs, *baked) -> (re, im)``.

    Returns ``(fn, in_layout, spec_layout)``.
    """
    if fused is None:
        fused = default_fused()
    plan.validate()
    methods.validate(plan.method)
    comm.validate(plan.comm)
    first = plan.real_axis
    fsteps, spec_layout = forward_schedule(plan.layout, first)
    isteps, _ = inverse_schedule(plan.layout, first)
    in_layout = plan.layout
    n_extra = len(batch_ndims) - 1

    def bspec(nb, layout):
        return P(*(((None,) * nb) + tuple(layout)))

    def barrier(pair):
        return comm.strategies.dbarrier(tuple(pair))

    if plan.real:
        ra = first
        nh = real_half_extent(plan.shape[-1])
        nh_pad = real_padded_extent(plan.shape, plan.layout,
                                    dict(plan.mesh.shape))
        packed = packed_plan(plan, nh_pad)
        assert fsteps[0] == ('fft', ra) and isteps[-1] == ('fft', ra)

        def r2c(x, off):
            re, im = methods.apply_real(x, axis=off + ra,
                                        method=plan.method,
                                        compute_dtype=plan.compute_dtype)
            if nh_pad != nh:
                pw = [(0, 0)] * re.ndim
                pw[off + ra] = (0, nh_pad - nh)
                re, im = jnp.pad(re, pw), jnp.pad(im, pw)
            return barrier((re, im))

        def c2r(re, im, off):
            re, im = barrier((re, im))
            re = jax.lax.slice_in_dim(re, 0, nh, axis=off + ra)
            im = jax.lax.slice_in_dim(im, 0, nh, axis=off + ra)
            return methods.apply_real(re, im, axis=off + ra, inverse=True,
                                      method=plan.method,
                                      compute_dtype=plan.compute_dtype)

        def local(*args):
            mains, baked = args[:1 + n_extra], args[1 + n_extra:]
            specs = []
            for x, nb in zip(mains, batch_ndims):
                if specs:
                    # serialize the operand chains: the next input only
                    # becomes available behind the previous spectrum, so
                    # XLA cannot sibling-fuse ops of independent chains
                    # (cross-chain fusion changes FMA contraction inside
                    # the twiddle multiplies and breaks fused == unfused
                    # bitwise)
                    x, specs[-1] = comm.strategies.dbarrier(
                        (x, specs[-1]))
                re, im = r2c(x, nb)
                re, im = _execute(re, im, in_layout, fsteps[1:],
                                  inverse=False, plan=packed, batch_ndim=nb,
                                  overlap_chunks=overlap_chunks, fused=fused)
                # pin the splice point: the forward section must compile
                # exactly like the standalone plan so fused == unfused
                # stays bitwise (same rationale as the r2c barrier)
                specs.append(barrier((re, im)))
            pairs = [(baked[2 * i], baked[2 * i + 1])
                     for i in range(len(baked) // 2)]
            re, im = specs[0]
            re, im = pointwise(re, im, *specs[1:], *pairs)
            re, im = barrier((re, im))
            nb = batch_ndims[0]
            re, im = _execute(re, im, spec_layout, isteps[:-1], inverse=True,
                              plan=packed, batch_ndim=nb,
                              overlap_chunks=overlap_chunks, fused=fused)
            return c2r(re, im, nb)

        in_specs = (tuple(bspec(nb, in_layout) for nb in batch_ndims)
                    + tuple(s for nb in baked_batch_ndims
                            for s in (bspec(nb, spec_layout),) * 2))
        fn = jax.shard_map(local, mesh=plan.mesh, in_specs=in_specs,
                           out_specs=bspec(batch_ndims[0], in_layout),
                           check_vma=False)
        return fn, in_layout, spec_layout

    def local_c(*args):
        base = 2 * (1 + n_extra)
        baked = args[base:]
        specs = []
        for i, nb in enumerate(batch_ndims):
            re, im = args[2 * i], args[2 * i + 1]
            if specs:
                # serialize the chains (see the real path): no
                # cross-chain sibling fusion, bitwise-stable sections
                re, im, specs[-1] = comm.strategies.dbarrier(
                    (re, im, specs[-1]))
            re, im = _execute(re, im, in_layout, fsteps, inverse=False,
                              plan=plan, batch_ndim=nb,
                              overlap_chunks=overlap_chunks, fused=fused)
            specs.append(barrier((re, im)))
        pairs = [(baked[2 * i], baked[2 * i + 1])
                 for i in range(len(baked) // 2)]
        re, im = specs[0]
        re, im = pointwise(re, im, *specs[1:], *pairs)
        re, im = barrier((re, im))
        re, im = _execute(re, im, spec_layout, isteps, inverse=True,
                          plan=plan, batch_ndim=batch_ndims[0],
                          overlap_chunks=overlap_chunks, fused=fused)
        return re, im

    in_specs = (tuple(s for nb in batch_ndims
                      for s in (bspec(nb, in_layout),) * 2)
                + tuple(s for nb in baked_batch_ndims
                        for s in (bspec(nb, spec_layout),) * 2))
    out_spec = bspec(batch_ndims[0], in_layout)
    fn = jax.shard_map(local_c, mesh=plan.mesh, in_specs=in_specs,
                       out_specs=(out_spec, out_spec), check_vma=False)
    return fn, in_layout, spec_layout


def fft3d(re, im, plan: PencilPlan, **kw) -> Planar:
    fn, _, _ = make_fft(plan, inverse=False, **kw)
    return fn(re, im)


def ifft3d(re, im, plan: PencilPlan, **kw) -> Planar:
    fn, _, _ = make_fft(plan, inverse=True, **kw)
    return fn(re, im)


fft2d = fft3d          # same machinery; the plan carries the rank
ifft2d = ifft3d
