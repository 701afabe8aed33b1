"""Large 1-D FFT: the four-step algorithm distributed over the mesh.

The length-n transform is factored n = n1 * n2 and viewed as the 2-D
array A[k1, k2] (k = k1*n2 + k2) with rows sharded over the flattened
mesh; columns DFT -> inter-factor twiddle -> rows DFT, with one
ownership swap on each side — the 1-D analogue of the paper's pencil
supersteps (and the TPU adaptation the paper cites as [17]). The swaps
dispatch through the :mod:`repro.comm` strategy registry; with a batch
axis present, ``overlap_chunks`` pipelines the whole four-step over
batch chunks so chunk i+1's DFTs overlap chunk i's exchanges
(:mod:`repro.comm.overlap`).

Internal to ``repro.fft`` — users should go through ``repro.fft.plan``,
which also handles the (n,) <-> (n1, n2) view and the natural-order
round trip.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import comm as commlib
from repro.comm import overlap as ov
from repro.core import scopes
from repro.fft import methods


def _flat_axis_index(ax, sizes=None):
    """DEPRECATED alias of :func:`repro.comm.group_index` (kept for the
    ``core.distributed`` shim): row-major flattened index over a tuple
    of mesh axis names, matching the group order all_to_all uses.
    ``sizes`` is ignored — the comm helper reads extents with the
    static ``lax.psum(1, axis)`` idiom."""
    return commlib.group_index(ax)


def make_fft1d_large(n1: int, n2: int, plan_mesh, mesh_axes=('x', 'y'), *,
                     inverse: bool = False, natural_order: bool = False,
                     method: str = 'auto', kernel: str = 'auto',
                     use_kernel: bool = False,
                     compute_dtype=None, batch: bool = False,
                     batch_spec=None, comm: str = 'all_to_all',
                     overlap_chunks: int = 1, wire_dtype: str = 'native',
                     fused=None):
    """1-D FFT of length n = n1*n2 as a distributed four-step.

    Input x viewed as row-major A[k1, k2] (k = k1*n2 + k2), rows sharded
    over the flattened mesh. Output D[j1, j2] with y[j1 + n1*j2] =
    D[j1, j2] (factor-transposed order), or the natural-order (n2, n1)
    matrix when ``natural_order``. With ``batch`` (or ``batch_spec``)
    one leading batch axis rides along, replicated or sharded over
    ``batch_spec``; ``overlap_chunks > 1`` pipelines the schedule over
    chunks of that batch axis. ``comm`` names the redistribution
    strategy (:mod:`repro.comm`); ``kernel`` the local-compute tier
    (``use_kernel`` is the deprecated boolean alias). With ``fused``
    (default on, see :func:`repro.fft.pencil.default_fused`) the column
    DFT, the inter-factor twiddle rotation and the orientation restore
    run as ONE fused superstep, and the natural-order epilogue's local
    transpose is emitted by the row DFT itself.
    """
    methods.validate(method)
    kern = methods._merge_kernel_arg(methods.validate_kernel(kernel),
                                     use_kernel)
    commlib.validate(comm)
    if fused is None:
        from repro.fft.pencil import default_fused
        fused = default_fused()
    n = n1 * n2
    ax = mesh_axes if isinstance(mesh_axes, tuple) else (mesh_axes,)
    psize = 1
    for a in ax:
        psize *= plan_mesh.shape[a]
    if n1 % psize or n2 % psize:
        raise ValueError(f"{psize} devices must divide both factors ({n1},{n2})")
    off = 1 if (batch or batch_spec is not None) else 0
    mesh_axis = ax if len(ax) > 1 else ax[0]
    strategy = commlib.resolve(comm)
    commlib.strategies.validate_wire_dtype(wire_dtype)

    def wswap(a, shard_pos, mem_pos):
        return commlib.strategies.swap_axes_wire(
            strategy, a, mesh_axis, shard_pos=shard_pos, mem_pos=mem_pos,
            wire_dtype=wire_dtype)

    def _twiddle(transposed: bool):
        # W[j1, k2_global] on the local k2 chunk; ``transposed`` gives
        # the (k2, j1) orientation the fused superstep consumes — the
        # integer products j1*k2 are identical either way, so the two
        # orientations hold bitwise-equal values
        idx = commlib.group_index(mesh_axis)
        m2 = n2 // psize
        k2 = idx * m2 + jnp.arange(m2)
        j1 = jnp.arange(n1)
        jk = (k2[:, None] * j1[None, :] if transposed
              else j1[:, None] * k2[None, :])
        ang = (-2.0 * np.pi / n) * jk
        wr, wi = jnp.cos(ang), jnp.sin(ang)
        if inverse:
            wi = -wi
        return wr, wi

    def body(ar, ai):
        # superstep 0 — in: (n1/p, n2) rows-sharded. swap -> (n1, n2/p)
        with scopes.scope(scopes.step(0)):
            ar = wswap(ar, off + 0, off + 1)
            ai = wswap(ai, off + 0, off + 1)
            if fused:
                # fused superstep: columns DFT over k1 + inter-factor
                # twiddle + orientation restore in ONE pass — the
                # rotation and both moveaxis passes around the column
                # FFT fold into the FFT's own transposed emit (in-kernel
                # on the Pallas tier), so the swap back reads pre-rotated
                # data
                with scopes.scope(scopes.PASS):
                    wr, wi = _twiddle(transposed=True)   # (m2, n1)
                with scopes.scope(scopes.RELAYOUT):
                    tr = jnp.swapaxes(ar, off + 0, off + 1)
                    ti = jnp.swapaxes(ai, off + 0, off + 1)
                ar, ai = methods.apply_fused(
                    tr, ti, wr=wr, wi=wi, inverse=inverse, method=method,
                    compute_dtype=compute_dtype, kernel=kern)
            else:
                # columns DFT over k1 (local axis 0)
                ar, ai = methods.apply(ar, ai, axis=off + 0,
                                       inverse=inverse, method=method,
                                       compute_dtype=compute_dtype,
                                       kernel=kern)
                with scopes.scope(scopes.PASS):
                    wr, wi = _twiddle(transposed=False)  # (n1, m2)
                    ar, ai = ar * wr - ai * wi, ar * wi + ai * wr
        # superstep 1 — swap back -> (n1/p, n2); rows DFT over k2
        with scopes.scope(scopes.step(1)):
            ar = wswap(ar, off + 1, off + 0)
            ai = wswap(ai, off + 1, off + 0)
            if natural_order and fused:
                # rows DFT with transposed emit: the fused op's (j2, j1)
                # output IS the natural-order local transpose, so only
                # the ownership exchange remains (at the permuted
                # positions)
                ar, ai = methods.apply_fused(ar, ai, inverse=inverse,
                                             method=method,
                                             compute_dtype=compute_dtype,
                                             kernel=kern)
            else:
                ar, ai = methods.apply(ar, ai, axis=off + 1,
                                       inverse=inverse, method=method,
                                       compute_dtype=compute_dtype,
                                       kernel=kern)
        if not natural_order:
            return ar, ai
        # superstep 2 — content transpose D -> D.T: exchange ownership
        # (then the local transpose, unless the fused emit made it)
        with scopes.scope(scopes.step(2)):
            if fused:
                ar = wswap(ar, off + 1, off + 0)         # -> (n2/p, n1)
                ai = wswap(ai, off + 1, off + 0)
                return ar, ai
            ar = wswap(ar, off + 0, off + 1)
            ai = wswap(ai, off + 0, off + 1)
            with scopes.scope(scopes.RELAYOUT):
                ar = ar.swapaxes(off + 0, off + 1)      # (n2/p, n1)
                ai = ai.swapaxes(off + 0, off + 1)
            return ar, ai

    def local(ar, ai):
        # the whole four-step is batch-independent: pipelining it over
        # batch chunks overlaps chunk i's swaps with chunk i+1's DFTs;
        # the shared chunk-axis rule falls back to the unpipelined body
        # when the batch doesn't divide (e.g. odd request counts)
        ck = (ov.pick_chunk_axis(ar.shape[:1], (), overlap_chunks)
              if off else None)
        if ck is not None:
            return ov.pipelined(overlap_chunks, ck, body, ar, ai)
        return body(ar, ai)

    spec = P(*(((batch_spec,) if off else ()) + (mesh_axis, None)))
    return jax.shard_map(local, mesh=plan_mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False)


def _real_fourstep(n1, n2, psize, mesh_axis, strategy, wire_dtype,
                   method, kern, compute_dtype):
    """Shared real four-step bodies, parameterized over the leading
    batch rank ``off`` so the transform path (:func:`make_rfft1d_large`,
    one flattened batch axis) and the fused operator path
    (:func:`make_fourstep_op`, arbitrary broadcastable batch dims) run
    the SAME float ops. Returns (body_fwd, body_inv, nh1, nh1p).

    Both bodies pin rounding at their spectrum-side boundary
    (:func:`repro.fft.pencil.pin_rounding`): the four-step is pure
    elementwise butterflies with no materializing transpose at the
    ends, so without the pin XLA FMA-contracts the trailing stockham /
    r2c multiplies into whatever consumes the spectrum — the facade's
    assembly epilogue in one program, the operator plan's pointwise in
    the other — and fused == unfused stops being bitwise.

    ``body_fwd`` also Hermitian-canonicalizes the half plane: rows 0
    and n1/2 contain internal conjugate pairs (row 0: (0, j2) pairs
    with (0, n2-j2); row n1/2: (n1/2, j2) with (n1/2, n2-1-j2)), and
    the butterflies compute the two partners through different float
    paths, so they are NOT exact conjugates. The facade's half plane ->
    ``np.fft.rfft``-order assembly keeps only the ``k <= n/2``
    representative of each pair and the inverse prologue rebuilds the
    other as its exact conjugate; canonicalizing here makes the raw
    spectrum identical to that round trip (interior rows survive it
    bit-exactly already — their partners live in the discarded mirror
    half, reconstructed as conj(conj(D)) = D), so a fused operator
    plan's pointwise sees exactly the bins the unfused composition
    sees. Conjugation is a sign flip — no rounding — and any
    conjugation-equivariant pointwise then preserves the exact
    symmetry through to the inverse."""
    from repro.fft.pencil import pin_rounding
    n = n1 * n2
    nh1 = n1 // 2 + 1
    nh1p = -(-nh1 // psize) * psize

    def wswap(a, shard_pos, mem_pos):
        return commlib.strategies.swap_axes_wire(
            strategy, a, mesh_axis, shard_pos=shard_pos, mem_pos=mem_pos,
            wire_dtype=wire_dtype)

    def _twiddle(conj: bool):
        # W[j1, k2_global] on this device's k2 chunk; the pad rows get
        # whatever phase falls out — they carry zeros
        idx = commlib.group_index(mesh_axis)
        m2 = n2 // psize
        k2 = idx * m2 + jnp.arange(m2)
        j1 = jnp.arange(nh1p)
        ang = (-2.0 * np.pi / n) * (j1[:, None] * k2[None, :])
        wr, wi = jnp.cos(ang), jnp.sin(ang)
        return (wr, -wi) if conj else (wr, wi)

    def body_fwd(x, off):
        with scopes.scope(scopes.step(0)):
            # in: (n1/p, n2) real rows-sharded; swap moves ONE real array
            x = wswap(x, off + 0, off + 1)
            # r2c column DFT over k1 -> (nh1, n2/p), padded rows
            ar, ai = methods.apply_real(x, axis=off + 0, method=method,
                                        compute_dtype=compute_dtype,
                                        kernel=kern)
            if nh1p != nh1:
                pw = [(0, 0)] * ar.ndim
                pw[off + 0] = (0, nh1p - nh1)
                with scopes.scope(scopes.RELAYOUT):
                    ar, ai = jnp.pad(ar, pw), jnp.pad(ai, pw)
            with scopes.scope(scopes.PASS):
                wr, wi = _twiddle(conj=False)
                ar, ai = ar * wr - ai * wi, ar * wi + ai * wr
        with scopes.scope(scopes.step(1)):
            # swap back -> (nh1p/p, n2); row DFT over k2
            ar = wswap(ar, off + 1, off + 0)
            ai = wswap(ai, off + 1, off + 0)
            ar, ai = methods.apply(ar, ai, axis=off + 1, method=method,
                                   compute_dtype=compute_dtype, kernel=kern)
            with scopes.scope(scopes.PASS):
                ar, ai = pin_rounding(ar, ai)
            with scopes.scope(scopes.COMBINE):
                return _canon(ar, ai)

    def _canon(ar, ai):
        # Hermitian-canonicalize rows 0 and n1//2 (see the factory
        # docstring). Rows are the -2 axis of the local (.., rl, n2)
        # block; each row is fully in-memory, so the column remaps are
        # local. Pad rows (global row >= nh1) never match the masks.
        idx = commlib.group_index(mesh_axis)
        rl = ar.shape[-2]
        grow = (idx * rl + jnp.arange(rl))[:, None]
        j2 = jnp.arange(n2)
        # row 0: (0, j2) := conj(D[0, n2 - j2]) for 2*j2 > n2
        m0 = (grow == 0) & (2 * j2 > n2)
        pr = jnp.roll(jnp.flip(ar, -1), 1, -1)   # c -> (n2 - c) % n2
        pi = jnp.roll(jnp.flip(ai, -1), 1, -1)
        ar = jnp.where(m0, pr, ar)
        ai = jnp.where(m0, -pi, ai)
        if n1 % 2 == 0:
            # row n1/2: (j2) := conj(D[n1/2, n2-1-j2]) for 2*j2 >= n2
            mh = (grow == n1 // 2) & (2 * j2 >= n2)
            ar = jnp.where(mh, jnp.flip(ar, -1), ar)
            ai = jnp.where(mh, -jnp.flip(ai, -1), ai)
        return ar, ai

    def body_inv(ar, ai, off):
        with scopes.scope(scopes.step(0)):
            # in: (nh1p/p, n2) planar rows-sharded; row IDFT over j2
            with scopes.scope(scopes.PASS):
                ar, ai = pin_rounding(ar, ai)
            ar, ai = methods.apply(ar, ai, axis=off + 1, inverse=True,
                                   method=method, compute_dtype=compute_dtype,
                                   kernel=kern)
            # swap -> (nh1p, n2/p)
            ar = wswap(ar, off + 0, off + 1)
            ai = wswap(ai, off + 0, off + 1)
        with scopes.scope(scopes.step(1)):
            # conjugate twiddle
            with scopes.scope(scopes.PASS):
                wr, wi = _twiddle(conj=True)
                ar, ai = ar * wr - ai * wi, ar * wi + ai * wr
            # drop pad rows, c2r column IDFT -> (n1, n2/p) real
            with scopes.scope(scopes.RELAYOUT):
                ar = lax.slice_in_dim(ar, 0, nh1, axis=off + 0)
                ai = lax.slice_in_dim(ai, 0, nh1, axis=off + 0)
            x = methods.apply_real(ar, ai, axis=off + 0, inverse=True,
                                   method=method, compute_dtype=compute_dtype,
                                   kernel=kern)
            # swap the real array back to rows-sharded
            return wswap(x, off + 1, off + 0)

    return body_fwd, body_inv, nh1, nh1p


def _complex_fourstep(n1, n2, psize, mesh_axis, strategy, wire_dtype,
                      method, kern, compute_dtype, fused):
    """Complex four-step bodies in the factor-transposed D-form —
    ``body_fwd`` is :func:`make_fft1d_large`'s body without the
    natural-order epilogue (D[j1, j2] = Y[j1 + n1*j2], every bin
    represented exactly once, so elementwise spectrum ops are exact);
    ``body_inv`` is its step-by-step mirror consuming that D-form
    directly. Used by the fused operator path, where the natural-order
    round trip through memory is precisely what gets elided."""
    n = n1 * n2

    def wswap(a, shard_pos, mem_pos):
        return commlib.strategies.swap_axes_wire(
            strategy, a, mesh_axis, shard_pos=shard_pos, mem_pos=mem_pos,
            wire_dtype=wire_dtype)

    def _twiddle(transposed: bool, conj: bool):
        idx = commlib.group_index(mesh_axis)
        m2 = n2 // psize
        k2 = idx * m2 + jnp.arange(m2)
        j1 = jnp.arange(n1)
        jk = (k2[:, None] * j1[None, :] if transposed
              else j1[:, None] * k2[None, :])
        ang = (-2.0 * np.pi / n) * jk
        wr, wi = jnp.cos(ang), jnp.sin(ang)
        return (wr, -wi) if conj else (wr, wi)

    def body_fwd(ar, ai, off):
        with scopes.scope(scopes.step(0)):
            # in: (n1/p, n2) rows-sharded. swap -> (n1, n2/p)
            ar = wswap(ar, off + 0, off + 1)
            ai = wswap(ai, off + 0, off + 1)
            if fused:
                with scopes.scope(scopes.PASS):
                    wr, wi = _twiddle(transposed=True, conj=False)
                with scopes.scope(scopes.RELAYOUT):
                    tr = jnp.swapaxes(ar, off + 0, off + 1)
                    ti = jnp.swapaxes(ai, off + 0, off + 1)
                ar, ai = methods.apply_fused(
                    tr, ti, wr=wr, wi=wi, inverse=False, method=method,
                    compute_dtype=compute_dtype, kernel=kern)
            else:
                ar, ai = methods.apply(ar, ai, axis=off + 0, inverse=False,
                                       method=method,
                                       compute_dtype=compute_dtype,
                                       kernel=kern)
                with scopes.scope(scopes.PASS):
                    wr, wi = _twiddle(transposed=False, conj=False)
                    ar, ai = ar * wr - ai * wi, ar * wi + ai * wr
        with scopes.scope(scopes.step(1)):
            # swap back -> (n1/p, n2); rows DFT over k2 -> D[j1, j2]
            ar = wswap(ar, off + 1, off + 0)
            ai = wswap(ai, off + 1, off + 0)
            return methods.apply(ar, ai, axis=off + 1, inverse=False,
                                 method=method, compute_dtype=compute_dtype,
                                 kernel=kern)

    def body_inv(ar, ai, off):
        # exact mirror: rows IDFT over j2, swap, conjugate twiddle,
        # columns IDFT over j1, swap back — 1/n2 then 1/n1 scaling
        # matches the natural-order inverse's ifft pair
        with scopes.scope(scopes.step(0)):
            ar, ai = methods.apply(ar, ai, axis=off + 1, inverse=True,
                                   method=method, compute_dtype=compute_dtype,
                                   kernel=kern)
            ar = wswap(ar, off + 0, off + 1)
            ai = wswap(ai, off + 0, off + 1)
        with scopes.scope(scopes.step(1)):
            with scopes.scope(scopes.PASS):
                wr, wi = _twiddle(transposed=False, conj=True)
                ar, ai = ar * wr - ai * wi, ar * wi + ai * wr
            ar, ai = methods.apply(ar, ai, axis=off + 0, inverse=True,
                                   method=method, compute_dtype=compute_dtype,
                                   kernel=kern)
            ar = wswap(ar, off + 1, off + 0)
            ai = wswap(ai, off + 1, off + 0)
            return ar, ai

    return body_fwd, body_inv


def make_fourstep_op(n1: int, n2: int, plan_mesh, mesh_axes, pointwise, *,
                     real: bool = True,
                     batch_ndims=(0,), baked_batch_ndims=(),
                     method: str = 'auto', kernel: str = 'auto',
                     compute_dtype=None, comm: str = 'all_to_all',
                     wire_dtype: str = 'native', fused=None):
    """Rank-1 fused spectral operator: four-step forward -> pointwise ->
    mirrored four-step inverse in ONE shard_map.

    The pointwise stage runs in the native distributed spectrum form —
    the rows-halved half plane ``D[j1 <= n1//2, j2]`` for real plans
    (every represented entry is a true ``rfft`` bin; the zero pad rows
    are sliced off by the inverse), the factor-transposed ``D[j1, j2]``
    for complex plans — so the Hermitian-mirror / natural-order
    assembly that the facade round-trips through memory is elided
    entirely. ``pointwise`` must be elementwise in the bins and (real
    plans) conjugation-equivariant — true of any multiplicative
    spectral factor, e.g. convolution.

    ``batch_ndims`` / ``baked_batch_ndims`` as in
    :func:`repro.fft.pencil.make_fused_op`; operands are the (n1, n2)
    row-major views, which the facade owns. Real plans:
    ``fn(x, *extras, *baked_pairs) -> y``; complex: planar pairs.
    """
    methods.validate(method)
    kern = methods.validate_kernel(kernel)
    commlib.validate(comm)
    if fused is None:
        from repro.fft.pencil import default_fused
        fused = default_fused()
    ax = mesh_axes if isinstance(mesh_axes, tuple) else (mesh_axes,)
    psize = 1
    for a in ax:
        psize *= plan_mesh.shape[a]
    if n1 % psize or n2 % psize:
        raise ValueError(f"{psize} devices must divide both factors ({n1},{n2})")
    mesh_axis = ax if len(ax) > 1 else ax[0]
    strategy = commlib.resolve(comm)
    commlib.strategies.validate_wire_dtype(wire_dtype)
    n_extra = len(batch_ndims) - 1

    def bspec(nb):
        return P(*(((None,) * nb) + (mesh_axis, None)))

    def barrier(pair):
        return commlib.strategies.dbarrier(tuple(pair))

    if real:
        body_fwd, body_inv, _, _ = _real_fourstep(
            n1, n2, psize, mesh_axis, strategy, wire_dtype, method, kern,
            compute_dtype)

        def local(*args):
            mains, baked = args[:1 + n_extra], args[1 + n_extra:]
            specs = []
            for x, nb in zip(mains, batch_ndims):
                if specs:
                    # serialize the operand chains: the next input enters
                    # the graph behind the previous spectrum, so XLA
                    # cannot sibling-fuse independent chains (cross-chain
                    # fusion changes FMA contraction in the twiddle
                    # multiplies and breaks fused == unfused bitwise)
                    x, specs[-1] = commlib.strategies.dbarrier(
                        (x, specs[-1]))
                specs.append(barrier(body_fwd(x, nb)))
            pairs = [(baked[2 * i], baked[2 * i + 1])
                     for i in range(len(baked) // 2)]
            ar, ai = specs[0]
            ar, ai = pointwise(ar, ai, *specs[1:], *pairs)
            ar, ai = barrier((ar, ai))
            return body_inv(ar, ai, batch_ndims[0])

        in_specs = (tuple(bspec(nb) for nb in batch_ndims)
                    + tuple(s for nb in baked_batch_ndims
                            for s in (bspec(nb),) * 2))
        return jax.shard_map(local, mesh=plan_mesh, in_specs=in_specs,
                             out_specs=bspec(batch_ndims[0]), check_vma=False)

    body_fwd, body_inv = _complex_fourstep(
        n1, n2, psize, mesh_axis, strategy, wire_dtype, method, kern,
        compute_dtype, fused)

    def local_c(*args):
        base = 2 * (1 + n_extra)
        baked = args[base:]
        specs = []
        for i, nb in enumerate(batch_ndims):
            ar, ai = args[2 * i], args[2 * i + 1]
            if specs:
                # serialize the chains (see the real path)
                ar, ai, specs[-1] = commlib.strategies.dbarrier(
                    (ar, ai, specs[-1]))
            specs.append(barrier(body_fwd(ar, ai, nb)))
        pairs = [(baked[2 * i], baked[2 * i + 1])
                 for i in range(len(baked) // 2)]
        ar, ai = specs[0]
        ar, ai = pointwise(ar, ai, *specs[1:], *pairs)
        ar, ai = barrier((ar, ai))
        return body_inv(ar, ai, batch_ndims[0])

    in_specs = (tuple(s for nb in batch_ndims for s in (bspec(nb),) * 2)
                + tuple(s for nb in baked_batch_ndims
                        for s in (bspec(nb),) * 2))
    out_spec = bspec(batch_ndims[0])
    return jax.shard_map(local_c, mesh=plan_mesh, in_specs=in_specs,
                         out_specs=(out_spec, out_spec), check_vma=False)


def make_rfft1d_large(n1: int, n2: int, plan_mesh, mesh_axes=('x', 'y'), *,
                      inverse: bool = False, method: str = 'auto',
                      kernel: str = 'auto', use_kernel: bool = False,
                      compute_dtype=None,
                      batch: bool = False, batch_spec=None,
                      comm: str = 'all_to_all', overlap_chunks: int = 1,
                      wire_dtype: str = 'native'):
    """Rank-1 REAL four-step: the rows-halved half-plane form.

    Forward consumes the real row-major view A[k1, k2] (rows sharded
    over the flattened mesh) and produces the planar half plane
    D[j1, j2] for j1 <= n1//2 (rows padded to ``nh1p`` for even
    sharding, same spec): the column DFT is r2c — one length-n1/2
    complex pencil per column plus the Hermitian combine — and the
    remaining rows carry every rfft output bin (``j1 > n1//2`` rows are
    conjugate-redundant). Wire bytes halve twice over the complex path:
    the first swap moves ONE real array instead of a planar pair, and
    the second swap moves the halved row count. Inverse is the exact
    mirror (row IDFT, conjugate twiddle, column c2r, real swap back).
    The half plane <-> ``np.fft.rfft``-order assembly lives in the
    facade (:mod:`repro.fft.api`), which owns the (n,) views.
    """
    methods.validate(method)
    kern = methods._merge_kernel_arg(methods.validate_kernel(kernel),
                                     use_kernel)
    commlib.validate(comm)
    ax = mesh_axes if isinstance(mesh_axes, tuple) else (mesh_axes,)
    psize = 1
    for a in ax:
        psize *= plan_mesh.shape[a]
    if n1 % psize or n2 % psize:
        raise ValueError(f"{psize} devices must divide both factors ({n1},{n2})")
    off = 1 if (batch or batch_spec is not None) else 0
    mesh_axis = ax if len(ax) > 1 else ax[0]
    strategy = commlib.resolve(comm)
    commlib.strategies.validate_wire_dtype(wire_dtype)

    body_fwd, body_inv, _, _ = _real_fourstep(
        n1, n2, psize, mesh_axis, strategy, wire_dtype, method, kern,
        compute_dtype)

    # barrier-bound the four-step body: the facade's half-plane <-> np
    # order assembly compiles in the same program, and letting XLA fuse
    # it into the body changes contraction decisions — the body must
    # compile exactly as it does inside a fused operator plan
    # (:func:`make_fourstep_op`) so fused == unfused stays bitwise
    if inverse:
        def body(ar, ai):
            ar, ai = commlib.strategies.dbarrier((ar, ai))
            return body_inv(ar, ai, off)
    else:
        def body(x):
            return commlib.strategies.dbarrier(body_fwd(x, off))

    def local(*arrays):
        ck = (ov.pick_chunk_axis(arrays[0].shape[:1], (), overlap_chunks)
              if off else None)
        if ck is not None:
            return ov.pipelined(overlap_chunks, ck, body, *arrays)
        return body(*arrays)

    spec = P(*(((batch_spec,) if off else ()) + (mesh_axis, None)))
    if inverse:
        return jax.shard_map(local, mesh=plan_mesh, in_specs=(spec, spec),
                             out_specs=spec, check_vma=False)
    return jax.shard_map(local, mesh=plan_mesh, in_specs=(spec,),
                         out_specs=(spec, spec), check_vma=False)
