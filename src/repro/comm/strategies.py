"""The redistribution strategy registry.

One inter-device ownership swap — exchange the in-memory array axis
with an axis owned by a mesh axis — is the repo's universal collective:
the wsFFT transpose supersteps (§4.2-§4.4), the four-step 1-D factor
exchanges, MoE expert dispatch and Ulysses sequence-parallel attention
all reduce to it. This module makes *how* that swap moves bytes a
pluggable choice, mirroring the local-pencil method registry
(:mod:`repro.fft.methods`):

* ``'all_to_all'`` — one tiled ``lax.all_to_all``: the TPU-native form
  of the paper's broadcast-and-filter transpose (§4.3). Default.
* ``'ppermute'``   — a pairwise ring schedule built from
  ``lax.ppermute``: p-1 rounds, round s sending each device's block for
  its s-th successor. Every round is a plain point-to-point permute, so
  it lowers on meshes/backends where all_to_all lowers poorly, and its
  bottleneck-link traffic is roughly half the broadcast-and-filter
  stream (cf. the multi-phase schedules of arXiv 2404.15888).
* ``'hierarchical'`` — a two-phase pod-split exchange for swaps over a
  *tuple* of mesh axes: all_to_all across the pod (outer) axis first,
  then within pods, then one local reorder of the concatenated blocks.
  Pays two small-group exchanges plus a local transpose instead of one
  p-wide exchange — it wins when the per-peer reconfiguration/latency
  term dominates (many peers, small blocks).
* ``'pod_tree:<spec>'`` — the generalization of ``'hierarchical'`` to
  an *arbitrary factorization tree* (cf. the multi-phase mesh
  collectives of arXiv 2404.15888): ``spec`` lists per-axis factor
  sequences (``'pod_tree:x.4*y.2*y.2'`` factors a 4x4 group as
  4 -> 2 x 2 along y), and the swap executes one grouped sub-exchange
  per factor — ``lax.all_to_all`` when a factor covers a whole named
  axis, strided ``lax.ppermute`` rounds for proper sub-factors — plus
  one local reorder. ``comm='auto'`` searches these trees via
  :func:`repro.comm.cost.enumerate_trees`.

Orthogonally, every strategy can carry a compact **wire format**
(:func:`wire_cast` / :func:`swap_axes_wire`): operands are cast to
fp16/bf16 immediately before the swap collective and restored right
after, so the wire moves half the bytes while all compute stays in the
request precision (the paper's FP16-vs-FP32 study, applied to the wire
only).

Every strategy implements the same :class:`Strategy` interface and is
**bit-exact**: for any operand the three produce identical results
(identical data placement — they are pure data movement), so swapping
strategies can never change numerics, only the schedule on the wire.

All ``swap``/``swap_axes`` calls run *inside* ``shard_map``: they see
per-device local blocks and named mesh axes. Group sizes are recovered
at trace time with the static ``lax.psum(1, axis)`` idiom, so no Mesh
object is threaded through.
"""
from __future__ import annotations

import functools
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import plan as planlib
from repro.core import scopes
from repro.core import wse_model as wm
from repro.core.plan import Layout, MeshAxis


# ---------------------------------------------------------------------------
# Group helpers (trace-time, inside shard_map)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _barrier_flat(*xs):
    return lax.optimization_barrier(xs)


def _barrier_fwd(*xs):
    return _barrier_flat(*xs), None


def _barrier_bwd(_, cts):
    return cts


_barrier_flat.defvjp(_barrier_fwd, _barrier_bwd)


def dbarrier(tree):
    """``lax.optimization_barrier`` with an identity gradient.

    The stock primitive has no differentiation rule, which would make
    every plan whose schedule pins a boundary (wire casts, superstep
    serialization) untrainable — and operator plans sit inside training
    steps (the fftconv mixer). Reverse mode passes cotangents through
    unchanged (the barrier IS an identity); the primal lowers to the
    plain barrier, so compiled programs — and the fused == unfused
    bitwise contract — are untouched.
    """
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, _barrier_flat(*leaves))


def axis_tuple(mesh_axis: MeshAxis) -> Tuple[str, ...]:
    """Canonicalize a mesh-axis spec to a tuple of axis names."""
    if mesh_axis is None:
        return ()
    return mesh_axis if isinstance(mesh_axis, tuple) else (mesh_axis,)


def group_size(mesh_axis: MeshAxis) -> int:
    """Static group size of a (possibly tuple) mesh axis, from inside
    shard_map: ``lax.psum(1, axis)`` of a Python literal folds to the
    axis extent at trace time."""
    p = 1
    for a in axis_tuple(mesh_axis):
        p *= lax.psum(1, a)
    return p


def group_index(mesh_axis: MeshAxis):
    """This device's row-major flat index within the (possibly tuple)
    mesh-axis group — the same member order ``all_to_all`` uses for
    tuple axis names."""
    axes = axis_tuple(mesh_axis)
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Strategy interface
# ---------------------------------------------------------------------------

class Strategy:
    """One registered redistribution schedule.

    ``swap_axes`` is the low-level form (explicit split/concat
    positions); ``swap`` adds the layout bookkeeping the planners
    thread; ``cost`` is the trace-time hook into the paper's cycle
    model (:mod:`repro.core.wse_model`) the ``comm='auto'`` selector
    ranks strategies with.
    """
    name: str = ''
    description: str = ''

    def swap_axes(self, x: jax.Array, mesh_axis: MeshAxis, *,
                  shard_pos: int, mem_pos: int) -> jax.Array:
        """Exchange ownership: split local axis ``mem_pos`` across the
        group, concatenate received blocks (in group order) along
        ``shard_pos``. Must be bit-identical to the tiled all_to_all."""
        raise NotImplementedError

    def swap(self, x: jax.Array, layout: Layout, mesh_axis: MeshAxis,
             mem_pos: int) -> Tuple[jax.Array, Layout]:
        """swap + layout bookkeeping."""
        sp = planlib.owner_pos(layout, mesh_axis)
        with scopes.scope(scopes.SWAP):
            y = self.swap_axes(x, mesh_axis, shard_pos=sp, mem_pos=mem_pos)
        return y, planlib.swap(layout, mesh_axis, mem_pos)

    def cost(self, mesh_axis: MeshAxis, mesh_shape, elems: float,
             precision: wm.Precision, *,
             axis_bw: Optional[Mapping[str, float]] = None) -> wm.SwapCost:
        """Predicted cycles for one swap of ``elems`` local complex
        elements over ``mesh_axis`` of a mesh with extents
        ``mesh_shape`` (a name->size mapping; no device objects
        needed, so paper-scale meshes can be costed abstractly).
        ``axis_bw`` optionally maps axis name -> relative bandwidth
        weight (>= 1 scales the wire term; asymmetric topologies)."""
        raise NotImplementedError


_REGISTRY: Dict[str, Strategy] = {}


def register(strategy: Strategy) -> Strategy:
    if strategy.name in _REGISTRY:
        raise ValueError(f"comm strategy {strategy.name!r} already registered")
    _REGISTRY[strategy.name] = strategy
    return strategy


def names() -> Tuple[str, ...]:
    """Registered concrete strategy names (excludes the 'auto' alias)."""
    return tuple(_REGISTRY)


def get(name: str) -> Strategy:
    if name.startswith(POD_TREE_PREFIX):
        return _pod_tree_strategy(name)
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown comm strategy {name!r}; known: "
            f"{names() + ('auto', POD_TREE_PREFIX + '<spec>')}"
        ) from None


def validate(name: str) -> str:
    """Check ``name`` is 'auto', a registered strategy, or a
    well-formed ``'pod_tree:<spec>'`` name; returns the canonical
    spelling (pod-tree specs are normalized to sorted axis order so
    equal trees share one cache/measured-table key)."""
    if name == 'auto':
        return name
    return get(name).name


def resolve(name: str) -> Strategy:
    """Strategy instance for ``name``. The ``'auto'`` alias maps to the
    default schedule ('all_to_all'): cost-model *selection* happens at
    the plan layer (``fft.plan`` / :func:`repro.comm.cost.select`);
    executors below it treat 'auto' as "the default"."""
    return get('all_to_all' if name == 'auto' else name)


def static_group_size(mesh_axis: MeshAxis, mesh_shape) -> int:
    """Group size from a name->extent mapping (outside shard_map)."""
    p = 1
    for a in axis_tuple(mesh_axis):
        p *= mesh_shape[a]
    return p


def _group_bw(mesh_axis: MeshAxis,
              axis_bw: Optional[Mapping[str, float]]) -> float:
    """Bandwidth weight of a (possibly tuple) axis group: the exchange
    is bottlenecked by its slowest participating link class."""
    axes = axis_tuple(mesh_axis)
    if not axis_bw or not axes:
        return 1.0
    return max(float(axis_bw.get(a, 1.0)) for a in axes)


def _scale_wire(cost: wm.SwapCost, bw: float) -> wm.SwapCost:
    if bw == 1.0:
        return cost
    return wm.SwapCost(cost.strategy, cost.p, cost.elems,
                       cost.wire_cycles * bw, cost.fixed_cycles)


# ---------------------------------------------------------------------------
# Wire formats: cast-to-compact around the collective only
# ---------------------------------------------------------------------------

#: valid ``wire_dtype`` values. 'native' moves request-precision bytes
#: (bit-identical, the default); 'fp16'/'bf16' cast each planar float
#: component to 16 bits immediately before the swap collective and
#: restore after, halving wire bytes (fp32 request) at a precision cost.
WIRE_DTYPES: Tuple[str, ...] = ('native', 'fp16', 'bf16')

_WIRE_JNP = {'fp16': jnp.float16, 'bf16': jnp.bfloat16}


def validate_wire_dtype(wire_dtype: str) -> str:
    if wire_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"unknown wire_dtype {wire_dtype!r}; known: {WIRE_DTYPES}")
    return wire_dtype


def wire_elem_bytes(wire_dtype: str, native_bytes: int) -> int:
    """Bytes one (planar float) element occupies on the wire."""
    if wire_dtype == 'native':
        return native_bytes
    return min(native_bytes, 2)


def wire_cast(x: jax.Array, wire_dtype: str):
    """Cast a planar float operand to the compact wire format. Returns
    ``(wire_operand, restore_dtype)``; ``restore_dtype`` is None when no
    cast happened (native wire, already-narrow or non-float operand).
    The optimization barrier pins the cast against the collective so
    XLA cannot hoist the upcast across it and silently move wide
    bytes."""
    if wire_dtype == 'native':
        return x, None
    wd = jnp.dtype(_WIRE_JNP[validate_wire_dtype(wire_dtype)])
    if (not jnp.issubdtype(x.dtype, jnp.floating)
            or jnp.dtype(x.dtype).itemsize <= wd.itemsize):
        # already at (or below) wire width — e.g. a bf16 block-state
        # operand under an fp16 wire: recasting moves no fewer bytes
        return x, None
    return dbarrier(x.astype(wd)), x.dtype


def wire_restore(x: jax.Array, restore_dtype) -> jax.Array:
    """Undo :func:`wire_cast` after the collective."""
    if restore_dtype is None:
        return x
    return dbarrier(x).astype(restore_dtype)


def swap_axes_wire(strategy: 'Strategy', x: jax.Array, mesh_axis: MeshAxis,
                   *, shard_pos: int, mem_pos: int,
                   wire_dtype: str = 'native') -> jax.Array:
    """One ownership swap with the operand cast to the compact wire
    format around the collective only — all upstream/downstream compute
    sees the original dtype."""
    with scopes.scope(scopes.SWAP):
        w, restore = wire_cast(x, wire_dtype)
        y = strategy.swap_axes(w, mesh_axis, shard_pos=shard_pos,
                               mem_pos=mem_pos)
        return wire_restore(y, restore)


# ---------------------------------------------------------------------------
# Pod-tree specs: 'pod_tree:x.4*y.2*y.2' <-> {'x': (4,), 'y': (2, 2)}
# ---------------------------------------------------------------------------

POD_TREE_PREFIX = 'pod_tree:'

Tree = Dict[str, Tuple[int, ...]]


def parse_tree_spec(spec: str) -> Tree:
    """Parse a pod-tree spec: '*'-joined ``<axis>.<factor>`` levels,
    factors >= 2, per-axis order = digit significance (most significant
    first)."""
    tree: Dict[str, list] = {}
    if not spec:
        raise ValueError("empty pod_tree spec")
    for part in spec.split('*'):
        axis, sep, fac = part.rpartition('.')
        if not sep or not axis or not fac.isdigit() or int(fac) < 2:
            raise ValueError(
                f"bad pod_tree level {part!r} in spec {spec!r}; expected "
                f"'<axis>.<factor>' with an integer factor >= 2")
        tree.setdefault(axis, []).append(int(fac))
    return {a: tuple(fs) for a, fs in tree.items()}


def format_tree_spec(tree: Mapping[str, Tuple[int, ...]]) -> str:
    """Canonical spec string (axes sorted by name)."""
    return '*'.join(f'{a}.{f}' for a in sorted(tree) for f in tree[a])


# ---------------------------------------------------------------------------
# 'all_to_all': the paper's broadcast-and-filter transpose, TPU form
# ---------------------------------------------------------------------------

class AllToAllStrategy(Strategy):
    name = 'all_to_all'
    description = ('one tiled lax.all_to_all (broadcast-and-filter '
                   'transpose, §4.3)')

    def swap_axes(self, x, mesh_axis, *, shard_pos, mem_pos):
        return lax.all_to_all(x, mesh_axis, split_axis=mem_pos,
                              concat_axis=shard_pos, tiled=True)

    def cost(self, mesh_axis, mesh_shape, elems, precision, *, axis_bw=None):
        p = static_group_size(mesh_axis, mesh_shape)
        return _scale_wire(
            wm.swap_cost_a2a(p, elems, precision, strategy=self.name),
            _group_bw(mesh_axis, axis_bw))


# ---------------------------------------------------------------------------
# Shared two-phase (pod-split) decomposition
# ---------------------------------------------------------------------------

def two_phase_swap(x, axes: Tuple[str, ...], *, shard_pos: int, mem_pos: int,
                   exchange) -> jax.Array:
    """Ownership swap over a tuple axis group as two phased exchanges.

    ``exchange(x, axis, shard_pos, mem_pos)`` performs the single-group
    swap for one phase (``axis`` is the outer name, then the inner
    name/tuple). Phase 1 delivers the p_out superblocks — superblock j
    covers the p_in blocks bound for pod j, because the flat group
    order is row-major (outer major); phase 2 splits every received
    superblock identically across the pod. Received order is then
    (inner-source, outer-source); one local transpose restores the flat
    row-major group order, making the whole thing bit-identical to the
    one-shot exchange over the full group.
    """
    outer = axes[0]
    inner = axes[1] if len(axes) == 2 else axes[1:]
    p_out = group_size(outer)
    p_in = group_size(inner)
    seg = x.shape[shard_pos]
    y = exchange(x, outer, shard_pos, mem_pos)
    z = exchange(y, inner, shard_pos, mem_pos)
    shp = z.shape
    z = z.reshape(shp[:shard_pos] + (p_in, p_out, seg) + shp[shard_pos + 1:])
    z = z.swapaxes(shard_pos, shard_pos + 1)
    return z.reshape(shp)


# ---------------------------------------------------------------------------
# 'ppermute': pairwise ring exchange
# ---------------------------------------------------------------------------

class PpermuteStrategy(Strategy):
    name = 'ppermute'
    description = ('p-1 pairwise ppermute rounds per axis (ring schedule; '
                   'point-to-point only)')

    @staticmethod
    def _ring(x, axis_name: str, shard_pos: int, mem_pos: int):
        """Single-named-axis ring: round s sends each device's block for
        its s-th successor. (Tuple groups go through the two-phase
        decomposition: jax flattens a tuple-axis ppermute's perm in mesh
        order, not tuple order, so only single-axis perms are
        portable.)"""
        p = lax.psum(1, axis_name)
        if p == 1:
            return x
        if x.shape[mem_pos] % p:
            # match the loud failure of the tiled all_to_all instead of
            # truncating blocks (dynamic_slice clamps out-of-range starts)
            raise ValueError(
                f"ring swap: mem axis size {x.shape[mem_pos]} not divisible "
                f"by group size {p} of axis {axis_name!r}")
        idx = lax.axis_index(axis_name)
        blk = x.shape[mem_pos] // p
        seg = x.shape[shard_pos]
        out_shape = list(x.shape)
        out_shape[mem_pos] = blk
        out_shape[shard_pos] = seg * p
        # own block keeps its relative position: global slot = own index
        own = lax.dynamic_slice_in_dim(x, idx * blk, blk, axis=mem_pos)
        out = jnp.zeros(tuple(out_shape), x.dtype)
        out = lax.dynamic_update_slice_in_dim(out, own, idx * seg,
                                              axis=shard_pos)
        for s in range(1, p):
            # round s: send the block for my s-th successor, receive the
            # block my s-th predecessor holds for me
            dst = (idx + s) % p
            send = lax.dynamic_slice_in_dim(x, dst * blk, blk, axis=mem_pos)
            recv = lax.ppermute(send, axis_name,
                                [(i, (i + s) % p) for i in range(p)])
            src = (idx - s) % p
            out = lax.dynamic_update_slice_in_dim(out, recv, src * seg,
                                                  axis=shard_pos)
        return out

    def swap_axes(self, x, mesh_axis, *, shard_pos, mem_pos):
        axes = axis_tuple(mesh_axis)
        if len(axes) == 1:
            return self._ring(x, axes[0], shard_pos, mem_pos)
        return two_phase_swap(
            x, axes, shard_pos=shard_pos, mem_pos=mem_pos,
            exchange=lambda a, ax, sp, mp: self.swap_axes(
                a, ax, shard_pos=sp, mem_pos=mp))

    def cost(self, mesh_axis, mesh_shape, elems, precision, *, axis_bw=None):
        p = static_group_size(mesh_axis, mesh_shape)
        return _scale_wire(
            wm.swap_cost_ring(p, elems, precision, strategy=self.name),
            _group_bw(mesh_axis, axis_bw))


# ---------------------------------------------------------------------------
# 'pod_tree:<spec>' / 'hierarchical': phased pod-split exchanges
# ---------------------------------------------------------------------------

def _digit_ring(x, axis_name: str, factor: int, stride: int,
                shard_pos: int, mem_pos: int):
    """One sub-factor exchange phase: a full ownership swap within the
    ``factor``-member *digit subgroup* of ``axis_name`` — the devices
    that agree on every axis coordinate except the digit of place value
    ``stride`` (axis index i has digit ``(i // stride) % factor``).

    ``lax.all_to_all`` cannot address a strict subgroup of a named
    axis, so this is built as factor-1 pairwise ``lax.ppermute`` rounds
    (round s shifts blocks s digits ahead *within* each subgroup, i.e.
    a strided permutation of the full axis), matching the tiled
    all_to_all's semantics over the subgroup: received blocks land in
    source-digit order along ``shard_pos``.
    """
    p = lax.psum(1, axis_name)
    if factor <= 1:
        return x
    if x.shape[mem_pos] % factor:
        raise ValueError(
            f"pod-tree swap: mem axis size {x.shape[mem_pos]} not divisible "
            f"by factor {factor} of axis {axis_name!r}")
    idx = lax.axis_index(axis_name)
    digit = (idx // stride) % factor
    blk = x.shape[mem_pos] // factor
    seg = x.shape[shard_pos]
    out_shape = list(x.shape)
    out_shape[mem_pos] = blk
    out_shape[shard_pos] = seg * factor
    own = lax.dynamic_slice_in_dim(x, digit * blk, blk, axis=mem_pos)
    out = jnp.zeros(tuple(out_shape), x.dtype)
    out = lax.dynamic_update_slice_in_dim(out, own, digit * seg,
                                          axis=shard_pos)
    for s in range(1, factor):
        dst_digit = (digit + s) % factor
        send = lax.dynamic_slice_in_dim(x, dst_digit * blk, blk,
                                        axis=mem_pos)
        perm = []
        for i in range(p):
            di = (i // stride) % factor
            perm.append((i, i + (((di + s) % factor) - di) * stride))
        recv = lax.ppermute(send, axis_name, perm)
        src_digit = (digit - s) % factor
        out = lax.dynamic_update_slice_in_dim(out, recv, src_digit * seg,
                                              axis=shard_pos)
    return out


class PodTreeStrategy(Strategy):
    """Phased pod-tree exchange over an arbitrary factorization.

    ``tree`` maps axis name -> factor sequence (most-significant digit
    first); axes of the swap group it does not name get one full-extent
    level. The swap runs one grouped sub-exchange per level in flat
    digit-significance order (mesh-axis tuple order, then per-axis
    factors), then a single local reorder restores row-major group
    order — bit-identical to the one-shot all_to_all, because every
    phase is pure data movement. ``tree=None`` is the classic
    'hierarchical' two-phase pod split (one level per named axis).
    """

    def __init__(self, tree: Optional[Mapping[str, Tuple[int, ...]]] = None):
        self.tree: Optional[Tree] = (
            None if tree is None
            else {a: tuple(int(f) for f in fs) for a, fs in tree.items()})
        if self.tree is not None:
            spec = format_tree_spec(self.tree)
            self.name = POD_TREE_PREFIX + spec
            self.description = (
                f'phased pod-tree exchange over factorization {spec} '
                f'(grouped sub-swaps + one local reorder)')

    def _levels(self, mesh_axis, extent_of):
        """Flatten the tree into ``(axis, factor, stride)`` phases in
        digit-significance order; ``stride`` is the digit's place value
        within its axis. Tree axes not in this swap group are ignored —
        the tree is a per-axis factorization map, and one plan applies
        its single strategy string to swaps over different groups."""
        axes = axis_tuple(mesh_axis)
        levels = []
        for a in axes:
            extent = extent_of(a)
            factors = ((self.tree or {}).get(a) or (extent,))
            prod = 1
            for f in factors:
                prod *= f
            if prod != extent:
                raise ValueError(
                    f"pod_tree factors {factors} for axis {a!r} multiply "
                    f"to {prod}, not its extent {extent}")
            stride = extent
            for f in factors:
                stride //= f
                levels.append((a, int(f), stride))
        return levels

    def swap_axes(self, x, mesh_axis, *, shard_pos, mem_pos):
        levels = [lv for lv in self._levels(
            mesh_axis, lambda a: lax.psum(1, a)) if lv[1] > 1]
        if not levels:
            return x           # extent-1 group: nothing moves
        seg = x.shape[shard_pos]
        for a, f, stride in levels:
            if f == lax.psum(1, a):
                x = lax.all_to_all(x, a, split_axis=mem_pos,
                                   concat_axis=shard_pos, tiled=True)
            else:
                x = _digit_ring(x, a, f, stride, shard_pos, mem_pos)
        if len(levels) == 1:
            return x
        # received shard order is (last phase, ..., first phase, seg);
        # reverse the digits to restore flat row-major group order
        shp = x.shape
        fs = tuple(f for _, f, _ in levels)
        k = len(fs)
        x = x.reshape(shp[:shard_pos] + tuple(reversed(fs)) + (seg,)
                      + shp[shard_pos + 1:])
        perm = (tuple(range(shard_pos))
                + tuple(shard_pos + k - 1 - i for i in range(k))
                + tuple(range(shard_pos + k, x.ndim)))
        return jnp.transpose(x, perm).reshape(shp)

    def cost(self, mesh_axis, mesh_shape, elems, precision, *, axis_bw=None):
        wm_levels = []
        for a, f, stride in self._levels(mesh_axis,
                                         lambda ax: mesh_shape[ax]):
            kind = 'a2a' if f == mesh_shape[a] else 'ring'
            bw = 1.0 if not axis_bw else float(axis_bw.get(a, 1.0))
            if kind == 'ring':
                # a stride-v digit ring's messages travel v x the links
                # (v interleaved subgroups share the physical row), so
                # each element occupies v x the bottleneck bandwidth
                bw *= max(int(stride), 1)
            wm_levels.append((f, kind, bw))
        return wm.swap_cost_tree(tuple(wm_levels), elems, precision,
                                 strategy=self.name)


@functools.lru_cache(maxsize=256)
def _pod_tree_strategy(name: str) -> Strategy:
    return PodTreeStrategy(parse_tree_spec(name[len(POD_TREE_PREFIX):]))


class HierarchicalStrategy(PodTreeStrategy):
    name = 'hierarchical'
    description = ('two-phase pod-split exchange (outer-axis all_to_all, '
                   'inner-axis all_to_all, local reorder)')

    def __init__(self):
        super().__init__(None)


_A2A = register(AllToAllStrategy())
register(PpermuteStrategy())
register(HierarchicalStrategy())
