"""Mixture-of-Experts FFN: top-k routing, capacity-bounded scatter
dispatch, expert parallelism over the 'model' mesh axis.

Dispatch is *sort + scatter* (MegaBlocks/MaxText-style), never the
GShard (tokens, experts, capacity) one-hot tensor — at deepseek scale
(top-6 of 160 at 32k tokens) that dense tensor is ~1e13 elements while
the scatter path materializes only the (E, C, D) expert buffers, i.e.
exactly top_k * capacity_factor x the token activations.

EP is the paper's row all-to-all: the (groups, E, C, D) dispatch buffer
is sharding-constrained to put E on 'model' while tokens arrive
data-sharded — under pjit XLA lowers the re-sharding to an all-to-all
along 'model', the same collective wsFFT issues between supersteps. An
explicit shard_map variant using repro.comm.swap_axes directly (any
registered strategy, optional capacity-chunked compute/comm overlap) is
provided for the perf study (moe_ep_explicit).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.models.layers import PSpec


def moe_plan(cfg) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    plan = {
        'router': PSpec((d, E), ('embed', None), 'lin'),
        'wi': PSpec((E, d, 2 * f), ('expert', 'embed', 'mlp')),
        'wo': PSpec((E, f, d), ('expert', 'mlp', 'embed')),
    }
    if cfg.num_shared_experts:
        plan['shared'] = L.mlp_plan(d, cfg.num_shared_experts * f)
    return plan


def capacity(tokens_per_group: int, cfg) -> int:
    c = int(math.ceil(tokens_per_group * cfg.top_k * cfg.capacity_factor
                      / cfg.num_experts))
    return max(c, cfg.top_k)


def route(router_w, x, cfg):
    """x: (G, T, d). Returns (gates (G,T,K) fp32, idx (G,T,K) int32,
    probs (G,T,E) fp32 for the aux loss)."""
    logits = jnp.einsum('gtd,de->gte', x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32), probs


def _dispatch_indices(idx, E: int, C: int):
    """idx: (T, K) expert assignment. Returns (order (T*K,), dest (T*K,),
    keep (T*K,) bool) — entry j of the *sorted* stream goes to flat
    buffer slot dest[j] iff keep[j] (capacity not exceeded)."""
    TK = idx.shape[0] * idx.shape[1]
    e_flat = idx.reshape(TK)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    start = jnp.searchsorted(e_sorted, jnp.arange(E), side='left')
    pos = jnp.arange(TK) - start[e_sorted]
    keep = pos < C
    dest = jnp.where(keep, e_sorted * C + pos, E * C)   # E*C = drop slot
    return order, dest, keep


def use_gathered(w, rules, axes):
    """Constrain a weight *at its use site* to the TP-only layout (FSDP
    axis unsharded). Without this, XLA may contract the FSDP-sharded
    d_model axis and ALL-REDUCE the (tokens x d_ff) output — for the MoE
    dispatched-hidden that is a 7 GB x n_layers fp32 all-reduce per step
    (measured on dbrx-132b); gathering the E/tp expert slice is 264 MB.
    """
    if rules is None:
        return w
    from repro.parallel import constrain
    return constrain(w, rules, axes)


def moe_apply(p: Dict, cfg, x, *, rules=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss). Groups = batch rows (each row's
    tokens share a capacity pool; rows are data-parallel shards).

    All steps run on batched (G, ...) arrays with explicit sharding
    constraints: groups over 'batch', experts over 'model' on BOTH
    matmul operands (a model-replicated dispatch buffer makes every
    device multiply all E*C rows by its local expert — 16x wasted MXU
    flops, measured on dbrx-132b)."""
    B, S, d = x.shape
    K, E = cfg.top_k, cfg.num_experts
    C = capacity(S, cfg)
    gates, idx, probs = route(p['router'], x, cfg)
    wi = use_gathered(p['wi'], rules, ('expert', None, 'mlp'))
    wo = use_gathered(p['wo'], rules, ('expert', 'mlp', None))

    order, dest, keep = jax.vmap(
        lambda ig: _dispatch_indices(ig, E, C))(idx)     # (B, S*K) each
    tok = order // K
    bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
    buf = jnp.zeros((B, E * C + 1, d), x.dtype)
    buf = buf.at[bidx, dest].set(x[bidx, tok])
    buf = buf[:, :E * C].reshape(B, E, C, d)
    buf = use_gathered(buf, rules, ('batch', 'expert', None, None))
    h = jnp.einsum('becd,edf->becf', buf, wi.astype(buf.dtype),
                   preferred_element_type=jnp.float32).astype(buf.dtype)
    h = use_gathered(h, rules, ('batch', 'expert', None, None))
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    out = jnp.einsum('becf,efd->becd', h, wo.astype(h.dtype),
                     preferred_element_type=jnp.float32).astype(buf.dtype)
    out = use_gathered(out, rules, ('batch', 'expert', None, None))
    out = jnp.concatenate([out.reshape(B, E * C, d),
                           jnp.zeros((B, 1, d), out.dtype)], axis=1)
    y_sorted = out[bidx, dest] * keep[..., None].astype(out.dtype)
    gate_sorted = jnp.take_along_axis(
        gates.reshape(B, S * K), order, axis=1).astype(out.dtype)
    y = jnp.zeros((B, S, d), out.dtype)
    y = y.at[bidx, tok].add(y_sorted * gate_sorted[..., None])
    if rules is not None:
        from repro.parallel import constrain
        y = constrain(y, rules, ('batch', None, None))
    if 'shared' in p:
        y = y + L.apply_mlp(p['shared'], x)
    # load-balance loss: E * sum_e fraction_e * mean_prob_e
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
    frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1)) / cfg.top_k
    pmean = jnp.mean(probs, axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(frac * pmean)
    return y, aux


# ---------------------------------------------------------------------------
# Explicit-EP variant: shard_map + the wsFFT transpose engine
# ---------------------------------------------------------------------------

def moe_ep_explicit(p: Dict, cfg, x, mesh, *, ep_axis: str = 'model',
                    batch_spec=P('data'), fsdp_axes=None,
                    comm_strategy: str = 'all_to_all',
                    overlap_chunks: int = 1
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Same math, but every re-sharding is an explicit
    repro.comm ownership swap (``comm_strategy`` picks the schedule;
    default the tiled all_to_all) along the EP axis — the identical
    primitive wsFFT uses between supersteps — plus an explicit
    all-gather of the FSDP-sharded expert weights at use.
    ``overlap_chunks > 1`` pipelines dispatch-a2a -> expert FFN ->
    return-a2a over capacity chunks (repro.comm.overlap), so chunk
    i+1's expert matmul overlaps chunk i's exchanges; the expert
    capacity itself never depends on the knob (chunking falls back to
    the unpipelined path when the capacity doesn't split evenly).

    This is the production train/serve path: under pure pjit XLA's
    sharding propagation either all-reduces the dispatched-hidden
    activations (3.8 TB/step fp32 on dbrx-132b), replicates the expert
    matmul over the EP axis (16x MXU flops), or replicates the scatter
    (21 TB) — all measured. The shard_map version pins the exact
    schedule: local scatter -> EP all_to_all -> local expert matmul ->
    reverse all_to_all -> local combine; AD transposes it to the
    mirror-image schedule with reduce-scattered weight gradients.
    """
    from repro import comm
    from repro.comm import overlap as ov
    # NB: 'auto' here means the default schedule, not cost-selection —
    # the cost model drives choices at the fft.plan layer only
    strategy = comm.resolve(comm_strategy)
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, (E, ep)
    gates, idx, probs = route(p['router'], x, cfg)
    gates = gates.astype(x.dtype)
    # shard the sequence over the EP axis into the dispatch: tokens
    # arriving model-REPLICATED make all ep replicas dispatch identical
    # copies into the all_to_all — 16x duplicated expert flops AND wire
    # bytes (measured on dbrx-132b). S=1 decode stays replicated.
    seq_shard = ep_axis if (S % ep == 0 and S > 1) else None

    def local(xl, gl, il, wi_l, wo_l):
        if fsdp_axes is not None:        # gather the weight's d_model shard
            wi_l = jax.lax.all_gather(wi_l, fsdp_axes, axis=1, tiled=True)
            wo_l = jax.lax.all_gather(wo_l, fsdp_axes, axis=2, tiled=True)
        Bl, Sl, _ = xl.shape
        C = capacity(Sl * Bl, cfg)
        C = ((C + ep - 1) // ep) * ep                  # divisible for a2a
        # capacity must NOT depend on the pipelining knob (it would
        # change token-drop behavior); chunk only when C splits evenly
        chunks = overlap_chunks if C % max(1, overlap_chunks) == 0 else 1
        xf = xl.reshape(Bl * Sl, d)
        order, dest, keep = _dispatch_indices(il.reshape(Bl * Sl, K), E, C)
        tok = order // K
        buf = jnp.zeros((E * C + 1, d), xl.dtype).at[dest].set(xf[tok])
        buf = buf[:E * C].reshape(E, C, d)

        def expert_ffn(bufc):
            # EP all-to-all: E sharded, capacity gathered (the FFT
            # transpose): split axis 0 (experts), concat axis 1 (capacity)
            bufc = strategy.swap_axes(bufc, ep_axis, shard_pos=1,
                                      mem_pos=0)   # (E/ep, C*ep, d)
            h = jnp.einsum('ecd,edf->ecf', bufc, wi_l.astype(bufc.dtype),
                           preferred_element_type=jnp.float32
                           ).astype(bufc.dtype)
            g, u = jnp.split(h, 2, axis=-1)
            o = jnp.einsum('ecf,efd->ecd', jax.nn.silu(g) * u,
                           wo_l.astype(bufc.dtype),
                           preferred_element_type=jnp.float32
                           ).astype(bufc.dtype)
            return strategy.swap_axes(o, ep_axis, shard_pos=0,
                                      mem_pos=1)   # (E, C, d)

        # every capacity row is independent through the expert FFN, so
        # the exchange->matmul->exchange pipeline chunks along capacity
        out = ov.pipelined(chunks, 1, expert_ffn, buf)
        out = jnp.concatenate([out.reshape(E * C, d),
                               jnp.zeros((1, d), out.dtype)], axis=0)
        y_sorted = out[dest] * keep[:, None].astype(out.dtype)
        gate_sorted = gl.reshape(Bl * Sl * K)[order].astype(out.dtype)
        y = jnp.zeros((Bl * Sl, d), out.dtype).at[tok].add(
            y_sorted * gate_sorted[:, None])
        return y.reshape(Bl, Sl, d)

    xspec = P(*batch_spec, seq_shard, None)
    wspec_i = P(ep_axis, fsdp_axes, None)
    wspec_o = P(ep_axis, None, fsdp_axes)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(xspec, xspec, xspec, wspec_i, wspec_o),
        out_specs=xspec, check_vma=False)
    y = fn(x, gates, idx, p['wi'], p['wo'])
    if 'shared' in p:
        y = y + L.apply_mlp(p['shared'], x)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
    frac = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1)) / cfg.top_k
    pmean = jnp.mean(probs, axis=(0, 1))
    aux = cfg.num_experts * jnp.sum(frac * pmean)
    return y, aux
