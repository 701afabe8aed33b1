"""Attention: flash-chunked GQA / sliding-window / MLA + Ulysses SP.

* ``flash_attention`` — online-softmax attention, lax.scan over KV
  chunks: O(S) memory for 32k+ sequences, fp32 accumulators, GQA via a
  (kv_heads, group) head split so repeated KV is never materialized.
* ``mla_*`` — DeepSeek-V2 Multi-head Latent Attention: queries/KV pass
  through low-rank compressions; the decode cache stores only the
  compressed latent (kv_lora + rope dims) per token.
* ``ulysses`` — sequence-parallel attention. This is the paper's pencil
  transpose applied to an LM: activations arrive sequence-sharded over
  the 'model' mesh axis, one ownership swap (repro.comm.swap_axes — the
  exact primitive wsFFT uses between supersteps, under any registered
  strategy) re-shards heads instead of sequence, local attention runs on
  full-length pencils, and a second swap restores sequence sharding;
  ``overlap_chunks`` pipelines the whole thing over head groups.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.comm import overlap as ov
from repro.models import layers as L
from repro.models.layers import PSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core: chunked online-softmax attention (GQA native)
# ---------------------------------------------------------------------------

def _mask(qpos, kpos, *, causal: bool, window: int):
    m = kpos[None, :] >= 0                    # slot -1 = empty (ring cache)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=0, kv_len: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    chunk: int = 1024,
                    q_chunk: int = 1024) -> jnp.ndarray:
    """q: (B, Sq, H, D); k, v: (B, Skv, KH, D) with KH | H.

    Double-blocked online-softmax attention: an outer scan over
    ``q_chunk`` query blocks bounds every probability/accumulator
    intermediate to (B, KH, G, q_chunk, chunk) — without the outer
    block, 128-head 4k-sequence layers materialize ~8 GB score tensors
    per KV chunk under remat (measured on deepseek-v2 train_4k; §Perf).

    ``q_offset``: global position of q[0] (decode: cache length).
    ``kv_len``: optional dynamic valid-length of k/v (ragged decode).
    ``kv_positions``: explicit (Skv,) absolute positions (-1 = empty
    slot) — used by the sliding-window ring cache. Default arange.
    Returns (B, Sq, H, D). Accumulation in fp32.
    """
    B, Sq, H, D = q.shape
    if Sq > q_chunk and Sq % q_chunk == 0:
        qs = q.reshape(B, Sq // q_chunk, q_chunk, H, D).swapaxes(0, 1)
        offs = q_offset + jnp.arange(Sq // q_chunk) * q_chunk

        def qstep(_, qo):
            qb, off = qo
            return None, flash_attention(
                qb, k, v, causal=causal, window=window, q_offset=off,
                kv_len=kv_len, kv_positions=kv_positions, chunk=chunk,
                q_chunk=q_chunk)
        _, out = jax.lax.scan(qstep, None, (qs, offs))
        return out.swapaxes(0, 1).reshape(B, Sq, H, D)
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = D ** -0.5
    out_dtype = q.dtype          # NOT v.dtype: v may be a quantized cache
    q = (q.astype(jnp.float32) * scale).reshape(B, Sq, KH, G, D)
    qpos = q_offset + jnp.arange(Sq)
    all_kpos = jnp.arange(Skv) if kv_positions is None else kv_positions

    if Skv > chunk and Skv % chunk == 0:
        nchunks, C = Skv // chunk, chunk
    else:                      # single pass for short/ragged sequences
        nchunks, C = 1, Skv

    def step(carry, kv):
        m_prev, l_prev, acc = carry
        kc, vc, kpos = kv                       # (B, C, KH, D), (C,)
        s = jnp.einsum('bqhgd,bkhd->bhgqk', q, kc.astype(jnp.float32))
        mask = _mask(qpos, kpos, causal=causal, window=window)
        if kv_len is not None:
            mask &= kpos[None, :] < kv_len
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[..., None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum('bhgqk,bkhd->bhgqd', p, vc.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((B, KH, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KH, G, Sq, D), jnp.float32)
    if nchunks == 1:
        (m, l, acc), _ = step((m0, l0, a0), (k, v, all_kpos))
    else:
        ks = k.reshape(B, nchunks, C, KH, D).swapaxes(0, 1)
        vs = v.reshape(B, nchunks, C, KH, D).swapaxes(0, 1)
        kpos = all_kpos.reshape(nchunks, C)
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (ks, vs, kpos))
    out = acc / jnp.maximum(l[..., None], 1e-30)      # (B, KH, G, Sq, D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Ulysses sequence parallelism (reuses the wsFFT transpose engine)
# ---------------------------------------------------------------------------

def ulysses_attention(q, k, v, mesh, *, seq_axis: str = 'model',
                      batch_spec=P(), causal: bool = True, window: int = 0,
                      chunk: int = 1024, comm_strategy: str = 'all_to_all',
                      overlap_chunks: int = 1) -> jnp.ndarray:
    """Attention over sequence-sharded activations.

    In-specs: q/k/v sharded (batch..., seq/p, heads, D) over ``seq_axis``.
    Inside shard_map: swap seq<->heads via the same ownership exchange
    the FFT supersteps use (``repro.comm``, any registered
    ``comm_strategy``), attend over the full sequence with heads/p local
    heads, swap back. KV heads that don't divide p are all-gathered
    instead (MQA/GQA fallback).

    ``overlap_chunks > 1`` pipelines the whole exchange-attend-exchange
    over head groups (heads are independent), so chunk i+1's attention
    overlaps chunk i's collectives; requires both H and KH divisible by
    ``overlap_chunks * p`` (falls back to the unpipelined path
    otherwise).
    """
    p = mesh.shape[seq_axis]
    H, KH = q.shape[-2], k.shape[-2]
    if H % p:
        raise ValueError(f'{H} heads not divisible by SP degree {p}')
    spec = P(*batch_spec, seq_axis, None, None)
    # NB: 'auto' here means the default schedule, not cost-selection —
    # the cost model drives choices at the fft.plan layer only
    strategy = comm.resolve(comm_strategy)

    def swap_in(t):    # seq (axis -3) sharded -> heads (axis -2) sharded
        return strategy.swap_axes(t, seq_axis, shard_pos=t.ndim - 3,
                                  mem_pos=t.ndim - 2)

    def swap_out(t):   # heads sharded -> seq sharded
        return strategy.swap_axes(t, seq_axis, shard_pos=t.ndim - 2,
                                  mem_pos=t.ndim - 3)

    def local(ql, kl, vl):
        if (overlap_chunks > 1 and H % (overlap_chunks * p) == 0
                and KH % (overlap_chunks * p) == 0):
            # chunk q/k/v by the SAME head groups so the positional GQA
            # pairing inside each chunk matches the global one (groups
            # nest within chunks since KH % overlap_chunks == 0)
            def stage(qc, kc, vc):
                qc, kc, vc = swap_in(qc), swap_in(kc), swap_in(vc)
                o = flash_attention(qc, kc, vc, causal=causal,
                                    window=window, chunk=chunk)
                return swap_out(o)
            return ov.pipelined(overlap_chunks, ql.ndim - 2, stage,
                                ql, kl, vl)
        ql = swap_in(ql)
        if KH % p == 0:
            kl = swap_in(kl)
            vl = swap_in(vl)
        else:
            # MQA/GQA with KH < p: gather the sequence, then slice the
            # kv head(s) THIS device's contiguous q-head block maps to —
            # pairing local q heads positionally with the gathered KH
            # axis would scramble the GQA grouping.
            kl = jax.lax.all_gather(kl, seq_axis, axis=kl.ndim - 3, tiled=True)
            vl = jax.lax.all_gather(vl, seq_axis, axis=vl.ndim - 3, tiled=True)
            Hl = H // p
            group = H // KH                     # q heads per kv head
            if Hl % group and group % Hl:
                raise ValueError(f'q-head shard {Hl} incompatible with '
                                 f'GQA group {group}')
            count = max(1, Hl // group)
            start = (jax.lax.axis_index(seq_axis) * Hl) // group
            kl = jax.lax.dynamic_slice_in_dim(kl, start, count, axis=kl.ndim - 2)
            vl = jax.lax.dynamic_slice_in_dim(vl, start, count, axis=vl.ndim - 2)
        o = flash_attention(ql, kl, vl, causal=causal, window=window, chunk=chunk)
        return swap_out(o)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# GQA block (plan + apply); covers dense/local/encoder variants
# ---------------------------------------------------------------------------

def gqa_plan(cfg) -> Dict:
    d, H, KH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        'wq': L.linear_plan(d, H * hd, ('embed', 'heads'), bias=cfg.qkv_bias),
        'wk': L.linear_plan(d, KH * hd, ('embed', 'kv_heads'), bias=cfg.qkv_bias),
        'wv': L.linear_plan(d, KH * hd, ('embed', 'kv_heads'), bias=cfg.qkv_bias),
        'wo': L.linear_plan(H * hd, d, ('heads', 'embed')),
    }


def gqa_qkv(p: Dict, cfg, x, positions):
    """Project + rope. x: (B, S, D) -> q (B,S,H,hd), k/v (B,S,KH,hd)."""
    B, S, _ = x.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = L.apply_linear(p['wq'], x).reshape(B, S, H, hd)
    k = L.apply_linear(p['wk'], x).reshape(B, S, KH, hd)
    v = L.apply_linear(p['wv'], x).reshape(B, S, KH, hd)
    if cfg.pos_kind == 'mrope':
        q = L.apply_mrope(q, positions, theta=cfg.rope_theta,
                          sections=cfg.mrope_sections)
        k = L.apply_mrope(k, positions, theta=cfg.rope_theta,
                          sections=cfg.mrope_sections)
    elif cfg.pos_kind == 'rope':
        q = L.apply_rope(q, positions, theta=cfg.rope_theta)
        k = L.apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def gqa_apply(p: Dict, cfg, x, positions, *, window: int = 0,
              mesh=None, sp: bool = False, batch_spec=P()) -> jnp.ndarray:
    """Full-sequence (train/prefill) GQA attention."""
    B, S, _ = x.shape
    q, k, v = gqa_qkv(p, cfg, x, positions)
    if sp and mesh is not None:
        o = ulysses_attention(q, k, v, mesh, causal=cfg.causal, window=window,
                              batch_spec=batch_spec, chunk=cfg.attn_chunk)
    else:
        o = flash_attention(q, k, v, causal=cfg.causal, window=window,
                            chunk=cfg.attn_chunk)
    return L.apply_linear(p['wo'], o.reshape(B, S, -1))


def gqa_prefill(p: Dict, cfg, x, positions, *, window: int = 0,
                cache_cap: Optional[int] = None, mesh=None, sp: bool = False,
                batch_spec=P()):
    """Full-sequence attention that also returns the decode cache.
    For windowed attention the cache keeps only the last min(W, S)
    tokens (+ their absolute positions) in ring order."""
    B, S, _ = x.shape
    q, k, v = gqa_qkv(p, cfg, x, positions)
    if sp and mesh is not None:
        o = ulysses_attention(q, k, v, mesh, causal=cfg.causal, window=window,
                              batch_spec=batch_spec, chunk=cfg.attn_chunk)
    else:
        o = flash_attention(q, k, v, causal=cfg.causal, window=window,
                            chunk=cfg.attn_chunk)
    out = L.apply_linear(p['wo'], o.reshape(B, S, -1))
    if window:
        W = window if cache_cap is None else min(window, cache_cap)
        if S >= W:
            keep = S - W
            kpos = jnp.arange(keep, S, dtype=jnp.int32)
            slot = kpos % W            # ring order: slot = pos % W
            inv = jnp.zeros((W,), jnp.int32).at[slot].set(jnp.arange(W))
            cache = {'k': k[:, keep:][:, inv], 'v': v[:, keep:][:, inv],
                     'kpos': jnp.zeros((W,), jnp.int32).at[slot].set(kpos)}
        else:                          # prefix shorter than the window
            pad = W - S
            cache = {'k': jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                     'v': jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
                     'kpos': jnp.concatenate(
                         [jnp.arange(S, dtype=jnp.int32),
                          jnp.full((pad,), -1, jnp.int32)])}
    else:
        cap = cache_cap or S
        pad = cap - S
        cache = {'k': jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                 'v': jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))}
    return out, cache


def gqa_decode_ring(p: Dict, cfg, x, cache, cache_len, *, window: int):
    """One-token decode against the sliding-window ring cache.
    cache: {'k','v': (B, W, KH, hd), 'kpos': (W,) int32}."""
    B = x.shape[0]
    W = cache['k'].shape[1]
    positions = jnp.broadcast_to(cache_len, (B, 1))
    q, k, v = gqa_qkv(p, cfg, x, positions)
    slot = cache_len % W
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache['k'], k.astype(cache['k'].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache['v'], v.astype(cache['v'].dtype), slot, axis=1)
    kpos = jax.lax.dynamic_update_slice_in_dim(
        cache['kpos'], cache_len[None].astype(jnp.int32), slot, axis=0)
    o = flash_attention(q, ck, cv, causal=True, window=window,
                        q_offset=cache_len, kv_positions=kpos,
                        chunk=ck.shape[1])
    out = L.apply_linear(p['wo'], o.reshape(B, 1, -1))
    return out, {'k': ck, 'v': cv, 'kpos': kpos}


def gqa_decode(p: Dict, cfg, x, cache_k, cache_v, cache_len, *,
               window: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode. x: (B, 1, D); caches (B, S_max, KH, hd).
    Returns (out, new_k_cache, new_v_cache)."""
    B = x.shape[0]
    if cfg.pos_kind == 'mrope':   # text continuation: all three streams advance
        positions = jnp.broadcast_to(cache_len, (3, B, 1))
    else:
        positions = jnp.broadcast_to(cache_len, (B, 1))
    q, k, v = gqa_qkv(p, cfg, x, positions)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), cache_len, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), cache_len, axis=1)
    # single pass (chunk = full cache): with a seq-sharded cache the
    # softmax reductions become tiny all-reduces instead of per-chunk
    # slices across shard boundaries
    o = flash_attention(q, cache_k, cache_v, causal=True, window=window,
                        q_offset=cache_len, kv_len=cache_len + 1,
                        chunk=cache_k.shape[1])
    return L.apply_linear(p['wo'], o.reshape(B, 1, -1)), cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): low-rank Q/KV with decoupled RoPE
# ---------------------------------------------------------------------------

def mla_plan(cfg) -> Dict:
    d, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nh, rh, vh = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.v_head_dim
    return {
        'wq_a': L.linear_plan(d, qr, ('embed', None)),
        'q_norm': L.norm_plan(qr),
        'wq_b': L.linear_plan(qr, H * (nh + rh), (None, 'heads')),
        'wkv_a': L.linear_plan(d, kvr + rh, ('embed', 'kv_lora')),
        'kv_norm': L.norm_plan(kvr),
        'wkv_b': L.linear_plan(kvr, H * (nh + vh), ('kv_lora', 'heads')),
        'wo': L.linear_plan(H * vh, d, ('heads', 'embed')),
    }


def _mla_qkv_from_latent(p, cfg, q_in, latent, k_rope):
    """latent: (B, T, kvr) normalized; k_rope: (B, T, 1, rh) roped."""
    B, Sq = q_in.shape[:2]
    T = latent.shape[1]
    H = cfg.num_heads
    nh, rh, vh = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.v_head_dim
    kv = L.apply_linear(p['wkv_b'], latent).reshape(B, T, H, nh + vh)
    k_nope, v = kv[..., :nh], kv[..., nh:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, H, rh)).astype(k_nope.dtype)],
        axis=-1)
    return k, v


def mla_apply(p: Dict, cfg, x, positions) -> jnp.ndarray:
    B, S, _ = x.shape
    H = cfg.num_heads
    nh, rh, vh = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.v_head_dim
    q = L.apply_linear(p['wq_b'],
                       L.apply_norm(p['q_norm'], L.apply_linear(p['wq_a'], x)))
    q = q.reshape(B, S, H, nh + rh)
    q_nope, q_rope = q[..., :nh], q[..., nh:]
    q_rope = L.apply_rope(q_rope, positions, theta=cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    kv_a = L.apply_linear(p['wkv_a'], x)
    latent = L.apply_norm(p['kv_norm'], kv_a[..., :cfg.kv_lora_rank])
    k_rope = L.apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions,
                          theta=cfg.rope_theta)
    k, v = _mla_qkv_from_latent(p, cfg, x, latent, k_rope)
    # pad v to qk head dim for the shared flash kernel, slice after
    if vh < nh + rh:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nh + rh - vh)))
    o = flash_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)[..., :vh]
    return L.apply_linear(p['wo'], o.reshape(B, S, H * vh))


def mla_prefill(p: Dict, cfg, x, positions, *, cache_cap: Optional[int] = None):
    """Full-sequence MLA that also returns the compressed decode cache."""
    B, S, _ = x.shape
    out = mla_apply(p, cfg, x, positions)
    kv_a = L.apply_linear(p['wkv_a'], x)
    latent = L.apply_norm(p['kv_norm'], kv_a[..., :cfg.kv_lora_rank])
    k_rope = L.apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions,
                          theta=cfg.rope_theta)[:, :, 0, :]
    cap = cache_cap or S
    pad = cap - S
    cache = {'latent': jnp.pad(latent, ((0, 0), (0, pad), (0, 0))),
             'krope': jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))}
    return out, cache


def mla_decode(p: Dict, cfg, x, cache_latent, cache_krope, cache_len):
    """Decode with the *compressed* cache: (B, S_max, kvr) latents +
    (B, S_max, rh) roped shared key — the MLA memory win."""
    B = x.shape[0]
    H = cfg.num_heads
    nh, rh, vh = cfg.qk_nope_dim, cfg.rope_head_dim, cfg.v_head_dim
    positions = jnp.broadcast_to(cache_len, (B, 1))
    q = L.apply_linear(p['wq_b'],
                       L.apply_norm(p['q_norm'], L.apply_linear(p['wq_a'], x)))
    q = q.reshape(B, 1, H, nh + rh)
    q_nope, q_rope = q[..., :nh], q[..., nh:]
    q_rope = L.apply_rope(q_rope, positions, theta=cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    kv_a = L.apply_linear(p['wkv_a'], x)
    latent = L.apply_norm(p['kv_norm'], kv_a[..., :cfg.kv_lora_rank])
    k_rope_new = L.apply_rope(kv_a[..., None, cfg.kv_lora_rank:], positions,
                              theta=cfg.rope_theta)[:, :, 0, :]
    cache_latent = jax.lax.dynamic_update_slice_in_dim(
        cache_latent, latent.astype(cache_latent.dtype), cache_len, axis=1)
    cache_krope = jax.lax.dynamic_update_slice_in_dim(
        cache_krope, k_rope_new.astype(cache_krope.dtype), cache_len, axis=1)

    k, v = _mla_qkv_from_latent(p, cfg, x, cache_latent.astype(x.dtype),
                                cache_krope.astype(x.dtype)[:, :, None, :])
    if vh < nh + rh:
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nh + rh - vh)))
    o = flash_attention(q, k, v, causal=True, q_offset=cache_len,
                        kv_len=cache_len + 1, chunk=k.shape[1])[..., :vh]
    out = L.apply_linear(p['wo'], o.reshape(B, 1, H * vh))
    return out, cache_latent, cache_krope
