"""Mamba2 SSD (state-space duality) mixer — chunked scan + O(1) decode.

The SSD recurrence per head (state N x P):
    h_t = a_t * h_{t-1} + dt_t * B_t x_t^T          a_t = exp(dt_t * A)
    y_t = C_t . h_t + D * x_t
computed with the chunk decomposition of the Mamba2 paper: within a
chunk the quadratic (attention-like) form with decay mask; across chunks
a sequential lax.scan carries the (H, N, P) state. This gives O(S * Lc)
memory, a tiny HLO (one loop), and an exact match to the sequential
recurrence (tested against the naive oracle in tests/test_models.py).

``fftconv`` at the bottom is the optional paper-tie-in mixer: for a
*constant* per-head decay the SSD kernel is a convolution, and the long
convolution is executed with the repo's own four-step FFT — the paper's
technique inside an LM block (examples/fftconv_lm.py).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import PSpec


def ssd_dims(cfg) -> Tuple[int, int, int, int]:
    di = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim
    H = di // P
    N = cfg.ssm_state
    return di, H, P, N


def ssd_plan(cfg) -> Dict:
    d = cfg.d_model
    di, H, P, N = ssd_dims(cfg)
    G = cfg.ssm_groups
    w = cfg.conv_width
    return {
        'wz': L.linear_plan(d, di, ('embed', 'heads')),
        'wx': L.linear_plan(d, di, ('embed', 'heads')),
        'wb': L.linear_plan(d, G * N, ('embed', None)),
        'wc': L.linear_plan(d, G * N, ('embed', None)),
        'wdt': L.linear_plan(d, H, ('embed', None)),
        'conv_x': PSpec((w, di), (None, 'heads')),
        'conv_b': PSpec((w, G * N), (None, None)),
        'conv_c': PSpec((w, G * N), (None, None)),
        'a_log': PSpec((H,), (None,), 'ssm_a'),
        'dt_bias': PSpec((H,), (None,), 'ssm_dt'),
        'dskip': PSpec((H,), (None,), 'ones'),
        'norm': L.norm_plan(di),
        'wo': L.linear_plan(di, d, ('heads', 'embed')),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv along axis 1. x: (B, S, C); w: (W, C).
    ``state``: (B, W-1, C) prefix (decode); returns (y, new_state)."""
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype)
            for i in range(W))
    return jax.nn.silu(y), xp[:, -(W - 1):, :]


def _ssd_chunk_scan(xh, b, c, dt, a_log, chunk: int):
    """Chunked SSD. xh: (B,S,H,P); b,c: (B,S,G,N); dt: (B,S,H) fp32.
    Returns (y (B,S,H,P) fp32, final state (B,H,N,P) fp32)."""
    B, S0, H, P = xh.shape
    G, N = b.shape[2], b.shape[3]
    Lc = min(chunk, S0)
    pad = (-S0) % Lc
    if pad:        # identity padding: dt=0 => a=1, zero state contribution
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    S = S0 + pad
    nc = S // Lc
    hg = H // G                       # heads per B/C group

    A = -jnp.exp(a_log.astype(jnp.float32))              # (H,)
    xf = xh.astype(jnp.float32).reshape(B, nc, Lc, H, P)
    # expand groups to per-head (head h belongs to group h // hg)
    bh = jnp.repeat(b.astype(jnp.float32), hg, axis=2).reshape(B, nc, Lc, H, N)
    ch = jnp.repeat(c.astype(jnp.float32), hg, axis=2).reshape(B, nc, Lc, H, N)
    dtf = dt.reshape(B, nc, Lc, H)
    la = dtf * A                                          # log a_t, <= 0
    cum = jnp.cumsum(la, axis=2)                          # (B,nc,Lc,H)

    # intra-chunk: M[t,s] = (C_t . B_s) * exp(cum_t - cum_s) * dt_s, s <= t
    gsc = jnp.einsum('bnthi,bnshi->bnhts', ch, bh)        # (B,nc,H,Lc,Lc)
    decay = cum.transpose(0, 1, 3, 2)[..., :, None] - \
        cum.transpose(0, 1, 3, 2)[..., None, :]           # (B,nc,H,Lc,Lc)
    tri = jnp.tril(jnp.ones((Lc, Lc), bool))
    m = jnp.where(tri, gsc * jnp.exp(jnp.where(tri, decay, 0.0)), 0.0)
    m = m * dtf.transpose(0, 1, 3, 2)[..., None, :]       # * dt_s
    y_intra = jnp.einsum('bnhts,bnshp->bnthp', m, xf)

    # per-chunk input to the state: S_loc = sum_s exp(cum_last - cum_s) dt_s B_s x_s
    w_s = jnp.exp(cum[:, :, -1:, :] - cum) * dtf          # (B,nc,Lc,H)
    bx = jnp.einsum('bnshi,bnshp,bnsh->bnhip', bh, xf, w_s)
    a_chunk = jnp.exp(jnp.sum(la, axis=2))                # (B,nc,H)

    def step(h, inp):
        bx_c, ac = inp                                    # (B,H,N,P), (B,H)
        h_new = h * ac[..., None, None] + bx_c
        return h_new, h                                   # emit state *entering* chunk

    h0 = jnp.zeros((B, H, N, P), jnp.float32)
    h_final, h_in = jax.lax.scan(step, h0, (bx.swapaxes(0, 1),
                                            a_chunk.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                            # (B,nc,H,N,P)

    # inter-chunk: y_inter[t] = exp(cum_t) * C_t . h_in
    y_inter = jnp.einsum('bnthi,bnhip->bnthp', ch, h_in)
    y_inter = y_inter * jnp.exp(cum)[..., None]
    y = (y_intra + y_inter).reshape(B, S, H, P)
    return y[:, :S0], h_final


def ssd_apply(p: Dict, cfg, x, *, return_cache: bool = False):
    """Full-sequence SSD block. x: (B, S, d_model). With
    ``return_cache`` also returns the decode cache (final SSM state +
    rolling conv prefixes)."""
    B, S, _ = x.shape
    di, H, P, N = ssd_dims(cfg)
    G = cfg.ssm_groups
    z = L.apply_linear(p['wz'], x)
    xi = L.apply_linear(p['wx'], x)
    bi = L.apply_linear(p['wb'], x)
    ci = L.apply_linear(p['wc'], x)
    dt = L.apply_linear(p['wdt'], x).astype(jnp.float32)
    xi, sx = _causal_conv(xi, p['conv_x'])
    bi, sb = _causal_conv(bi, p['conv_b'])
    ci, sc = _causal_conv(ci, p['conv_c'])
    dt = jax.nn.softplus(dt + p['dt_bias'].astype(jnp.float32))
    xh = xi.reshape(B, S, H, P)
    y, state = _ssd_chunk_scan(xh, bi.reshape(B, S, G, N),
                               ci.reshape(B, S, G, N), dt,
                               p['a_log'], cfg.ssm_chunk)
    y = y + xh.astype(jnp.float32) * p['dskip'].astype(jnp.float32)[:, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = L.apply_norm(p['norm'], y * jax.nn.silu(z))
    out = L.apply_linear(p['wo'], y)
    if return_cache:
        return out, {'state': state, 'conv_x': sx, 'conv_b': sb, 'conv_c': sc}
    return out


def ssd_decode(p: Dict, cfg, x, cache: Dict):
    """One-token decode. x: (B, 1, d); cache: {'state' (B,H,N,P) fp32,
    'conv_x'/'conv_b'/'conv_c' (B, W-1, C) rolling prefixes}."""
    state = cache['state']
    conv_x, conv_b, conv_c = cache['conv_x'], cache['conv_b'], cache['conv_c']
    B = x.shape[0]
    di, H, P, N = ssd_dims(cfg)
    G = cfg.ssm_groups
    z = L.apply_linear(p['wz'], x)
    xi = L.apply_linear(p['wx'], x)
    bi = L.apply_linear(p['wb'], x)
    ci = L.apply_linear(p['wc'], x)
    dt = L.apply_linear(p['wdt'], x).astype(jnp.float32)
    xi, conv_x = _causal_conv(xi, p['conv_x'], conv_x)
    bi, conv_b = _causal_conv(bi, p['conv_b'], conv_b)
    ci, conv_c = _causal_conv(ci, p['conv_c'], conv_c)
    dt = jax.nn.softplus(dt + p['dt_bias'].astype(jnp.float32))[:, 0]  # (B,H)
    A = -jnp.exp(p['a_log'].astype(jnp.float32))
    a = jnp.exp(dt * A)                                   # (B,H)
    xh = xi.reshape(B, H, P).astype(jnp.float32)
    bf = bi.reshape(B, G, N).astype(jnp.float32)
    cf = ci.reshape(B, G, N).astype(jnp.float32)
    hg = H // G
    bfh = jnp.repeat(bf, hg, axis=1)                      # (B,H,N)
    cfh = jnp.repeat(cf, hg, axis=1)
    state = state * a[..., None, None] + \
        (dt[..., None, None] * bfh[..., None] * xh[:, :, None, :])
    y = jnp.einsum('bhi,bhip->bhp', cfh, state)
    y = y + xh * p['dskip'].astype(jnp.float32)[:, None]
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = L.apply_norm(p['norm'], y * jax.nn.silu(z))
    return L.apply_linear(p['wo'], y), {'state': state, 'conv_x': conv_x,
                                        'conv_b': conv_b, 'conv_c': conv_c}


# ---------------------------------------------------------------------------
# FFT long-convolution mixer (paper tie-in; examples/fftconv_lm.py)
# ---------------------------------------------------------------------------

#: (kind, n, mesh) -> cached fftconv operator plan. The runtime entry
#: ('rt') is one n_spectra=1 plan shared by every training step; the
#: baked entry holds (param-identity token, strong param refs, plan) —
#: the refs keep the id()-based token valid for the entry's lifetime.
_fftconv_plans: Dict = {}


def _pick_axes(mesh, n: int):
    """Mesh axes for a length-``n`` rank-1 conv plan: the axes whose
    device product divides BOTH four-step factors (the rank-1 layout
    constraint). Tries all size>1 axes together, then each alone
    (largest first). None -> no distributed plan fits this mesh; the
    caller falls back to the local real-pencil path."""
    from repro.core import twiddle as tw
    n1, n2 = tw.four_step_factors(n)
    live = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    for axes in ((live,) if live else ()) + \
            tuple((a,) for a in sorted(live, key=lambda a: -mesh.shape[a])):
        psize = 1
        for a in axes:
            psize *= mesh.shape[a]
        if n1 % psize == 0 and n2 % psize == 0:
            return axes
    return None if live else (mesh.axis_names[0],)


def _fftconv_op_plan(n: int, mesh, p: Dict, kr, klen: int):
    """The cached fused operator plan for an (n, mesh) conv, or None
    when the mesh cannot host one. Traced kernel (training: the
    spectrum is a function of live parameters) -> the shared
    ``n_spectra=1`` plan, kernel riding as a runtime operand of the
    same single dispatch. Concrete kernel (eval/decode) -> a plan with
    the kernel spectrum BAKED: transformed once (``bake_count``) and
    reused until the parameter arrays change identity."""
    from repro import fft
    axes = _pick_axes(mesh, n)
    if axes is None:
        return None
    if isinstance(kr, jax.core.Tracer):
        key = ('rt', n, mesh)
        pl = _fftconv_plans.get(key)
        if pl is None:
            pl = fft.plan_op((n,), mesh, op=fft.spectral_mul,
                             op_name='fftconv', real=True, n_spectra=1,
                             donate=False, mesh_axes=axes)
            _fftconv_plans[key] = pl
        return pl
    key = ('baked', n, mesh)
    tok = (id(p['kernel']), id(p['decay']), klen)
    ent = _fftconv_plans.get(key)
    if ent is None or ent[0] != tok:
        pl = fft.plan_op((n,), mesh, op=fft.spectral_mul,
                         op_name='fftconv', real=True, donate=False,
                         mesh_axes=axes, spectra=(kr,))
        ent = (tok, (p['kernel'], p['decay']), pl)
        _fftconv_plans[key] = ent
    return ent[2]


def fftconv_plan(cfg) -> Dict:
    d = cfg.d_model
    return {
        'wi': L.linear_plan(d, d, ('embed', 'heads')),
        'kernel': PSpec((cfg.fftconv_len, d), (None, 'heads'), 'emb'),
        'decay': PSpec((d,), (None,), 'zeros'),   # softplus(0): taps at
        # lag 2-4 start alive; 'ones' kills them below grad noise
        'wo': L.linear_plan(d, d, ('heads', 'embed')),
    }


def fftconv_apply(p: Dict, cfg, x, *, mesh=None):
    """y = causal_conv(x, k) via the repo's FFT stack: pad to 2S, fused
    rfft -> spectral multiply -> irfft. The long-conv form of a
    constant-decay SSM — the wsFFT engine as an LM mixer.

    With ``mesh`` the conv runs through a cached :func:`repro.fft.
    plan_op` operator plan: ONE dispatch whose interior spectrum never
    hits a boundary gather. A traced (training) kernel rides as a
    runtime operand of that dispatch; a concrete (eval) kernel's
    spectrum is baked into the plan — transformed once, never
    recomputed per forward. Without a usable mesh the conv uses the
    local REAL pencil transforms (half spectra via
    ``methods.apply_real``) — in no case the old complex transform of
    a zero imaginary plane whose inverse's imaginary half is dropped.

    No multiplicative gate: a pointwise content gate corrupts the
    relative-offset copy path that IS the conv mixer's strength
    (measured: gated version cannot learn period-k copying; ungated
    reaches ~0.3 nats on it)."""
    from repro import fft
    from repro.fft import methods as fftm
    B, S, d = x.shape
    h = L.apply_linear(p['wi'], x)
    klen = min(cfg.fftconv_len, S)
    decay = jnp.exp(-jax.nn.softplus(p['decay'].astype(jnp.float32))
                    * jnp.arange(klen, dtype=jnp.float32)[:, None])
    ker = p['kernel'].astype(jnp.float32)[:klen] * decay          # (klen, d)
    n = 2 * S                         # linear (non-circular) convolution
    hf = h.astype(jnp.float32).swapaxes(1, 2)                     # (B, d, S)
    kf = ker.T                                                    # (d, klen)
    hr = jnp.pad(hf, ((0, 0), (0, 0), (0, n - S)))
    kr = jnp.pad(kf, ((0, 0), (0, n - klen)))
    op = None if mesh is None else _fftconv_op_plan(n, mesh, p, kr, klen)
    if op is not None:
        yr = op.apply(hr, kr) if op.n_spectra else op.apply(hr)
    else:
        # the reference tier: training differentiates this path, and the
        # Pallas kernels have no reverse-mode rule
        hre, him = fftm.apply_real(hr, method='four_step', kernel='reference')
        kre, kim = fftm.apply_real(kr, method='four_step', kernel='reference')
        yre, yim = fft.spectral_mul(hre, him, (kre, kim))
        yr = fftm.apply_real(yre, yim, inverse=True, method='four_step',
                             kernel='reference')
    y = yr[..., :S].swapaxes(1, 2).astype(x.dtype)
    return L.apply_linear(p['wo'], y)
