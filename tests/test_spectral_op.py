"""Fused spectral-operator plans (``fft.plan_op``) and the fftconv
mixer regressions that motivated them.

In-process tests run on a 1x1 mesh (same shard_map program, group size
1). The 16-fake-device matrix — fused vs unfused bitwise identity
across comm strategies, wire dtypes, kernel tiers and ranks, plus
engine serving — runs in a subprocess (_spectral_op_worker.py) so this
process keeps one device.
"""
import os
import subprocess
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.fft as fft
from repro.fft import methods as fftm
from repro.models import ssd
from repro.launch.mesh import make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

RNG = np.random.default_rng(11)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


def _pw_scale(re, im):
    return re * 2.0, im * 2.0


# -- plan_op construction and validation --------------------------------


def test_plan_op_validation(mesh):
    with pytest.raises(ValueError, match="op must be callable"):
        fft.plan_op((16, 32), mesh, op=42)
    with pytest.raises(ValueError, match="spectra_form"):
        fft.plan_op((16, 32), mesh, op=_pw_scale, spectra_form="nope")
    with pytest.raises(ValueError, match="n_spectra"):
        fft.plan_op((16, 32), mesh, op=_pw_scale, n_spectra=-1)
    with pytest.raises(ValueError, match="restore_layout"):
        fft.plan_op((16, 32), mesh, op=_pw_scale, restore_layout=True)
    with pytest.raises(ValueError, match="batch_spec"):
        fft.plan_op((16, 32), mesh, op=_pw_scale, batch_spec="x")


def test_plan_op_derives_padded_spectrum(mesh):
    # real rank>=2 operator plans ALWAYS keep the padded native
    # spectrum interior — the option is derived, never user-set
    op = fft.plan_op((16, 32), mesh, op=_pw_scale, real=True,
                     padded_spectrum=False)
    assert op.padded_spectrum
    op1 = fft.plan_op((256,), mesh, op=_pw_scale, real=True)
    assert not op1.padded_spectrum      # rank 1 has no pencil padding
    assert not op.restore_layout and op.batch_spec is None


def test_apply_operand_validation(mesh):
    op = fft.plan_op((16, 32), mesh, op=_pw_scale, real=True)
    x = jnp.asarray(RNG.standard_normal((16, 32)), jnp.float32)
    with pytest.raises(ValueError, match="runtime spectra"):
        op.apply(x, x)
    with pytest.raises(ValueError, match="real arrays"):
        op.apply(x.astype(jnp.complex64))
    with pytest.raises(ValueError, match="single real arrays"):
        op.apply((x, x))
    with pytest.raises(ValueError, match="does not end with"):
        op.apply(x[:, :16])


# -- fused == unfused on the 1x1 mesh -----------------------------------


@pytest.mark.parametrize("shape", [(256,), (16, 32), (8, 8, 8)])
def test_fused_matches_unfused_real(mesh, shape):
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    k = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                     n_spectra=1, donate=False)
    got = np.asarray(op.apply(x, k))
    axes = tuple(range(len(shape)))
    want = np.fft.irfftn(
        np.fft.rfftn(np.asarray(x, np.float64), axes=axes) *
        np.fft.rfftn(np.asarray(k, np.float64), axes=axes),
        s=shape, axes=axes)
    np.testing.assert_allclose(got, want, atol=3e-4 * np.max(np.abs(want)))
    # same shape/dtype round trip: the fused chain ends where it began
    assert got.shape == shape and got.dtype == np.float32


def test_fused_matches_unfused_complex(mesh):
    shape = (16, 32)
    x = RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)
    op = fft.plan_op(shape, mesh, op=_pw_scale, real=False, donate=False)
    got = np.asarray(op.apply(jnp.asarray(x, jnp.complex64)),
                     np.complex128)
    p = fft.plan(shape, mesh)
    want = np.asarray(p.inverse(p.forward(jnp.asarray(x, jnp.complex64))
                                * 2.0), np.complex128)
    np.testing.assert_allclose(got, want, atol=1e-5 * np.max(np.abs(want)))


def test_baked_spectrum_once(mesh):
    shape = (16, 32)
    k = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                     donate=False, spectra=(k,))
    assert op.bake_count == 0 and op.n_baked == 1 and op.n_spectra == 0
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    y0 = np.asarray(op.apply(x))
    for _ in range(3):
        assert np.array_equal(np.asarray(op.apply(x)), y0)
    assert op.bake_count == 1           # transformed once, ever
    rt = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                     n_spectra=1, donate=False)
    assert np.array_equal(np.asarray(rt.apply(x, k)), y0)


def test_plan_cost_shows_elided_gather(mesh):
    op = fft.plan_op((1024,), mesh, op=_pw_scale, real=True)
    pc = op.plan_cost()
    kinds = [s.kind for s in pc.steps]
    assert "elided" in kinds and "pointwise" in kinds
    elided = [s for s in pc.steps if s.kind == "elided"]
    assert all(s.cycles == 0.0 for s in elided)
    assert "elided" in op.cost_report()


# -- with_options round-trip (satellite: the resolved-options contract) -


OPTION_MATRIX = [
    {"comm": "ppermute"},
    {"comm": "hierarchical"},
    {"overlap_chunks": 2},
    {"kernel": "reference"},
    {"wire_dtype": "fp16"},
    {"donate": False},
    # NOTE: compute_dtype=bf16 is untestable here — real plans hit
    # lax.complex on bf16 pencils (pre-existing, not op-plan specific)
    {"wire_dtype": "bf16"},
]


@pytest.mark.parametrize("ov", OPTION_MATRIX,
                         ids=[f"{k}={v}" for d in OPTION_MATRIX
                              for k, v in d.items()])
def test_with_options_roundtrips_op_plan(mesh, ov):
    k = jnp.asarray(RNG.standard_normal((16, 32)), jnp.float32)
    op = fft.plan_op((16, 32), mesh, op=fft.spectral_mul, real=True,
                     donate=True, spectra=(k,), op_name="conv")
    op2 = op.with_options(**ov)
    assert isinstance(op2, fft.SpectralOp)
    # the op-specific options survive the re-plan...
    assert op2.op is fft.spectral_mul and op2.op_name == "conv"
    assert op2.n_spectra == 0 and op2.n_baked == 1
    assert op2.spectra_form == "plan"
    assert op2.padded_spectrum and not op2.restore_layout
    # ...the override landed...
    for key, val in ov.items():
        assert getattr(op2, key) == val, key
    # ...and everything else carried over resolved
    base = op._options()
    for key, val in op2._options().items():
        if key not in ov and key not in ("spectra",):
            assert val == base[key], key
    xv = RNG.standard_normal((16, 32))
    if ov.get("wire_dtype") == "fp16":
        tol = 5e-3
    elif ov.get("wire_dtype") == "bf16":
        tol = 3e-2
    else:
        tol = 1e-5
    # donating plans consume their operand — fresh array per apply
    a = np.asarray(op.apply(jnp.asarray(xv, jnp.float32)))
    b = np.asarray(op2.apply(jnp.asarray(xv, jnp.float32)))
    np.testing.assert_allclose(b, a, atol=tol * max(np.max(np.abs(a)), 1))
    assert op2.bake_count == 1          # fresh plan baked its own copy


def test_with_options_roundtrips_real_padded_plan(mesh):
    # plain (non-op) real padded_spectrum plans keep the padding knob
    rp = fft.rplan((16, 32), mesh, padded_spectrum=True)
    for ov in ({"comm": "ppermute"}, {"overlap_chunks": 2},
               {"donate": False}):
        rp2 = rp.with_options(**ov)
        assert rp2.real and rp2.padded_spectrum
        assert rp2.spectrum_shape == rp.spectrum_shape


# -- the fftconv mixer regressions --------------------------------------


def _old_fftconv_apply(p, cfg, x):
    """The pre-fix mixer, inlined verbatim: complex transforms built
    from real inputs via a zero imaginary plane, kernel FFT recomputed
    every forward. The new path must match it numerically."""
    import repro.models.layers as L
    B, S, d = x.shape
    h = L.apply_linear(p['wi'], x)
    klen = min(cfg.fftconv_len, S)
    decay = jnp.exp(-jax.nn.softplus(p['decay'].astype(jnp.float32))
                    * jnp.arange(klen, dtype=jnp.float32)[:, None])
    ker = p['kernel'].astype(jnp.float32)[:klen] * decay
    n = 2 * S
    hf = h.astype(jnp.float32).swapaxes(1, 2)
    kf = ker.T
    hr = jnp.pad(hf, ((0, 0), (0, 0), (0, n - S)))
    kr = jnp.pad(kf, ((0, 0), (0, n - klen)))
    hre, him = fftm.apply(hr, jnp.zeros_like(hr), method='four_step')
    kre, kim = fftm.apply(kr, jnp.zeros_like(kr), method='four_step')
    yre = hre * kre - him * kim
    yim = hre * kim + him * kre
    yr, _ = fftm.apply(yre, yim, inverse=True, method='four_step')
    y = yr[..., :S].swapaxes(1, 2).astype(x.dtype)
    return L.apply_linear(p['wo'], y)


def _fftconv_fixture(S=32, d=8, B=2):
    cfg = types.SimpleNamespace(fftconv_len=S)
    p = {
        'wi': {'w': jnp.asarray(RNG.standard_normal((d, d)) / np.sqrt(d),
                                jnp.float32)},
        'kernel': jnp.asarray(RNG.standard_normal((S, d)) * 0.1,
                              jnp.float32),
        'decay': jnp.asarray(RNG.standard_normal(d) * 0.3, jnp.float32),
        'wo': {'w': jnp.asarray(RNG.standard_normal((d, d)) / np.sqrt(d),
                                jnp.float32)},
    }
    x = jnp.asarray(RNG.standard_normal((B, S, d)), jnp.float32)
    return cfg, p, x


def test_fftconv_new_matches_old_fp32(mesh):
    cfg, p, x = _fftconv_fixture()
    old = np.asarray(_old_fftconv_apply(p, cfg, x))
    new = np.asarray(ssd.fftconv_apply(p, cfg, x, mesh=mesh))
    np.testing.assert_allclose(new, old,
                               atol=1e-5 * max(np.max(np.abs(old)), 1))
    local = np.asarray(ssd.fftconv_apply(p, cfg, x))   # mesh=None path
    np.testing.assert_allclose(local, old,
                               atol=1e-5 * max(np.max(np.abs(old)), 1))


def test_fftconv_kernel_fft_baked_once(mesh):
    cfg, p, x = _fftconv_fixture()
    y0 = np.asarray(ssd.fftconv_apply(p, cfg, x, mesh=mesh))
    key = ('baked', 2 * x.shape[1], mesh)
    tok, _refs, plan = ssd._fftconv_plans[key]
    assert plan.bake_count == 1
    for _ in range(3):    # repeated eval: same plan, no rebake
        assert np.array_equal(
            np.asarray(ssd.fftconv_apply(p, cfg, x, mesh=mesh)), y0)
    assert ssd._fftconv_plans[key][2] is plan and plan.bake_count == 1
    # new params -> new token -> fresh bake, exactly once
    p2 = dict(p, kernel=p['kernel'] * 0.5)
    ssd.fftconv_apply(p2, cfg, x, mesh=mesh)
    plan2 = ssd._fftconv_plans[key][2]
    assert plan2 is not plan and plan2.bake_count == 1


def test_fftconv_traced_path_inside_jit(mesh):
    cfg, p, x = _fftconv_fixture()
    eager = np.asarray(ssd.fftconv_apply(p, cfg, x, mesh=mesh))
    jitted = np.asarray(jax.jit(
        lambda pp, xx: ssd.fftconv_apply(pp, cfg, xx, mesh=mesh))(p, x))
    np.testing.assert_allclose(jitted, eager,
                               atol=1e-5 * max(np.max(np.abs(eager)), 1))
    assert ('rt', 2 * x.shape[1], mesh) in ssd._fftconv_plans


def test_fftconv_hermitian_imag_residual(mesh):
    # the real machinery's inverse is exactly real by construction;
    # cross-check: the complex-transform composition of the same conv
    # has ~zero imaginary residual, and its real part matches the
    # fused real path
    cfg, p, x = _fftconv_fixture()
    S, d = x.shape[1], x.shape[2]
    n = 2 * S
    import repro.models.layers as L
    h = L.apply_linear(p['wi'], x)
    klen = min(cfg.fftconv_len, S)
    decay = jnp.exp(-jax.nn.softplus(p['decay'].astype(jnp.float32))
                    * jnp.arange(klen, dtype=jnp.float32)[:, None])
    ker = p['kernel'].astype(jnp.float32)[:klen] * decay
    hr = jnp.pad(h.astype(jnp.float32).swapaxes(1, 2),
                 ((0, 0), (0, 0), (0, n - S)))
    kr = jnp.pad(ker.T, ((0, 0), (0, n - klen)))
    hre, him = fftm.apply(hr, jnp.zeros_like(hr), method='four_step')
    kre, kim = fftm.apply(kr, jnp.zeros_like(kr), method='four_step')
    yre, yim = fft.spectral_mul(hre, him, (kre, kim))
    yr, yi = fftm.apply(yre, yim, inverse=True, method='four_step')
    scale = max(float(jnp.max(jnp.abs(yr))), 1e-9)
    assert float(jnp.max(jnp.abs(yi))) / scale < 1e-5
    rre, rim = fftm.apply_real(hr, method='four_step')
    krr, kri = fftm.apply_real(kr, method='four_step')
    zre, zim = fft.spectral_mul(rre, rim, (krr, kri))
    zr = fftm.apply_real(zre, zim, inverse=True, method='four_step')
    np.testing.assert_allclose(np.asarray(zr), np.asarray(yr),
                               atol=1e-5 * scale)


def test_fftconv_lm_loss_parity(monkeypatch):
    # the fftconv_lm smoke with the OLD mixer vs the NEW fused-plan
    # mixer: loss curves must track (the fix changes execution, not
    # math)
    import dataclasses
    from repro.configs import get_config, smoke_config
    from repro.models import model as M
    from repro.train.optim import adamw_init
    from repro.train.trainstep import make_train_step

    cfg = dataclasses.replace(
        smoke_config(get_config('mamba2-1.3b')),
        block_pattern=('fftconv',), num_layers=2, d_model=16,
        vocab_size=64, fftconv_len=16)
    lm_mesh = make_mesh((1, 1), ('data', 'model'))
    new_mixer = ssd.fftconv_apply

    def batches():
        rng = np.random.default_rng(7)
        for _ in range(6):
            toks = rng.integers(1, cfg.vocab_size, (2, 17)).astype(np.int32)
            yield {'tokens': jnp.asarray(toks[:, :-1]),
                   'labels': jnp.asarray(toks[:, 1:])}

    def run(mixer):
        monkeypatch.setattr(ssd, 'fftconv_apply', mixer)
        step = jax.jit(make_train_step(cfg, lm_mesh, peak_lr=3e-3,
                                       warmup_steps=2, total_steps=6,
                                       param_dtype=jnp.float32))
        params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        opt = adamw_init(params)
        losses = []
        for batch in batches():
            params, opt, m = step(params, opt, batch)
            losses.append(float(m['ce']))
        return losses

    new = run(new_mixer)
    old = run(lambda p, c, x, mesh=None: _old_fftconv_apply(p, c, x))
    np.testing.assert_allclose(new, old, rtol=2e-3, atol=2e-3)


def test_fftconv_gradients_flow(mesh):
    cfg, p, x = _fftconv_fixture()

    def loss(pp):
        return jnp.sum(ssd.fftconv_apply(pp, cfg, x, mesh=mesh) ** 2)

    g = jax.grad(loss)(p)
    for name in ('kernel', 'decay'):
        ga = np.asarray(g[name])
        assert np.all(np.isfinite(ga)) and np.max(np.abs(ga)) > 0, name


def test_fftconv_local_path_differentiates_on_tpu(monkeypatch):
    """Without a mesh the mixer's real transforms stay on the reference
    tier where 'auto' would pick the Pallas kernels (the TPU): training
    differentiates them, and the kernels have no reverse-mode rule."""
    cfg, p, x = _fftconv_fixture()
    monkeypatch.setattr(fftm, 'backend', lambda: 'tpu')

    def loss(pp):
        return jnp.sum(ssd.fftconv_apply(pp, cfg, x) ** 2)

    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(loss))(p))


# -- engine integration (1x1 mesh; the 16-device flow is in the worker) -


def test_engine_register_and_serve_op(mesh):
    from repro.serve.fft_engine import FFTEngine
    shape = (16, 32)
    eng = FFTEngine(shape, mesh)
    k = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    op = eng.register_op("conv", shape=shape, op=fft.spectral_mul,
                         real=True, donate=False, spectra=(k,))
    assert "conv" in eng.registered_ops()
    xv = RNG.standard_normal(shape)
    # the engine re-plans with its own donate policy and consumes the
    # request buffer — take the direct-apply reference first
    want = np.asarray(op.apply(jnp.asarray(xv, jnp.float32)))
    t = eng.submit(jnp.asarray(xv, jnp.float32), op="conv")
    eng.flush()
    assert np.array_equal(np.asarray(t.result()), want)
    with pytest.raises(ValueError, match="direction"):
        eng.submit(jnp.asarray(xv, jnp.float32), op="conv",
                   direction="inv")
    with pytest.raises(ValueError, match="runtime spectra"):
        eng.register_op("bad", shape=shape, op=fft.spectral_mul,
                        real=True, n_spectra=1)
    eng.close()


# -- the 16-fake-device matrix ------------------------------------------


@pytest.mark.slow
def test_spectral_op_worker_16_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests",
                                      "_spectral_op_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, (
        f"worker failed\nstdout:\n{proc.stdout[-4000:]}\n"
        f"stderr:\n{proc.stderr[-4000:]}")
    assert "SPECTRAL_OP_WORKER_OK" in proc.stdout
    assert proc.stdout.count("PASS") >= 25, proc.stdout
