"""Worker script: fused spectral-operator plans on 16 fake devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_spectral_op_worker.py
Exits 0 on success; prints PASS lines per case.

The acceptance contract: ``fft.plan_op`` output is BIT-IDENTICAL to
the unfused composition ``rp.inverse(pw(rp.forward(x), rp.forward(k)))``
with a jitted ``pw`` built on :func:`fft.spectral_mul` — across comm
strategies, wire dtypes (native bitwise; fp16/bf16 bitwise against the
same-wire unfused composition and within wire tolerance of fp32),
kernel tiers, ranks 1-3, real and complex plans, runtime and baked
spectra, batch broadcasting, and overlap pipelining. Plus the serving
integration: operator plans registered on an FFTEngine dispatch as one
coalesced fused group, bitwise equal to direct ``apply``.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(23)
SHAPES = {1: (1024,), 2: (32, 64), 3: (16, 16, 16)}
STRATEGIES = ("all_to_all", "ppermute", "hierarchical")

#: wire-format tolerance vs the fp32 composition (PR-7 accuracy study:
#: the deviation IS the wire quantization, not a fused-plan artifact)
WIRE_RTOL = {"fp16": 5e-3, "bf16": 3e-2}


@jax.jit
def _pw(y, k):
    """The unfused pointwise stage: spectral_mul on complex spectra,
    jitted so its contraction pinning compiles exactly as the fused
    plan's interior does."""
    re, im = fft.spectral_mul(jnp.real(y), jnp.imag(y),
                              (jnp.real(k), jnp.imag(k)))
    return jax.lax.complex(re, im)


def unfused_real(shape, mesh, x, k, **kw):
    rp = fft.rplan(shape, mesh,
                   padded_spectrum=len(shape) > 1, **kw)
    return np.asarray(rp.inverse(_pw(rp.forward(x), rp.forward(k))))


def unfused_complex(shape, mesh, x, k, **kw):
    p = fft.plan(shape, mesh, **kw)
    return np.asarray(p.inverse(_pw(p.forward(x), p.forward(k))))


def np_conv(x, k, rank):
    axes = tuple(range(-rank, 0))
    return np.fft.irfftn(np.fft.rfftn(x, axes=axes)
                         * np.fft.rfftn(k, axes=axes),
                         s=x.shape[-rank:], axes=axes)


def check_bitwise(name, fused, unfused):
    assert fused.shape == unfused.shape, (name, fused.shape, unfused.shape)
    assert np.array_equal(fused, unfused), (
        f"{name}: fused != unfused, maxerr "
        f"{np.max(np.abs(fused - unfused)):.3e}")
    print(f"PASS {name} bitwise")


def check_strategy_matrix(mesh):
    for rank, shape in SHAPES.items():
        x = RNG.standard_normal(shape).astype(np.float32)
        k = RNG.standard_normal(shape).astype(np.float32)
        want = np_conv(x, k, rank)
        for strategy in STRATEGIES:
            op = fft.plan_op(shape, mesh, op=fft.spectral_mul,
                             real=True, n_spectra=1, comm=strategy)
            got = np.asarray(op.apply(jnp.asarray(x), jnp.asarray(k)))
            assert not np.iscomplexobj(got)
            ref = unfused_real(shape, mesh, jnp.asarray(x), jnp.asarray(k),
                               comm=strategy)
            check_bitwise(f"rank{rank} comm={strategy} real", got, ref)
            err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-30)
            assert err < 3e-4, (rank, strategy, err)
        print(f"PASS rank{rank} fused conv matches numpy")


def check_complex(mesh):
    for rank in (1, 3):
        shape = SHAPES[rank]
        x = (RNG.standard_normal(shape)
             + 1j * RNG.standard_normal(shape)).astype(np.complex64)
        k = (RNG.standard_normal(shape)
             + 1j * RNG.standard_normal(shape)).astype(np.complex64)
        op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=False,
                         n_spectra=1)
        got = np.asarray(op.apply(jnp.asarray(x), jnp.asarray(k)))
        ref = unfused_complex(shape, mesh, jnp.asarray(x), jnp.asarray(k))
        check_bitwise(f"rank{rank} complex", got, ref)
        # planar operands return planar, same bits
        gr, gi = op.apply((jnp.real(x), jnp.imag(x)), jnp.asarray(k))
        assert np.array_equal(np.asarray(gr), got.real)
        assert np.array_equal(np.asarray(gi), got.imag)
        print(f"PASS rank{rank} complex planar form")


def check_baked(mesh):
    for rank in (1, 2):
        shape = SHAPES[rank]
        x = RNG.standard_normal(shape).astype(np.float32)
        k = RNG.standard_normal(shape).astype(np.float32)
        rt = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                         n_spectra=1)
        want = np.asarray(rt.apply(jnp.asarray(x), jnp.asarray(k)))
        # 'plan' form: baked through this plan's own forward
        bp = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                         spectra=(k,))
        got = np.asarray(bp.apply(jnp.asarray(x)))
        check_bitwise(f"rank{rank} baked(plan) == runtime", got, want)
        for _ in range(3):
            bp.apply(jnp.asarray(x))
        assert bp.bake_count == 1, bp.bake_count
        # 'spectrum' form: np.fft.rfftn-order input, mapped (pure
        # indexing) into the native layout
        ks = np.fft.rfftn(k, axes=tuple(range(-rank, 0)))
        bs = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                         spectra=(ks,), spectra_form='spectrum')
        got_s = np.asarray(bs.apply(jnp.asarray(x)))
        err = np.max(np.abs(got_s - want)) / max(np.max(np.abs(want)),
                                                 1e-30)
        assert err < 3e-4, (rank, err)
        print(f"PASS rank{rank} baked(spectrum) rel_err={err:.2e} "
              f"bake_count={bs.bake_count}")


def check_wire_dtypes(mesh):
    shape = SHAPES[2]
    x = RNG.standard_normal(shape).astype(np.float32)
    k = RNG.standard_normal(shape).astype(np.float32)
    fp32 = unfused_real(shape, mesh, jnp.asarray(x), jnp.asarray(k))
    for wd in ("fp16", "bf16"):
        op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                         n_spectra=1, wire_dtype=wd)
        got = np.asarray(op.apply(jnp.asarray(x), jnp.asarray(k)))
        ref = unfused_real(shape, mesh, jnp.asarray(x), jnp.asarray(k),
                           wire_dtype=wd)
        check_bitwise(f"wire={wd} vs same-wire unfused", got, ref)
        rel = np.max(np.abs(got - fp32)) / max(np.max(np.abs(fp32)), 1e-30)
        assert rel < WIRE_RTOL[wd], (wd, rel)
        print(f"PASS wire={wd} vs fp32 rel_err={rel:.2e}")


def check_kernel_tiers(mesh):
    shape = SHAPES[2]
    x = RNG.standard_normal(shape).astype(np.float32)
    k = RNG.standard_normal(shape).astype(np.float32)
    for tier in ("reference", "pallas"):
        op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                         n_spectra=1, kernel=tier)
        got = np.asarray(op.apply(jnp.asarray(x), jnp.asarray(k)))
        ref = unfused_real(shape, mesh, jnp.asarray(x), jnp.asarray(k),
                           kernel=tier)
        check_bitwise(f"kernel={tier}", got, ref)


def check_batch_broadcast(mesh):
    shape = SHAPES[2]
    xb = RNG.standard_normal((2,) + shape).astype(np.float32)
    k = RNG.standard_normal(shape).astype(np.float32)
    op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                     n_spectra=1)
    got = np.asarray(op.apply(jnp.asarray(xb), jnp.asarray(k)))
    per = np.stack([np.asarray(op.apply(jnp.asarray(xb[i]),
                                        jnp.asarray(k)))
                    for i in range(2)])
    check_bitwise("batched main x unbatched kernel", got, per)
    want = np_conv(xb, k, 2)
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
    assert err < 3e-4, err
    print(f"PASS batched conv matches numpy rel_err={err:.2e}")


def check_overlap(mesh):
    shape = SHAPES[3]
    x = RNG.standard_normal(shape).astype(np.float32)
    k = RNG.standard_normal(shape).astype(np.float32)
    base = None
    for oc in (1, 2, 4):
        op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                         n_spectra=1, overlap_chunks=oc)
        got = np.asarray(op.apply(jnp.asarray(x), jnp.asarray(k)))
        if base is None:
            base = got
        assert np.array_equal(base, got), oc
    print("PASS overlap chunks bit-identical across depths")


def check_with_options(mesh):
    shape = SHAPES[2]
    k = RNG.standard_normal(shape).astype(np.float32)
    op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                     spectra=(k,), wire_dtype='native')
    x = RNG.standard_normal(shape).astype(np.float32)
    want = np.asarray(op.apply(jnp.asarray(x)))
    for kw in ({'comm': 'ppermute'}, {'overlap_chunks': 2},
               {'kernel': 'reference'}, {'donate': False}):
        q = op.with_options(**kw)
        assert type(q) is type(op) and q.n_baked == 1, kw
        got = np.asarray(q.apply(jnp.asarray(x)))
        assert np.array_equal(got, want), kw   # pure schedule changes
        print(f"PASS with_options({kw}) round-trips baked op plan")
    w = op.with_options(wire_dtype='fp16')
    assert w.wire_dtype == 'fp16' and w.op_name == op.op_name
    rel = np.max(np.abs(np.asarray(w.apply(jnp.asarray(x))) - want)) \
        / max(np.max(np.abs(want)), 1e-30)
    assert rel < WIRE_RTOL['fp16'], rel
    print(f"PASS with_options(wire_dtype) rebakes, rel_err={rel:.2e}")


def check_serving(mesh):
    from repro.serve.fft_engine import FFTEngine
    shape = SHAPES[2]
    eng = FFTEngine(shape, mesh)
    k = RNG.standard_normal(shape).astype(np.float32)
    eng.register_op('conv', shape=shape, op=fft.spectral_mul,
                    spectra=(k,))
    assert eng.registered_ops() == ['conv']
    plan = eng.plan_for(op='conv')
    xs = [RNG.standard_normal(shape).astype(np.float32) for _ in range(4)]
    tickets = [eng.submit(jnp.asarray(x), op='conv') for x in xs]
    eng.flush()
    for x, t in zip(xs, tickets):
        got = np.asarray(t.result(timeout=60))
        want = np.asarray(plan.apply(jnp.asarray(x)))
        assert np.array_equal(got, want), "served != direct apply"
    stats = eng.dispatch_stats()
    assert stats['groups'] == 1, stats   # one coalesced fused dispatch
    print(f"PASS engine serving: 4 op requests -> {stats['groups']} "
          f"group, bitwise == direct apply")
    # op and plain transform requests never share a group
    t1 = eng.submit(jnp.asarray(xs[0]), op='conv')
    t2 = eng.submit(jnp.asarray(xs[1]), direction='fwd', real=True)
    eng.flush()
    t1.result(timeout=60)
    t2.result(timeout=60)
    assert eng.dispatch_stats()['groups'] == 3
    print("PASS engine serving: op and plain kinds dispatch separately")
    eng.close()


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    check_strategy_matrix(mesh)
    check_complex(mesh)
    check_baked(mesh)
    check_wire_dtypes(mesh)
    check_kernel_tiers(mesh)
    check_batch_broadcast(mesh)
    check_overlap(mesh)
    check_with_options(mesh)
    check_serving(mesh)
    print("SPECTRAL_OP_WORKER_OK")


if __name__ == "__main__":
    main()
