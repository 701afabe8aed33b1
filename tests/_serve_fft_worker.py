"""Worker script: the batched FFT serving engine on 16 fake devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_serve_fft_worker.py
Exits 0 on success; prints PASS lines per case.

Covers the acceptance contract on a real multi-device mesh: engine
outputs BIT-IDENTICAL to per-request plan execution for complex and
real requests across every comm strategy, remainder groups, inverse
serving, donation of staged (not caller) buffers, and the overlap
fallback inside batched executions.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["REPRO_SERVE_SCHEDULES"] = ""       # deterministic picks

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro import comm  # noqa: E402
from repro.serve import FFTEngine  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(41)
SHAPE = (16, 16, 16)


def per_request_refs(shape, mesh, reqs, strategy):
    pc = fft.plan(shape, mesh, comm=strategy, donate=False)
    pr = fft.rplan(shape, mesh, comm=strategy)
    refs = []
    for x in reqs:
        p = pc if np.iscomplexobj(x) else pr
        refs.append(np.asarray(
            p.forward(jax.device_put(jnp.asarray(x), p.in_sharding))))
    return refs


def check_engine_bit_identity(mesh):
    for strategy in comm.names():
        eng = FFTEngine(SHAPE, mesh, comm=strategy)
        reqs = []
        for i in range(7):                    # 7: exercises a remainder group
            x = RNG.standard_normal(SHAPE).astype(np.float32)
            if i % 2 == 0:
                x = (x + 1j * RNG.standard_normal(SHAPE)).astype(np.complex64)
            reqs.append(x)
        outs = eng.transform(reqs)
        refs = per_request_refs(SHAPE, mesh, reqs, strategy)
        for i, (o, r) in enumerate(zip(outs, refs)):
            assert np.array_equal(np.asarray(o), r), (strategy, i)
        w, c = eng.schedule(False)
        print(f"PASS engine comm={strategy} bit-identical "
              f"(7 mixed requests, w={w} c={c})")


def check_engine_inverse_roundtrip(mesh):
    eng = FFTEngine(SHAPE, mesh)
    xc = [(RNG.standard_normal(SHAPE)
           + 1j * RNG.standard_normal(SHAPE)).astype(np.complex64)
          for _ in range(3)]
    xr = [RNG.standard_normal(SHAPE).astype(np.float32) for _ in range(3)]
    specs = eng.transform(xc + xr)
    backs = eng.transform(specs, direction='inv')
    for x, b in zip(xc + xr, backs):
        assert np.max(np.abs(np.asarray(b) - x)) < 1e-4
    assert not np.iscomplexobj(np.asarray(backs[-1]))
    print("PASS engine inverse serving round trips (complex + real)")


def check_engine_donation(mesh):
    p = fft.plan(SHAPE, mesh, donate=False)

    def make():
        return jax.device_put(
            jnp.asarray((RNG.standard_normal(SHAPE)
                         + 1j * RNG.standard_normal(SHAPE)), jnp.complex64),
            p.in_sharding)

    # donate=True engine consumes submitted jax arrays (plan contract)
    eng = FFTEngine(SHAPE, mesh)
    xs = [make() for _ in range(4)]
    eng.transform(xs)
    assert all(x.is_deleted() for x in xs)
    # donate=False engine keeps them reusable
    engnd = FFTEngine(SHAPE, mesh, donate=False)
    xs2 = [make() for _ in range(4)]
    a = engnd.transform(xs2)
    b = engnd.transform(xs2)
    assert not any(x.is_deleted() for x in xs2)
    assert all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(a, b))
    # direct donating plan consumes its operand on this mesh too
    pd = fft.plan(SHAPE, mesh)
    x = make()
    y = pd.forward(x)
    assert x.is_deleted()
    try:
        _ = x + 1
        raise AssertionError("reuse after donate must raise")
    except RuntimeError:
        pass
    assert not y.is_deleted()
    print("PASS donation: donated requests consumed, donate=False "
          "reusable, reuse-after-donate raises")


def check_engine_overlap_fallback(mesh):
    # a 6-wide group with overlap_chunks=4: the batch axis (6) does not
    # divide, so pairs fall back (or chunk another free axis) per the
    # shared rule — results must stay bit-identical
    eng = FFTEngine(SHAPE, mesh, max_coalesce=8, overlap_chunks=4)
    eng.set_schedule(6, 4)
    plan = eng.plan_for(False)
    assert plan.overlap_chunks == 4
    reqs = [(RNG.standard_normal(SHAPE)
             + 1j * RNG.standard_normal(SHAPE)).astype(np.complex64)
            for _ in range(6)]
    outs = eng.transform(reqs)
    assert eng.schedule(False) == (6, 4)       # preset actually served
    refs = per_request_refs(SHAPE, mesh, reqs, plan.comm)
    for o, r in zip(outs, refs):
        assert np.array_equal(np.asarray(o), r)
    print("PASS engine overlap fallback (non-dividing width) bit-identical")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    check_engine_bit_identity(mesh)
    check_engine_inverse_roundtrip(mesh)
    check_engine_donation(mesh)
    check_engine_overlap_fallback(mesh)
    print("SERVE_FFT_WORKER_OK")


if __name__ == "__main__":
    main()
