"""Worker script: real-input (rfft/irfft) plans on 16 fake devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_rfft_worker.py
Exits 0 on success; prints PASS lines per case.

Covers the acceptance matrix: ranks 1/2/3 vs ``np.fft.rfftn`` /
``np.fft.irfftn`` across every comm strategy and the registered
methods, exact round trips, leading batch dims, output shardings
(truncated axis gathered by default; distributed under
``padded_spectrum``), and overlap-pipeline bit-equivalence.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro import comm  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(17)
SHAPES = {1: (1024,), 2: (32, 64), 3: (16, 16, 16)}


def nprfft(x, rank):
    return np.fft.rfftn(x, axes=tuple(range(-rank, 0)))


def check(name, got, want, tol=3e-4):
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30)
    assert err < tol, f"{name}: rel err {err:.2e} > {tol}"
    print(f"PASS {name} rel_err={err:.2e}")


def check_strategy_matrix(mesh):
    for rank, shape in SHAPES.items():
        x = RNG.standard_normal(shape).astype(np.float32)
        want = nprfft(x, rank)
        ref = None
        for strategy in comm.names():
            p = fft.rplan(shape, mesh, comm=strategy)
            assert p.real and p.spectrum_shape[-1] == shape[-1] // 2 + 1
            xs = jax.device_put(jnp.asarray(x), p.in_sharding)
            y = p.forward(xs)
            assert y.shape == p.spectrum_shape, (y.shape, p.spectrum_shape)
            got = np.asarray(y, np.complex128)
            check(f"rank{rank} comm={strategy} rfft", got, want)
            if ref is None:
                ref = got
            assert np.array_equal(ref, got), (rank, strategy,
                                              "strategies disagree")
            back = p.inverse(y)
            assert not np.iscomplexobj(np.asarray(back))
            check(f"rank{rank} comm={strategy} roundtrip",
                  np.asarray(back, np.float64), x, 1e-4)
            # matches numpy's irfftn on the same (Hermitian) spectrum
            nb = np.fft.irfftn(want, s=shape, axes=tuple(range(-rank, 0)))
            assert np.max(np.abs(np.asarray(back, np.float64) - nb)) < 1e-4


def check_method_matrix(mesh):
    shape = (16, 16, 16)
    x = RNG.standard_normal(shape).astype(np.float32)
    want = nprfft(x, 3)
    for method in fft.available_methods():
        p = fft.rplan(shape, mesh, method=method)
        xs = jax.device_put(jnp.asarray(x), p.in_sharding)
        y = p.forward(xs)
        check(f"method={method} rfft", np.asarray(y, np.complex128), want)
        back = p.inverse(y)
        check(f"method={method} roundtrip", np.asarray(back, np.float64),
              x, 1e-4)


def check_shardings(mesh):
    for rank, shape in SHAPES.items():
        x = RNG.standard_normal(shape).astype(np.float32)
        p = fft.rplan(shape, mesh)
        y = p.forward(jax.device_put(jnp.asarray(x), p.in_sharding))
        assert y.sharding.is_equivalent_to(p.out_sharding, rank), (
            rank, y.sharding, p.out_sharding)
        back = p.inverse(y)
        assert back.sharding.is_equivalent_to(p.in_sharding, rank)
        print(f"PASS rank{rank} shardings: out={y.sharding.spec} "
              f"in={back.sharding.spec}")
    # default contract gathers the truncated axis into memory
    p3 = fft.rplan((16, 16, 16), mesh)
    assert p3.out_layout[-1] is None


def check_padded_mode(mesh):
    for rank, shape in ((2, (32, 64)), (3, (16, 16, 16))):
        nh = shape[-1] // 2 + 1
        x = RNG.standard_normal(shape).astype(np.float32)
        want = nprfft(x, rank)
        p = fft.rplan(shape, mesh, padded_spectrum=True)
        # the padded extent must shard evenly over the owning mesh group
        owner = p.out_layout[-1]
        psize = 1
        for a in (owner if isinstance(owner, tuple) else (owner,)):
            psize *= mesh.shape[a]
        assert p.spectrum_shape[-1] % psize == 0, (p.spectrum_shape, owner)
        assert p.spectrum_shape[-1] >= nh
        y = p.forward(jax.device_put(jnp.asarray(x), p.in_sharding))
        assert y.shape == p.spectrum_shape
        # the distributed native spectrum keeps the rotated layout
        assert y.sharding.is_equivalent_to(p.out_sharding, rank)
        check(f"rank{rank} padded rfft",
              np.asarray(y, np.complex128)[..., :nh], want)
        back = p.inverse(y)
        check(f"rank{rank} padded roundtrip", np.asarray(back, np.float64),
              x, 1e-4)
        # pad bins are dead: poisoning them must not change the inverse
        yj = np.asarray(y).copy()
        yj[..., nh:] = 1e6
        backj = p.inverse(jnp.asarray(yj))
        assert np.array_equal(np.asarray(backj), np.asarray(back)), rank
        print(f"PASS rank{rank} padded pad-bins inert")


def check_batch_and_cache(mesh):
    for rank, shape in SHAPES.items():
        xb = RNG.standard_normal((2,) + shape).astype(np.float32)
        p = fft.rplan(shape, mesh)
        yb = p.forward(jnp.asarray(xb))
        check(f"rank{rank} batched rfft", np.asarray(yb, np.complex128),
              nprfft(xb, rank))
        bb = p.inverse(yb)
        check(f"rank{rank} batched roundtrip", np.asarray(bb, np.float64),
              xb, 1e-4)
    p = fft.rplan((16, 16, 16), mesh)
    x = jnp.asarray(RNG.standard_normal((16, 16, 16)), jnp.float32)
    y = p.forward(x)
    n_keys = len(p._exec_cache)
    p.forward(x)
    p.inverse(y)
    p.inverse(y)
    assert len(p._exec_cache) == n_keys + 1, p._exec_cache.keys()
    print("PASS rfft exec cache stable across repeat calls")


def check_overlap_equivalence(mesh):
    """Every strategy x chunk depth is bit-identical — the (fft, swap)
    pairs AND the r2c split-combine pair (first forward superstep, last
    inverse superstep) now both pipeline."""
    shape = (16, 16, 16)
    x = RNG.standard_normal(shape).astype(np.float32)
    base, rbase = None, None
    for strategy in comm.names():
        for oc in (1, 2, 4):
            p = fft.rplan(shape, mesh, comm=strategy, overlap_chunks=oc)
            xs = jax.device_put(jnp.asarray(x), p.in_sharding)
            got = np.asarray(p.forward(xs))
            if base is None:
                base = got
            assert np.array_equal(base, got), (strategy, oc)
            back = np.asarray(p.inverse(jnp.asarray(got)))
            if rbase is None:
                rbase = back
            assert np.array_equal(rbase, back), (strategy, oc, "inverse")
    print("PASS rfft overlap pipeline (incl. r2c split-combine pair) "
          "bit-identical across strategies x chunks")


def check_overlap_fallback(mesh):
    """Chunk counts nothing divides fall back bit-exactly to the
    unpipelined path, per strategy (the r2c pair falls back by the same
    shared rule); rank-1 odd batches fall back in the real four-step."""
    shape = (16, 16, 16)
    x = RNG.standard_normal(shape).astype(np.float32)
    for strategy in comm.names():
        base = None
        for oc in (1, 3, 5):
            p = fft.rplan(shape, mesh, comm=strategy, overlap_chunks=oc)
            xs = jax.device_put(jnp.asarray(x), p.in_sharding)
            got = np.asarray(p.forward(xs))
            if base is None:
                base = got
            assert np.array_equal(base, got), (strategy, oc)
        print(f"PASS rfft overlap fallback comm={strategy} bit-exact")
    xb = RNG.standard_normal((3, 1024)).astype(np.float32)
    a = np.asarray(fft.rplan((1024,), mesh,
                             overlap_chunks=1).forward(jnp.asarray(xb)))
    b = np.asarray(fft.rplan((1024,), mesh,
                             overlap_chunks=2).forward(jnp.asarray(xb)))
    assert np.array_equal(a, b)
    print("PASS rfft overlap fallback rank-1 odd batch bit-exact")


def check_auto_and_cost(mesh):
    p = fft.rplan((16, 16, 16), mesh, comm='auto')
    assert p.comm in comm.names()
    rep = p.cost_report()
    assert 'rfft' in rep and 'swap' in rep
    x = RNG.standard_normal((16, 16, 16)).astype(np.float32)
    back = p.inverse(p.forward(jax.device_put(jnp.asarray(x),
                                              p.in_sharding)))
    assert np.max(np.abs(np.asarray(back) - x)) < 1e-4
    print(f"PASS rfft comm='auto' plan: strategy={p.comm} "
          f"overlap={p.overlap_chunks} method={p.method}")


def check_restore_layout(mesh):
    shape = (16, 16, 16)
    x = RNG.standard_normal(shape).astype(np.float32)
    p = fft.rplan(shape, mesh, restore_layout=True)
    y = p.forward(jax.device_put(jnp.asarray(x), p.in_sharding))
    check("restore_layout rfft", np.asarray(y, np.complex128), nprfft(x, 3))
    back = p.inverse(y)
    check("restore_layout roundtrip", np.asarray(back, np.float64), x, 1e-4)


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    check_strategy_matrix(mesh)
    check_method_matrix(mesh)
    check_shardings(mesh)
    check_padded_mode(mesh)
    check_batch_and_cache(mesh)
    check_overlap_equivalence(mesh)
    check_overlap_fallback(mesh)
    check_auto_and_cost(mesh)
    check_restore_layout(mesh)
    print("RFFT_WORKER_OK")


if __name__ == "__main__":
    main()
