"""Worker script: repro.comm strategy equivalence on 16 fake devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_comm_worker.py
Exits 0 on success; prints PASS lines per case.

Checks, on a 4x4 ('x', 'y') mesh:
  * every registered strategy's swap — plus parameterized pod trees
    (``'pod_tree:<spec>'``) — is BIT-EXACT equal to the tiled
    all_to_all reference, for single-axis and flattened tuple-axis
    groups and several (shard_pos, mem_pos) placements;
  * ``redistribute(x, src, dst)`` then ``redistribute(y, dst, src)``
    round-trips bit-exactly for random layouts, under every strategy;
  * the overlap pipeline (pipelined fft+swap) is numerically identical
    to the unpipelined path through the public facade.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import itertools  # noqa: E402
import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import comm  # noqa: E402
import repro.fft as fft  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(11)

#: parameterized pod trees exercised beyond the registered names — a
#: deep single-axis split and an asymmetric mixed-depth tree, both on
#: the 4x4 mesh
TREES = ('pod_tree:x.2*x.2*y.2*y.2', 'pod_tree:x.4*y.2*y.2')


def all_strategies():
    return comm.names() + TREES


def run_swap(mesh, mesh_axis, strategy, x, shard_pos, mem_pos, ndim):
    in_spec = [None] * ndim
    in_spec[shard_pos] = mesh_axis
    out_spec = [None] * ndim
    out_spec[mem_pos] = mesh_axis

    def f(a):
        return comm.swap_axes(a, mesh_axis, shard_pos=shard_pos,
                              mem_pos=mem_pos, strategy=strategy)

    fn = jax.shard_map(f, mesh=mesh, in_specs=P(*in_spec), out_specs=P(*out_spec),
                       check_vma=False)
    return np.asarray(jax.jit(fn)(x))


def check_swaps(mesh):
    shape = (16, 16, 16)
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    for mesh_axis in ('x', 'y', ('x', 'y'), ('y', 'x')):
        for shard_pos, mem_pos in ((0, 1), (0, 2), (2, 0), (1, 2)):
            ref = None
            for name in all_strategies():
                got = run_swap(mesh, mesh_axis, name, x, shard_pos, mem_pos, 3)
                if ref is None:
                    ref = got
                assert np.array_equal(ref, got), (mesh_axis, name,
                                                  shard_pos, mem_pos)
            print(f"PASS swap bit-exact axis={mesh_axis} "
                  f"sp={shard_pos} mp={mem_pos}")


def random_layouts(ndim, n_cases):
    """Random distinct (src, dst) layout pairs over axes x/y on ndim
    array axes, each layout using each mesh axis at most once."""
    opts = []
    for owners in itertools.permutations(['x', 'y'] + [None] * ndim, ndim):
        if 'x' in owners and 'y' in owners:
            opts.append(tuple(owners))
    cases = []
    while len(cases) < n_cases:
        src = opts[RNG.integers(len(opts))]
        dst = opts[RNG.integers(len(opts))]
        if src != dst:
            cases.append((src, dst))
    return cases


def check_redistribute_roundtrip(mesh):
    shape = (16, 16, 16)
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    for src, dst in random_layouts(3, 8):
        for name in comm.names():
            def go(a, s=src, d=dst, n=name):
                y = comm.redistribute(a, s, d, strategy=n)
                return comm.redistribute(y, d, s, strategy=n)
            fn = jax.shard_map(go, mesh=mesh, in_specs=P(*src), out_specs=P(*src),
                               check_vma=False)
            got = np.asarray(jax.jit(fn)(x))
            assert np.array_equal(got, np.asarray(x)), (src, dst, name)
        print(f"PASS redistribute round-trip {src} <-> {dst} (all strategies)")


def check_facade_matrix(mesh):
    """Ranks 1/2/3 x complex/planar x every strategy: round trips on the
    16-device mesh, and strategies agree with each other."""
    shapes = {1: (1024,), 2: (32, 64), 3: (16, 16, 16)}
    for rank, shape in shapes.items():
        z = RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)
        want = np.fft.fftn(z, axes=tuple(range(-rank, 0)))
        ref = None
        for strategy in comm.names() + TREES[:1]:
            p = fft.plan(shape, mesh, comm=strategy)
            zc = jax.device_put(jnp.asarray(z, jnp.complex64), p.in_sharding)
            y = p.forward(zc)
            got = np.asarray(y, np.complex128)
            err = np.max(np.abs(got - want)) / np.max(np.abs(want))
            assert err < 3e-4, (rank, strategy, err)
            back = np.asarray(p.inverse(y), np.complex128)
            rerr = np.max(np.abs(back - z)) / np.max(np.abs(z))
            assert rerr < 3e-4, (rank, strategy, rerr)
            if ref is None:
                ref = got
            assert np.array_equal(ref, got), (rank, strategy,
                                              "strategies disagree")
            # planar front-end, same strategy
            re, im = jnp.asarray(z.real, jnp.float32), jnp.asarray(
                z.imag, jnp.float32)
            fr, fi = p.forward((re, im))
            perr = np.max(np.abs((np.asarray(fr, np.float64)
                                  + 1j * np.asarray(fi, np.float64)) - want))
            assert perr / np.max(np.abs(want)) < 3e-4, (rank, strategy)
            print(f"PASS facade rank{rank} comm={strategy} "
                  f"fwd_err={err:.2e}")


def check_overlap_equivalence(mesh):
    shape = (16, 16, 16)
    z = RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)
    base = None
    for strategy in comm.names():
        for oc in (1, 2, 4):
            p = fft.plan(shape, mesh, comm=strategy, overlap_chunks=oc)
            zc = jax.device_put(jnp.asarray(z, jnp.complex64), p.in_sharding)
            got = np.asarray(p.forward(zc))
            if base is None:
                base = got
            assert np.array_equal(base, got), (strategy, oc)
    print("PASS overlap pipeline bit-identical across strategies x chunks")


def check_auto_plan(mesh):
    p = fft.plan((16, 16, 16), mesh, comm='auto')
    # auto may pick a measured pod tree beyond the registered names;
    # validate() accepts both and raises on anything else
    assert comm.validate(p.comm) == p.comm, p.comm
    assert p.overlap_chunks >= 1
    rep = p.cost_report()
    assert 'swap' in rep and 'fft' in rep
    z = RNG.standard_normal((16,) * 3)         # keep a host copy: the
    zc = jax.device_put(                       # donated zc is consumed
        jnp.asarray(z, jnp.complex64), p.in_sharding)
    back = p.inverse(p.forward(zc))
    assert np.max(np.abs(np.asarray(back) - z)) < 1e-3
    print(f"PASS comm='auto' plan: strategy={p.comm} "
          f"overlap={p.overlap_chunks} method={p.method}")


def check_overlap_fallback(mesh):
    """pick_chunk_axis -> None paths: chunk counts no local axis
    divides must fall back BIT-EXACTLY to the unpipelined schedule, for
    every strategy — including the partial case where some (fft, swap)
    pairs chunk and others fall back."""
    shape = (16, 16, 16)
    z = RNG.standard_normal(shape) + 1j * RNG.standard_normal(shape)
    for strategy in comm.names():
        base = None
        # local shape (4, 4, 16): nothing divides by 3 or 5 -> every
        # pair falls back; 1 is the unpipelined reference
        for oc in (1, 3, 5):
            p = fft.plan(shape, mesh, comm=strategy, overlap_chunks=oc)
            zc = jax.device_put(jnp.asarray(z, jnp.complex64),
                                p.in_sharding)
            got = np.asarray(p.forward(zc))
            if base is None:
                base = got
            assert np.array_equal(base, got), (strategy, oc)
        print(f"PASS overlap fallback comm={strategy} bit-exact "
              f"(no-axis-divides)")
    # mixed: (16, 64, 16) pairs see free sizes 16 (chunks) and 4
    # (falls back) at oc=8
    shape2 = (16, 64, 16)
    z2 = RNG.standard_normal(shape2) + 1j * RNG.standard_normal(shape2)
    base = None
    for oc in (1, 8):
        p = fft.plan(shape2, mesh, overlap_chunks=oc)
        zc = jax.device_put(jnp.asarray(z2, jnp.complex64), p.in_sharding)
        got = np.asarray(p.forward(zc))
        if base is None:
            base = got
        assert np.array_equal(base, got), oc
    print("PASS overlap fallback mixed chunk/fallback pairs bit-exact")
    # rank-1: an odd batch (3) cannot chunk -> unpipelined body
    p1 = fft.plan((1024,), mesh, overlap_chunks=1)
    p2 = fft.plan((1024,), mesh, overlap_chunks=2)
    xb = (RNG.standard_normal((3, 1024))
          + 1j * RNG.standard_normal((3, 1024)))
    a = np.asarray(p1.forward(jnp.asarray(xb, jnp.complex64)))
    b = np.asarray(p2.forward(jnp.asarray(xb, jnp.complex64)))
    assert np.array_equal(a, b)
    print("PASS overlap fallback rank-1 odd batch bit-exact")


def check_ulysses_overlap(mesh):
    """Sequence-parallel attention: every strategy and the head-chunked
    pipeline agree with plain flash attention — including GQA (KH < H),
    where the chunk-nesting arithmetic must keep the positional q/kv
    head pairing intact."""
    from repro.models import attention as A
    B, S, D = 2, 32, 16
    for H, KH in ((8, 8), (16, 8)):    # MHA, and GQA with group 2
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)
        want = np.asarray(A.flash_attention(q, k, v, causal=True, chunk=8))
        with mesh:
            for strategy in comm.names():
                for oc in (1, 2):
                    got = np.asarray(jax.jit(
                        lambda a, b, c, s=strategy, o=oc: A.ulysses_attention(
                            a, b, c, mesh, seq_axis='y', batch_spec=P(None),
                            causal=True, chunk=8,
                            comm_strategy=s, overlap_chunks=o))(q, k, v))
                    err = np.max(np.abs(got - want))
                    assert err < 1e-5, (H, KH, strategy, oc, err)
        print(f"PASS ulysses H={H} KH={KH} strategies x overlap "
              "match flash reference")


def check_strategy_grads(mesh):
    """AD through the redistribution strategies: a swap is a pure
    permutation, so its linearization is the inverse permutation —
    grad and vjp through 'ppermute' (dynamic_slice/ppermute rounds) and
    'hierarchical' (two-phase + reshape/transpose) must match the
    all_to_all path bit-for-bit. Gate for training-path adoption of
    non-default strategies (ROADMAP)."""
    shape = (16, 16, 16)
    x = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    w = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    ct = jnp.asarray(RNG.standard_normal(shape), jnp.float32)
    for mesh_axis in ('x', 'y', ('x', 'y')):
        grads, cts = {}, {}
        for name in all_strategies():
            f = jax.shard_map(
                lambda a, n=name: comm.swap_axes(
                    a, mesh_axis, shard_pos=0, mem_pos=1, strategy=n),
                mesh=mesh, in_specs=P(mesh_axis, None, None),
                out_specs=P(None, mesh_axis, None), check_vma=False)
            loss = jax.jit(lambda a, f=f: jnp.sum(jnp.sin(f(a)) * w))
            grads[name] = np.asarray(jax.grad(loss)(x))
            _, vjp = jax.vjp(jax.jit(f), x)
            cts[name] = np.asarray(vjp(ct)[0])
        ref = grads['all_to_all']
        ref_ct = cts['all_to_all']
        for name in all_strategies():
            assert np.array_equal(grads[name], ref), (mesh_axis, name)
            assert np.array_equal(cts[name], ref_ct), (mesh_axis, name)
        print(f"PASS grad/vjp through strategies axis={mesh_axis} "
              "matches all_to_all")


def check_moe_overlap(mesh):
    """Explicit-EP MoE: strategies and the capacity-chunked pipeline
    agree (ample capacity so the chunk-padded capacity drops nothing)."""
    from types import SimpleNamespace
    from repro.models import moe as M
    cfg = SimpleNamespace(d_model=16, d_ff=32, num_experts=8, top_k=2,
                          capacity_factor=4.0, num_shared_experts=0)
    kp = jax.random.split(jax.random.PRNGKey(5), 4)
    params = {
        'router': jax.random.normal(kp[0], (16, 8), jnp.float32) * 0.1,
        'wi': jax.random.normal(kp[1], (8, 16, 64), jnp.float32) * 0.1,
        'wo': jax.random.normal(kp[2], (8, 32, 16), jnp.float32) * 0.1,
    }
    x = jax.random.normal(kp[3], (2, 16, 16), jnp.float32)
    with mesh:
        ref = None
        for strategy in comm.names():
            for oc in (1, 2):
                y, aux = jax.jit(
                    lambda px, s=strategy, o=oc: M.moe_ep_explicit(
                        params, cfg, px, mesh, ep_axis='y',
                        batch_spec=P(None),
                        comm_strategy=s, overlap_chunks=o))(x)
                got = np.asarray(y)
                if ref is None:
                    ref = got
                err = np.max(np.abs(got - ref))
                assert err < 1e-5, (strategy, oc, err)
        print("PASS moe_ep_explicit strategies x overlap agree")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    check_swaps(mesh)
    check_redistribute_roundtrip(mesh)
    check_facade_matrix(mesh)
    check_overlap_equivalence(mesh)
    check_auto_plan(mesh)
    check_overlap_fallback(mesh)
    check_strategy_grads(mesh)
    check_ulysses_overlap(mesh)
    check_moe_overlap(mesh)
    print("COMM_WORKER_OK")


if __name__ == "__main__":
    main()
