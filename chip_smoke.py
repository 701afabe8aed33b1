"""Smoke test of the main path on a TPU: the distributed 3-D FFT at the
paper's 512^3, an operator plan, and the FFT service, each checked
against float64 ``numpy.fft``.

    python chip_smoke.py          # one chip
    python chip_smoke.py --four   # 2x2 mesh on a four-chip host

One chip runs five phases: c2c 512^3 forward and inverse, r2c 512^3
forward and inverse, a 512^3 operator plan with a baked spectrum (a
Poisson solve), ``FFTService`` serving two tenants over a unix socket
with mixed c2c/r2c requests at 32^3-128^3, and r2c requests at
32^3-128^3 coalesced by ``FFTEngine``, checked bitwise against
per-request plan calls. ``--four`` runs only
the c2c and r2c 512^3 phases on a 2x2 mesh and checks that every output
is sharded over the four chips.

Every phase prints the resolved method, kernel tier and comm strategy,
the max relative error against numpy (bound 1e-4), compile and warm
times (smoke timings, not a benchmark) and the device's peak memory. It
asserts the Pallas tier, a Mosaic kernel (``tpu_custom_call``) in the
compiled program, and the error bound. The last line of a passing run is
one JSON object naming the device; a failing run, or one without a TPU,
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, 'src'))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: max |got - want| / max |want| against float64 numpy, for fp32 plans
#: at 512^3 (measured error ~3e-7: the bound leaves room for the fp32
#: rounding growth of log2(n) stages, and still fails any wrong bin)
REL_BOUND = 1e-4
N = 512
#: seed of the random inputs
SEED = 0


def log(*a) -> None:
    print(*a, flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def check(name: str, got, want) -> None:
    err = rel_err(got, want)
    log(f'  {name}: max rel err {err:.3e} (bound {REL_BOUND:g})')
    if not err <= REL_BOUND:
        raise AssertionError(f'{name}: rel err {err:.3e} > {REL_BOUND:g}')


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats().get('peak_bytes_in_use', 0))


def describe(tag: str, p) -> None:
    from repro.fft import methods
    n = p.shape[-1]
    log(f'[{tag}] shape={p.shape} real={p.real} '
        f'method={methods.resolve(p.method, n // 2 if p.real else n).name} '
        f'kernel={p.kernel}->{p.resolved_kernel} '
        f'comm={p.comm} mesh={dict(p.mesh.shape)}')
    if p.resolved_kernel != 'pallas':
        raise AssertionError(f'{tag}: kernel tier resolved to '
                             f'{p.resolved_kernel!r}, not pallas')


def assert_mosaic(label: str, hlo: str) -> None:
    if 'tpu_custom_call' not in hlo:
        raise AssertionError(f'{label}: no tpu_custom_call in the program')


def compiled_call(label: str, fn, *args):
    """Compile ``fn`` for ``args``, assert a Mosaic kernel is in the
    program, run it twice and return the second (warm) result."""
    t0 = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    t_compile = time.perf_counter() - t0
    assert_mosaic(label, exe.as_text())
    jax.block_until_ready(exe(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(exe(*args))
    warm = time.perf_counter() - t0
    log(f'  {label}: compile {t_compile:.2f} s, tpu_custom_call present, '
        f'warm {warm * 1e3:.3f} ms (smoke timing, not a benchmark)')
    return out


def check_sharded(name: str, y, n_dev: int) -> None:
    devs = {s.device for s in y.addressable_shards}
    sizes = sorted({s.data.size for s in y.addressable_shards})
    log(f'  {name}: shards on {len(devs)} distinct devices, '
        f'shard sizes {sizes} of {y.size}')
    if len(devs) != n_dev or max(sizes) >= y.size:
        raise AssertionError(f'{name}: not sharded over {n_dev} devices')


def phase_c2c(mesh, rng, tag: str, n_dev: int) -> None:
    import repro.fft as fft
    shape = (N,) * 3
    p = fft.plan(shape, mesh, donate=False)
    describe(tag, p)
    x = (rng.standard_normal(shape, np.float32)
         + 1j * rng.standard_normal(shape, np.float32)).astype(np.complex64)
    xd = jax.device_put(x, p.in_sharding)
    y = compiled_call('forward', p.forward, xd)
    want = np.fft.fftn(x.astype(np.complex128))
    check('forward vs numpy.fft.fftn', y, want)
    del want
    z = compiled_call('inverse', p.inverse, y)
    check('inverse(forward(x)) vs x', z, x)
    if n_dev > 1:
        check_sharded('forward output', y, n_dev)
        check_sharded('inverse output', z, n_dev)
    log(f'  peak_bytes_in_use {peak_bytes()}')


def phase_r2c(mesh, rng, tag: str, n_dev: int) -> None:
    import repro.fft as fft
    shape = (N,) * 3
    p = fft.rplan(shape, mesh)
    describe(tag, p)
    x = rng.standard_normal(shape, np.float32)
    xd = jax.device_put(x, p.in_sharding)
    y = compiled_call('forward', p.forward, xd)
    nh = N // 2 + 1
    check('forward vs numpy.fft.rfftn', np.asarray(y)[..., :nh],
          np.fft.rfftn(x.astype(np.float64)))
    z = compiled_call('inverse', p.inverse, y)
    want = np.fft.irfftn(np.asarray(y)[..., :nh].astype(np.complex128),
                         s=shape, axes=(0, 1, 2))
    check('inverse vs numpy.fft.irfftn', z, want)
    if n_dev > 1:
        check_sharded('forward output', y, n_dev)
        check_sharded('inverse output', z, n_dev)
    log(f'  peak_bytes_in_use {peak_bytes()}')


def phase_op(mesh, rng) -> None:
    """A periodic Poisson solve, -laplace(phi) = rho: the Green's
    function 1/|k|^2 is baked once, every apply is one dispatch."""
    import repro.fft as fft
    shape = (N,) * 3
    k = [np.fft.fftfreq(N) * N] * 2 + [np.fft.rfftfreq(N) * N]
    k2 = sum(np.meshgrid(*[a ** 2 for a in k], indexing='ij',
                         sparse=True))
    green = np.where(k2 > 0, 1.0 / np.where(k2 > 0, k2, 1.0), 0.0)
    op = fft.plan_op(shape, mesh, op=fft.spectral_mul, real=True,
                     spectra=(green.astype(np.complex64),),
                     spectra_form='spectrum', op_name='poisson',
                     donate=False)
    describe('operator plan 512^3 (baked Green\'s function)', op)
    rho = rng.standard_normal(shape, np.float32)
    rd = jax.device_put(rho, op.in_sharding)
    phi = compiled_call('apply', op.apply, rd)
    want = np.fft.irfftn(np.fft.rfftn(rho.astype(np.float64)) * green,
                         s=shape, axes=(0, 1, 2))
    check('apply vs numpy irfftn(rfftn(rho) * G)', phi, want)
    if op.bake_count != 1:
        raise AssertionError(f'spectrum baked {op.bake_count} times')
    log(f'  baked {op.bake_count}x, peak_bytes_in_use {peak_bytes()}')


def phase_service(mesh, rng) -> None:
    from repro.serve import FFTClient, FFTService, TenantConfig
    log('[served path] FFTService + 2 tenants over a unix socket')
    shapes = [(32,) * 3, (64,) * 3, (128,) * 3]
    reqs = {}
    for t, tenant in enumerate(('alice', 'bob')):
        reqs[tenant] = []
        for i, shape in enumerate(shapes):
            x = rng.standard_normal(shape, np.float32)
            if (i + t) % 2 == 0:
                x = (x + 1j * rng.standard_normal(shape, np.float32)
                     ).astype(np.complex64)
            reqs[tenant].append(x)
    tmp = tempfile.mkdtemp(prefix='chip_smoke_')
    path = os.path.join(tmp, 'fft.sock')
    svc = FFTService(mesh, schedule_table=None,
                     tenants=[TenantConfig('alice', max_inflight=4),
                              TenantConfig('bob', max_inflight=4,
                                           slo='interactive')],
                     allow_unknown_tenants=False).start(path)
    outs, failures = {}, []

    def client(tenant):
        try:
            t0 = time.perf_counter()
            with FFTClient(path, tenant=tenant) as c:
                outs[tenant] = c.transform(reqs[tenant])
                c.drain(timeout=600)
            log(f'  tenant {tenant}: {len(reqs[tenant])} requests in '
                f'{time.perf_counter() - t0:.3f} s (smoke timing, '
                f'includes first-call compiles)')
        except BaseException as exc:         # reraised after join
            failures.append((tenant, exc))

    try:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in ('alice', 'bob')]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=900)
            if th.is_alive():
                raise AssertionError('service client wedged')
        if failures:
            raise failures[0][1]
        served = sorted({(x.shape, not np.iscomplexobj(x))
                         for rs in reqs.values() for x in rs})
        for shape, real in served:
            p = svc.engine.plan_for(real, shape)
            describe(f'served {"r2c" if real else "c2c"} {shape}', p)
            sds = jax.ShapeDtypeStruct(
                shape, jnp.float32 if real else jnp.complex64,
                sharding=p.in_sharding)
            assert_mosaic(f'served {shape}',
                          jax.jit(p.forward).lower(sds).compile().as_text())
        for tenant in ('alice', 'bob'):
            for x, y in zip(reqs[tenant], outs[tenant]):
                x64 = x.astype(np.complex128 if np.iscomplexobj(x)
                               else np.float64)
                want = (np.fft.fftn(x64) if np.iscomplexobj(x)
                        else np.fft.rfftn(x64))
                check(f'{tenant} {x.shape} {x.dtype} vs numpy', y, want)
        m = svc.metrics()
        for tenant in ('alice', 'bob'):
            tm = m['tenants'][tenant]
            if tm['completed'] != len(shapes) or tm['failed']:
                raise AssertionError(f'{tenant} accounting: {tm}')
    finally:
        svc.close(drain=True)
        if os.path.exists(path):
            os.unlink(path)
        os.rmdir(tmp)
    log(f'  peak_bytes_in_use {peak_bytes()}')


def phase_batched(mesh, rng) -> None:
    """Served r2c requests coalesced by ``FFTEngine`` (width 4, two
    overlap chunks) give the bits of per-request plan calls, forward and
    inverse, at the served sizes, with every axis of the real plan on
    the kernels."""
    import repro.fft as fft
    from repro.serve import FFTEngine
    for n in (32, 64, 128):
        shape = (n,) * 3
        p = fft.rplan(shape, mesh)
        describe(f'batched r2c {shape}', p)
        eng = FFTEngine(shape, mesh, schedule_table=None)
        eng.set_schedule(4, 2, real=True)
        xs = [rng.standard_normal(shape, np.float32) for _ in range(5)]
        ys = eng.transform(xs)
        backs = eng.transform(ys, direction='inv')
        for i, (x, y, b) in enumerate(zip(xs, ys, backs)):
            for name, got, want in (('forward', y, p.forward(x)),
                                    ('inverse', b, p.inverse(y))):
                if not np.array_equal(np.asarray(got), np.asarray(want)):
                    raise AssertionError(
                        f'{shape} request {i} {name}: batched != '
                        f'per-request, max abs diff '
                        f'{np.max(np.abs(np.asarray(got) - want)):.3e}')
        check('request 0 forward vs numpy.fft.rfftn', ys[0],
              np.fft.rfftn(xs[0].astype(np.float64)))
        log(f'  {len(xs)} requests: batched == per-request bitwise, '
            f'forward and inverse')


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--four', action='store_true',
                    help='run the c2c and r2c 512^3 phases on a 2x2 mesh')
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != 'tpu':
        print(f'chip_smoke: no TPU (jax sees {dev.platform!r}); the smoke '
              f'test runs only on the chip', file=sys.stderr)
        return 1
    n_dev = 4 if args.four else 1
    if len(devices) < n_dev:
        print(f'chip_smoke: --four needs 4 chips, jax sees {len(devices)}',
              file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    log(f'[device] platform={dev.platform} device_kind={dev.device_kind} '
        f'count={len(devices)} jax={jax.__version__}')
    log(f'[cache] {enable_compile_cache()}')
    rng = np.random.default_rng(SEED)
    if args.four:
        mesh = make_mesh((2, 2), ('x', 'y'), devices=devices[:4])
        phase_c2c(mesh, rng, 'c2c 512^3 on 2x2', 4)
        phase_r2c(mesh, rng, 'r2c 512^3 on 2x2', 4)
    else:
        mesh = make_mesh((1, 1), ('x', 'y'), devices=devices[:1])
        phase_c2c(mesh, rng, 'paper cell c2c 512^3', 1)
        phase_r2c(mesh, rng, 'real input r2c 512^3', 1)
        phase_op(mesh, rng)
        phase_service(mesh, rng)
        phase_batched(mesh, rng)
    print(json.dumps({'ok': True, 'device': {
        'platform': dev.platform, 'kind': dev.device_kind,
        'count': len(devices)}}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
