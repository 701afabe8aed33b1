"""Serve a continuous mixed stream of FFT requests — no flush() calls.

Mirrors examples/serve_batched.py for the FFT path: clients submit
independent transform requests — several SHAPES, complex fields AND
real fields (which route to rfft plans at ~half the wire) — and one
:class:`FFTEngine` with a background drainer coalesces them into
batched, overlap-pipelined executions. Requests dispatch when a kind's
queue reaches its coalesce-width watermark or when the oldest request
has waited ``--deadline-ms``; ``submit(...).result()`` is all a client
ever calls. The outputs are bit-identical to running each request
alone; only the schedule on the wire changes.

Plans (and their compiled group executables) are cached per shape in a
byte-budgeted LRU, and each kind's (width, chunks) schedule comes from
``BENCH_serve_schedule.json`` when this host has autotuned it
(``--autotune`` refreshes that table).

    PYTHONPATH=src python examples/serve_fft.py --n 32 --requests 12
"""
import argparse
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

import repro.fft as fft         # noqa: E402
from repro.serve import FFTEngine  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--n', type=int, default=32)
    ap.add_argument('--requests', type=int, default=12)
    ap.add_argument('--deadline-ms', type=float, default=5.0)
    ap.add_argument('--autotune', action='store_true',
                    help='measure candidate schedules before serving and '
                         'persist them to BENCH_serve_schedule.json')
    args = ap.parse_args()
    n = args.n
    mesh = make_mesh((4, 4), ('x', 'y'))
    shapes = [(n, n, n), (n // 2, n // 2, n // 2), (n, n)]
    rng = np.random.default_rng(0)

    # a mixed request stream: three shapes interleaved, ~half real
    # fields (rfft plans, half the wire per request), ~half complex
    reqs = []
    for i in range(args.requests):
        shape = shapes[i % len(shapes)]
        x = rng.standard_normal(shape).astype(np.float32)
        if i % 2:
            x = (x + 1j * rng.standard_normal(shape)).astype(np.complex64)
        reqs.append(x)

    # watermark 2: full pairs dispatch immediately; odd remainders in
    # any (shape, kind) queue ride the deadline — both triggers live
    with FFTEngine(mesh=mesh, max_wait_ms=args.deadline_ms,
                   watermark=2) as eng:
        if args.autotune:
            for shape in shapes:
                sub = [r for r in reqs if r.shape == shape]
                for kind in (True, False):
                    ops = [r for r in sub if np.iscomplexobj(r) != kind]
                    if ops:
                        eng.autotune(ops, persist=True)

        tickets = [eng.submit(x) for x in reqs]      # warm/compile pass
        outs = [t.result(timeout=600) for t in tickets]
        tickets = [eng.submit(x) for x in reqs]      # served continuously
        t0 = time.perf_counter()
        outs = [t.result(timeout=600) for t in tickets]
        jax.block_until_ready(outs)
        dt = (time.perf_counter() - t0) / len(reqs) * 1e6

        # verify against per-request plans (bit-identical by contract)
        for x, y in zip(reqs, outs):
            shape = x.shape
            p = (fft.plan(shape, mesh, donate=False)
                 if np.iscomplexobj(x) else fft.rplan(shape, mesh))
            ref = p.forward(jax.device_put(jnp.asarray(x), p.in_sharding))
            assert np.array_equal(np.asarray(y), np.asarray(ref))

        print(f'[serve_fft] {args.requests} mixed requests '
              f'({len(shapes)} shapes) on 4x4: {dt:.0f} us/request, '
              f'zero flush() calls')
        for (shape, real) in eng.serving_shapes():
            w, c = eng.schedule(real, shape)
            print(f"  {'x'.join(map(str, shape))}"
                  f"{' real' if real else ' complex'}: "
                  f"coalesce={w} overlap_chunks={c}")
    print('  outputs bit-identical to per-request plans; engine closed '
          'cleanly')
    print('serve_fft OK')


if __name__ == '__main__':
    main()
