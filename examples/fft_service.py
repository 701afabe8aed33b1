"""Two tenants share one FFT engine through the multi-tenant service.

Mirrors examples/serve_fft.py one layer up the stack: instead of
calling :class:`FFTEngine` in-process, clients connect to an
:class:`FFTService` over a unix socket and speak the length-prefixed
frame protocol (``repro.serve.protocol``). The service multiplexes
every connection onto ONE shared engine — all tenants' requests
coalesce into the same batched dispatches — while keeping the tenants
isolated at the edge:

* ``ana`` is an *interactive* tenant: small quota, tight SLO deadline.
  Her requests carry a short drainer wait, so a lone request never
  sits out a long coalescing window.
* ``bulk`` is a *batch* tenant with a tiny inflight quota: fire-hosing
  past it earns typed ``RetryAfter`` backpressure (with a retry hint)
  instead of queue bloat, and ana's latency is untouched.

The adaptive drainer policy watches the combined arrival rate and
retargets the engine's (watermark, max_wait_ms) as load changes.
Outputs are bit-identical to per-request plan execution — the service
only changes who may enter and when groups dispatch, never the math.

    PYTHONPATH=src python examples/fft_service.py --n 16 --requests 10
"""
import argparse
import os
import tempfile
import threading

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

import repro.fft as fft         # noqa: E402
from repro.serve import (FFTClient, FFTService, RetryAfter,  # noqa: E402
                         TenantConfig)
from repro.launch.mesh import make_mesh  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--n', type=int, default=16)
    ap.add_argument('--requests', type=int, default=10)
    args = ap.parse_args()
    n = args.n
    mesh = make_mesh((4, 4), ('x', 'y'))
    shapes = [(n, n, n), (n, n)]
    rng = np.random.default_rng(7)

    reqs = []
    for i in range(args.requests):
        x = rng.standard_normal(shapes[i % len(shapes)]).astype(np.float32)
        if i % 2:
            x = (x + 1j * rng.standard_normal(x.shape)).astype(np.complex64)
        reqs.append(x)

    sock = os.path.join(tempfile.mkdtemp(prefix='fft_service_'), 's.sock')
    svc = FFTService(
        mesh=mesh, schedule_table=None,
        tenants=[TenantConfig('ana', max_inflight=4, slo='interactive'),
                 TenantConfig('bulk', max_inflight=2, slo='batch')],
    ).start(sock)
    try:
        # -- ana: mixed interactive stream, verified bit-identical -----
        with FFTClient(sock, tenant='ana') as ana:
            outs = ana.transform(reqs)           # retries RetryAfter
            for x, y in zip(reqs, outs):
                p = (fft.plan(x.shape, mesh, donate=False)
                     if np.iscomplexobj(x) else fft.rplan(x.shape, mesh))
                ref = p.forward(
                    jax.device_put(jnp.asarray(x), p.in_sharding))
                assert np.array_equal(np.asarray(y), np.asarray(ref))
            print(f"[fft_service] ana: {len(reqs)} mixed requests over "
                  f"the socket, bit-identical to per-request plans")

            # -- bulk floods past its quota while ana keeps serving ----
            stats = {'served': 0, 'rejected': 0}

            def flood():
                with FFTClient(sock, tenant='bulk') as bulk:
                    tickets = [bulk.submit(reqs[0]) for _ in range(12)]
                    for t in tickets:
                        try:
                            t.result(timeout=600)
                            stats['served'] += 1
                        except RetryAfter as ra:
                            assert ra.retry_after_ms > 0
                            stats['rejected'] += 1

            th = threading.Thread(target=flood)
            th.start()
            ana_outs = ana.transform(reqs[:4])
            th.join(timeout=600)
            assert len(ana_outs) == 4 and not th.is_alive()

            m = ana.metrics()
            assert m['tenants']['ana']['rejected'] == {}
            lat = m['tenants']['ana']['latency_ms'].get('interactive', {})
            print(f"[fft_service] bulk: served={stats['served']} "
                  f"rejected={stats['rejected']} (quota 2, typed "
                  f"backpressure); ana: 0 rejections, "
                  f"p99 {lat.get('p99_ms', float('nan')):.1f}ms")
            pol = m['service'].get('policy')
            if pol:
                print(f"  adaptive policy: level={pol['load_level']} "
                      f"watermark={pol['watermark']} "
                      f"wait={pol['max_wait_ms']:.1f}ms "
                      f"(rate {pol['rate_per_s']:.0f}/s)")
    finally:
        svc.close(drain=True)
    print('fft_service OK')


if __name__ == '__main__':
    main()
