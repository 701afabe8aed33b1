"""Worker script: the multi-tenant FFT service on 16 fake devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_serve_service_worker.py
Exits 0 on success; prints PASS lines per case.

Covers the acceptance contract on a real multi-device mesh, over a
real unix socket:

* CASE 1 — three tenants stream mixed shapes/kinds (complex and real,
  forward and inverse) concurrently and every served output is
  BIT-IDENTICAL to direct per-request plan execution.
* CASE 2 — one tenant saturates its inflight quota: it observes typed
  RETRY_AFTER backpressure while a well-behaved tenant keeps serving
  with zero rejections and an un-degraded p99.
* CASE 3 — SLO classes order the wire: batch-class requests sit out a
  long coalescing wait until one interactive-class request's deadline
  ripens the shared queue and the whole group dispatches promptly.

Every per-request reference is computed BEFORE any service traffic:
two host threads executing multi-device collectives concurrently can
deadlock XLA's CPU collectives — the service serializes all dispatch
through the engine's one drainer thread, which is exactly why the
serving path is safe.
"""
import os
import tempfile
import threading
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["REPRO_SERVE_SCHEDULES"] = ""       # deterministic picks

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro.serve import (FFTClient, FFTEngine, FFTService,  # noqa: E402
                         RetryAfter, SLOClass, TenantConfig)
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(53)
SHAPES = [(8, 8, 8), (4, 4, 4), (16, 16)]
SOCK = os.path.join(tempfile.mkdtemp(prefix="serve_service_"), "s.sock")


def ref_plans(mesh):
    plans = {}
    for shape in SHAPES:
        plans[(shape, False)] = fft.plan(shape, mesh, donate=False)
        plans[(shape, True)] = fft.rplan(shape, mesh)
    return plans


def ref_forward(plans, shape, x):
    p = plans[(shape, not np.iscomplexobj(x))]
    return np.asarray(
        p.forward(jax.device_put(jnp.asarray(x), p.in_sharding)))


def ref_inverse(plans, shape, spec):
    p = plans[(shape, False)]
    return np.asarray(p.inverse(
        jax.device_put(jnp.asarray(spec), p.out_sharding)))


def make_stream(seed, count):
    """(kind, operand) pairs: rotating shapes, complex/real forward
    plus a complex inverse every 5th request."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(count):
        shape = SHAPES[i % len(SHAPES)]
        if i % 5 == 4:
            spec = (rng.standard_normal(shape)
                    + 1j * rng.standard_normal(shape)).astype(np.complex64)
            out.append(('inv', spec))
        elif i % 2:
            x = (rng.standard_normal(shape)
                 + 1j * rng.standard_normal(shape)).astype(np.complex64)
            out.append(('fwd', x))
        else:
            out.append(('fwd',
                        rng.standard_normal(shape).astype(np.float32)))
    return out


def case1_multi_tenant_bit_identity(eng, plans):
    streams = {name: make_stream(seed, 10)
               for name, seed in (('alice', 1), ('bob', 2), ('carol', 3))}
    refs = {}                                  # BEFORE any serving
    for name, stream in streams.items():
        for i, (d, x) in enumerate(stream):
            refs[(name, i)] = (ref_forward(plans, x.shape, x) if d == 'fwd'
                               else ref_inverse(plans, x.shape, x))

    svc = FFTService(
        engine=eng, persist_policy=False,
        tenants=[TenantConfig(n, max_inflight=16) for n in streams],
    ).start(SOCK)
    failures = []

    def run(name, stream):
        try:
            with FFTClient(SOCK, tenant=name) as c:
                tickets = []
                for d, x in stream:
                    real = None if d == 'fwd' else False
                    tickets.append(c.submit(x, direction=d, real=real))
                for i, t in enumerate(tickets):
                    got = np.asarray(t.result(timeout=600))
                    if not np.array_equal(got, refs[(name, i)]):
                        raise AssertionError(
                            f"{name}[{i}]: served output != direct plan "
                            f"execution (max abs diff "
                            f"{np.abs(got - refs[(name, i)]).max():g})")
                c.drain(timeout=120)
        except BaseException as exc:
            failures.append((name, repr(exc)))

    threads = [threading.Thread(target=run, args=(n, s))
               for n, s in streams.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
        assert not t.is_alive(), "client thread wedged"
    assert not failures, failures

    with FFTClient(SOCK, tenant='alice') as probe:
        m = probe.metrics()
    for name in streams:
        tm = m['tenants'][name]
        assert tm['completed'] == 10 and tm['failed'] == 0, (name, tm)
        assert tm['rejected'] == {}, (name, tm)
    assert m['service']['dispatch']['groups'] > 0
    svc.close(drain=True)
    for name in streams:
        print(f"PASS case1 {name}: 10 mixed requests bit-identical, "
              f"0 rejections")


def case2_quota_isolation(eng, plans):
    shape = SHAPES[0]
    good_reqs = [(RNG.standard_normal(shape)
                  + 1j * RNG.standard_normal(shape)).astype(np.complex64)
                 for _ in range(8)]
    good_refs = [ref_forward(plans, shape, x) for x in good_reqs]
    flood_x = (RNG.standard_normal(shape)
               + 1j * RNG.standard_normal(shape)).astype(np.complex64)
    _ = ref_forward(plans, shape, flood_x)     # warm nothing extra

    svc = FFTService(
        engine=eng, persist_policy=False,
        tenants=[TenantConfig('good', max_inflight=8),
                 TenantConfig('flood', max_inflight=2)],
    ).start(SOCK)

    def serve_good(latencies):
        with FFTClient(SOCK, tenant='good') as c:
            for x, ref in zip(good_reqs, good_refs):
                t0 = time.monotonic()
                got = np.asarray(c.submit(x).result(timeout=600))
                latencies.append((time.monotonic() - t0) * 1e3)
                assert np.array_equal(got, ref)

    # baseline: the good tenant alone
    base = []
    serve_good(base)

    # under flood: 'flood' fire-hoses far past its quota of 2 while the
    # good tenant keeps its sequential stream going
    flood_stats = {'rejected': 0, 'served': 0}
    underf = []

    def run_flood():
        with FFTClient(SOCK, tenant='flood') as c:
            tickets = [c.submit(flood_x) for _ in range(60)]
            for t in tickets:
                try:
                    t.result(timeout=600)
                    flood_stats['served'] += 1
                except RetryAfter as ra:
                    assert ra.reason in ('tenant_quota', 'rate'), ra
                    assert ra.retry_after_ms > 0
                    flood_stats['rejected'] += 1

    tf = threading.Thread(target=run_flood)
    tg = threading.Thread(target=serve_good, args=(underf,))
    tf.start()
    tg.start()
    for t in (tf, tg):
        t.join(timeout=900)
        assert not t.is_alive(), "case2 thread wedged"

    assert flood_stats['rejected'] > 0, flood_stats
    assert flood_stats['served'] >= 2, flood_stats

    def p99(v):
        s = sorted(v)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    # isolation: the good tenant saw zero rejections and its p99 is
    # not degraded beyond noise (generous bound: 10x baseline + 500ms)
    with FFTClient(SOCK, tenant='good') as probe:
        m = probe.metrics()
    assert m['tenants']['good']['rejected'] == {}, m['tenants']['good']
    assert m['tenants']['flood']['rejected'], m['tenants']['flood']
    bound = 10.0 * p99(base) + 500.0
    assert p99(underf) <= bound, (p99(base), p99(underf), bound)
    svc.close(drain=True)
    print(f"PASS case2: flood rejected={flood_stats['rejected']} "
          f"served={flood_stats['served']}; good p99 "
          f"{p99(underf):.1f}ms <= {bound:.1f}ms (baseline "
          f"{p99(base):.1f}ms), 0 rejections")


def case3_slo_ordering(eng, plans):
    shape = SHAPES[0]
    xs = [(RNG.standard_normal(shape)
           + 1j * RNG.standard_normal(shape)).astype(np.complex64)
          for _ in range(4)]
    refs = [ref_forward(plans, shape, x) for x in xs]

    eng.set_drainer(watermark=16, max_wait_ms=None)
    svc = FFTService(
        engine=eng, persist_policy=False, policy=None,
        slo_classes={
            'batch': SLOClass('batch', deadline_ms=120000,
                              max_wait_ms=30000),
            'rush': SLOClass('rush', deadline_ms=200, max_wait_ms=1.0),
        },
        tenants=[TenantConfig('mix', max_inflight=8, slo='batch')],
    ).start(SOCK)
    with FFTClient(SOCK, tenant='mix') as c:
        t0 = time.monotonic()
        batch = [c.submit(x) for x in xs[:3]]  # 30s wait: they sit
        time.sleep(0.3)
        assert not any(t.done for t in batch), \
            "batch requests dispatched before any deadline/watermark"
        rush = c.submit(xs[3], slo='rush')     # 1ms deadline: ripens all
        outs = [np.asarray(t.result(timeout=600))
                for t in batch + [rush]]
        dt = time.monotonic() - t0
        for got, ref in zip(outs, refs):
            assert np.array_equal(got, ref)
        # far sooner than the 30s batch wait: the interactive deadline
        # ordered the whole shared queue
        assert dt < 20.0, f"queue ripened in {dt:.1f}s (batch wait 30s)"
        c.drain(timeout=120)
    svc.close(drain=True)
    print(f"PASS case3: 3 batch + 1 rush dispatched together in "
          f"{dt:.2f}s (<< 30s batch wait), bit-identical")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    plans = ref_plans(mesh)
    with FFTEngine(mesh=mesh, max_wait_ms=20.0,
                   schedule_table=None) as eng:
        case1_multi_tenant_bit_identity(eng, plans)
        case2_quota_isolation(eng, plans)
        case3_slo_ordering(eng, plans)
    print("SERVE_SERVICE_WORKER_OK")


if __name__ == "__main__":
    main()
