"""Worker script: continuous multi-shape serving on 16 fake devices.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_serve_drainer_worker.py
Exits 0 on success; prints PASS lines per case.

Covers the acceptance contract on a real multi-device mesh: ONE
background engine (no explicit flush anywhere) serves N producer
threads submitting a mixed stream of >= 3 distinct shapes, complex and
real, forward and inverse, and every output is BIT-IDENTICAL to
per-request plan execution; deadline-only and watermark-only loads
both dispatch; an injected drainer fault re-queues (never drops) and
either retries to success or surfaces on ``result()``.

Every per-request reference is computed BEFORE its engine phase runs:
two host threads executing multi-device collectives concurrently (a
reference ``plan.forward`` racing the drainer's dispatches) can
deadlock XLA's CPU collectives — the engine itself serializes all its
dispatches through the one drainer thread, which is exactly why the
serving path is safe.
"""
import os
import threading

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ["REPRO_SERVE_SCHEDULES"] = ""       # deterministic picks

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft  # noqa: E402
from repro.serve import FFTEngine  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(47)
SHAPES = [(8, 8, 8), (4, 4, 4), (16, 16)]


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


def ref_inverse(plans, shape, real, spec):
    p = plans[(shape, real)]
    return np.asarray(p.inverse(
        jax.device_put(jnp.asarray(spec), p.out_sharding)))


def make_request(i, shape):
    x = RNG.standard_normal(shape).astype(np.float32)
    if i % 2 == 0:
        x = (x + 1j * RNG.standard_normal(shape)).astype(np.complex64)
    return x


def check_concurrent_producers(mesh, plans):
    """3 producer threads x 4 mixed requests plus an inverse each, one
    shared background engine, zero flush() calls: every output
    bit-identical to the precomputed per-request execution."""
    n_threads, per_thread = 3, 4
    work = []                                  # per thread: (reqs, inv)
    for tid in range(n_threads):
        reqs = []
        for i in range(per_thread):
            shape = SHAPES[(tid + i) % len(SHAPES)]
            x = make_request(tid + i, shape)
            reqs.append((shape, x, ref_forward(plans, shape, x)))
        shape, x, spec = reqs[0]
        real = not np.iscomplexobj(x)
        inv = (shape, real, spec, ref_inverse(plans, shape, real, spec))
        work.append((reqs, inv))
    errors = []

    with FFTEngine(mesh=mesh, max_wait_ms=100.0, max_coalesce=4) as eng:

        def producer(tid):
            try:
                reqs, inv = work[tid]
                tickets = [eng.submit(x) for _, x, _ in reqs]
                for (shape, x, want), t in zip(reqs, tickets):
                    got = np.asarray(t.result(timeout=600))
                    assert np.array_equal(got, want), (tid, shape)
                shape, real, spec, want_back = inv
                back = eng.submit(spec, direction='inv',
                                  real=real).result(timeout=600)
                assert np.array_equal(np.asarray(back), want_back), \
                    (tid, 'inv', shape)
            except Exception as e:              # surface on the main thread
                errors.append((tid, repr(e)))

        threads = [threading.Thread(target=producer, args=(tid,))
                   for tid in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errors, errors
    print(f"PASS {n_threads} producer threads x {per_thread} mixed "
          f"requests ({len(SHAPES)} shapes, complex+real, fwd+inv) "
          f"bit-identical, no flush()")


def check_deadline_only(mesh, plans):
    """A watermark that never trips: the 50 ms deadline alone must
    dispatch everything."""
    reqs = []
    for i in range(5):
        shape = SHAPES[i % 2]
        x = make_request(i, shape)
        reqs.append((shape, x, ref_forward(plans, shape, x)))
    with FFTEngine(mesh=mesh, max_wait_ms=50.0, watermark=10**6,
                   max_coalesce=4) as eng:
        tickets = [eng.submit(x) for _, x, _ in reqs]
        for (shape, x, want), t in zip(reqs, tickets):
            assert np.array_equal(np.asarray(t.result(timeout=600)),
                                  want), shape
    print("PASS deadline-only load (watermark never trips) bit-identical")


def check_watermark_only(mesh, plans):
    """No deadline at all: dispatch happens purely on the width
    watermark."""
    shape = SHAPES[0]
    reqs = [make_request(2 * i, shape) for i in range(4)]  # all complex
    wants = [ref_forward(plans, shape, x) for x in reqs]
    with FFTEngine(mesh=mesh, watermark=2, max_coalesce=2) as eng:
        tickets = [eng.submit(x) for x in reqs]
        for want, t in zip(wants, tickets):
            assert np.array_equal(np.asarray(t.result(timeout=600)), want)
    print("PASS watermark-only load (no deadline) bit-identical")


def check_exception_injection(mesh, plans):
    """A drainer fault re-queues the group (never drops it): with
    retries left the retry succeeds bit-identically; with retries
    exhausted the fault surfaces on result()."""
    shape = SHAPES[1]
    x = make_request(0, shape)
    want = ref_forward(plans, shape, x)

    eng = FFTEngine(mesh=mesh, max_wait_ms=20.0, retries=3, max_coalesce=4)
    real_run = eng._run_group
    fails = {'left': 2}

    def flaky(*a, **k):
        if fails['left'] > 0:
            fails['left'] -= 1
            raise RuntimeError("injected drainer fault")
        return real_run(*a, **k)

    eng._run_group = flaky
    with eng:
        got = np.asarray(eng.submit(x).result(timeout=600))
    assert fails['left'] == 0                  # the fault really fired
    assert np.array_equal(got, want)

    eng2 = FFTEngine(mesh=mesh, max_wait_ms=20.0, retries=1, max_coalesce=4)

    def boom(*a, **k):
        raise RuntimeError("persistent drainer fault")

    eng2._run_group = boom
    with eng2:
        t = eng2.submit(x)
        try:
            t.result(timeout=600)
            raise AssertionError("persistent fault must surface on result()")
        except RuntimeError as e:
            assert "persistent drainer fault" in str(e)
    print("PASS drainer exception injection: re-queued + retried to "
          "success; persistent fault surfaces on result()")


def check_donated_inflight_snapshot(mesh, plans):
    """A background engine serving donated jax-array requests: an
    injected post-dispatch fault consumes the donated operands, and the
    retry still succeeds from the in-flight snapshots."""
    shape = SHAPES[1]
    host = make_request(0, shape)
    want = ref_forward(plans, shape, host)
    eng = FFTEngine(mesh=mesh, max_wait_ms=20.0, retries=2, max_coalesce=4)
    real_run = eng._run_group
    state = {'armed': True}

    def run_then_fail(*a, **k):
        out = real_run(*a, **k)
        if state['armed']:
            state['armed'] = False
            raise RuntimeError("post-dispatch fault")
        return out

    eng._run_group = run_then_fail
    p = plans[(shape, False)]
    xj = jax.device_put(jnp.asarray(host), p.in_sharding)
    with eng:
        got = np.asarray(eng.submit(xj).result(timeout=600))
    assert not state['armed']                  # the fault fired
    assert xj.is_deleted()                     # donation still happened
    assert np.array_equal(got, want)
    print("PASS donated in-flight snapshot: post-dispatch fault retried "
          "bit-identically")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    plans = ref_plans(mesh)
    check_concurrent_producers(mesh, plans)
    check_deadline_only(mesh, plans)
    check_watermark_only(mesh, plans)
    check_exception_injection(mesh, plans)
    check_donated_inflight_snapshot(mesh, plans)
    print("SERVE_DRAINER_WORKER_OK")


if __name__ == "__main__":
    main()
