"""Chaos worker: the resilient multi-tenant service on 16 fake
devices under a seeded fault-injection plan.

Run in a *subprocess* (so the main pytest process keeps 1 device):
    python tests/_service_chaos_worker.py
Exits 0 on success; prints PASS lines per case.

The acceptance contract of the fault-injection PR, on a real mesh
over real unix sockets:

* CASE 1 — chaos soak: connection drops, truncated result frames,
  slow reads, accept delays, drainer stalls and clock skew all fire
  mid-stream while three tenants run mixed forward/inverse streams
  through ``FFTClient.transform``. NOTHING hangs, every operand is
  served exactly once, and every served output is BIT-IDENTICAL to
  direct plan execution.
* CASE 2 — fairness: a tenant flooding 3x the victim's load cannot
  push the equal-weight victim's completed share below 40% (weighted
  deficit round-robin), observed via the scheduler-share metrics.
* CASE 3 — idempotent resubmit: a scripted drop of the first RESULT
  frame forces a reconnect+resubmit; the cached result is
  RE-DELIVERED, never recomputed. A mid-flight drop re-attaches
  delivery to the new connection. Idle connections are reaped on the
  heartbeat timeout while keepalive clients survive.
* CASE 4 — brownout: consecutive injected dispatch failures trip the
  circuit breaker; batch traffic sheds with typed
  ``RETRY_AFTER('brownout')`` while interactive traffic still serves;
  after the cooldown a half-open probe closes it and the failed keys
  recompute successfully (failures are never cached).
* CASE 5 — hot reload: an admin RELOAD bumps the config generation,
  re-weights a live tenant and retires a missing one atomically —
  with the retired tenant's inflight request still served.

Every per-request reference is computed BEFORE any service traffic:
two host threads executing multi-device collectives concurrently can
deadlock XLA's CPU collectives — the service serializes all dispatch
through the engine's one drainer thread.
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
from repro.serve import (BrownoutBreaker, FaultPlan, FaultPoint,  # noqa: E402
                         FFTClient, FFTEngine, FFTService, RetryAfter,
                         TenantConfig)
from repro.launch.mesh import make_mesh  # noqa: E402

RNG = np.random.default_rng(101)
SHAPES = [(8, 8, 8), (4, 4, 4)]
TMP = tempfile.mkdtemp(prefix="serve_chaos_")


def sock_path(case):
    return os.path.join(TMP, f"c{case}.sock")


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


def creq(shape):
    return (RNG.standard_normal(shape)
            + 1j * RNG.standard_normal(shape)).astype(np.complex64)


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


def connect(sock, tenant, attempts=6, **kw):
    """Client construction with retry: an armed reader/writer fault
    can kill the handshake itself; a resilient caller just redials."""
    last = None
    for i in range(attempts):
        try:
            return FFTClient(sock, tenant=tenant, **kw)
        except (ConnectionError, OSError) as exc:
            last = exc
            time.sleep(0.02 * (i + 1))
    raise AssertionError(f"could not connect as {tenant!r}: {last}")


# ---------------------------------------------------------------------------
# CASE 1 — chaos soak: faults everywhere, exactly-once, bit-identical
# ---------------------------------------------------------------------------

def case1_chaos_soak(eng, plans):
    streams = {name: make_stream(seed, 12)
               for name, seed in (('alice', 11), ('bob', 12), ('carol', 13))}
    refs = {}                                  # BEFORE any serving
    for name, stream in streams.items():
        for i, (d, x) in enumerate(stream):
            refs[(name, i)] = (ref_forward(plans, x.shape, x) if d == 'fwd'
                               else ref_inverse(plans, x.shape, x))

    plan = FaultPlan(seed=7, points=[
        FaultPoint('service.writer', 'drop', p=0.06, limit=5),
        FaultPoint('service.writer', 'truncate', p=0.04, limit=3),
        FaultPoint('service.reader', 'drop', p=0.02, limit=3),
        FaultPoint('service.reader', 'delay', p=0.05, delay_s=0.02,
                   limit=10),
        FaultPoint('service.accept', 'delay', p=0.3, delay_s=0.01,
                   limit=5),
        FaultPoint('engine.drainer', 'stall', every=25, delay_s=0.05,
                   limit=4),
        FaultPoint('policy.clock', 'skew', every=40, skew_s=5.0, limit=3),
    ])
    sock = sock_path(1)
    svc = FFTService(
        engine=eng, persist_policy=False, faults=plan,
        tenants=[TenantConfig(n, max_inflight=16) for n in streams],
    ).start(sock)
    failures = []

    def run(name, stream):
        try:
            c = connect(sock, name)
            with c:
                for i, (d, x) in enumerate(stream):
                    real = None if d == 'fwd' else False
                    [got] = c.transform([x], direction=d, real=real,
                                        timeout=90.0, deadline_s=90.0)
                    got = np.asarray(got)
                    if not np.array_equal(got, refs[(name, i)]):
                        raise AssertionError(
                            f"{name}[{i}]: served output != direct plan "
                            f"execution under chaos")
        except BaseException as exc:
            failures.append((name, repr(exc)))

    threads = [threading.Thread(target=run, args=(n, s))
               for n, s in streams.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
        assert not t.is_alive(), "chaos soak client thread wedged (hang)"
    assert not failures, failures

    m = svc.metrics()                          # server-side: no wire faults
    for name in streams:
        tm = m['tenants'][name]
        # exactly once: every operand completed, none lost, none redone
        assert tm['completed'] == 12 and tm['failed'] == 0, (name, tm)
    stats = m['service']['faults']
    assert stats is not None and plan.total_fired() > 0, stats
    assert stats['service.writer']['fired'] > 0, stats
    assert stats['engine.drainer']['fired'] > 0, stats
    assert plan.skew_s('policy.clock') > 0, "skew never accumulated"
    svc.close(drain=True)
    eng.faults = None
    print(f"PASS case1: 36 chaos-soaked requests exactly-once and "
          f"bit-identical; {plan.total_fired()} faults fired across "
          f"{sum(1 for s in stats.values() if s['fired'])} sites")


# ---------------------------------------------------------------------------
# CASE 2 — fairness: a flood cannot starve an equal-weight tenant
# ---------------------------------------------------------------------------

def case2_fairness_under_flood(eng, plans):
    shape = SHAPES[0]
    victim_reqs = [creq(shape) for _ in range(16)]
    victim_refs = [ref_forward(plans, shape, x) for x in victim_reqs]
    flood_x = creq(shape)
    flood_ref = ref_forward(plans, shape, flood_x)

    eng.set_drainer(watermark=2, max_wait_ms=5.0)
    sock = sock_path(2)
    svc = FFTService(
        engine=eng, persist_policy=False, policy=None,
        max_inflight=256, sched_window=2,
        tenants=[TenantConfig('victim', max_inflight=64),
                 TenantConfig('flood', max_inflight=64)],
    ).start(sock)
    with connect(sock, 'flood') as cf, connect(sock, 'victim') as cv:
        flood_tix = [cf.submit(flood_x) for _ in range(48)]
        victim_tix = [cv.submit(x) for x in victim_reqs]
        for t, ref in zip(victim_tix, victim_refs):
            assert np.array_equal(np.asarray(t.result(timeout=600)), ref)
        # snapshot at the instant the victim's own stream finished:
        # the flood may not have completed more than ~1.5x the victim
        m = svc.metrics()
        done_v = m['tenants']['victim']['completed']
        done_f = m['tenants']['flood']['completed']
        share = done_v / (done_v + done_f)
        assert share >= 0.40, (done_v, done_f, share)
        sched = m['service']['scheduler']
        assert sched['window'] == 2
        assert sched['shares']['victim'] >= 0.40, sched['shares']
        for t in flood_tix:                    # then let the flood drain
            assert np.array_equal(np.asarray(t.result(timeout=600)),
                                  flood_ref)
    svc.close(drain=True)
    print(f"PASS case2: victim completed share {share:.2f} >= 0.40 "
          f"under a 3x flood (victim {done_v}, flood {done_f})")


# ---------------------------------------------------------------------------
# CASE 3 — idempotent resubmit, re-attach, heartbeat reaping
# ---------------------------------------------------------------------------

def case3_idempotent_resubmit(eng, plans):
    shape = SHAPES[0]
    xs = [creq(shape) for _ in range(4)]
    refs = [ref_forward(plans, shape, x) for x in xs]
    # compile the single-request executable up front: a first request
    # that spends longer than the 1 s heartbeat timeout compiling gets
    # its connection reaped while inflight (re-attach, not re-delivery)
    eng.transform([xs[3]])

    eng.set_drainer(watermark=1, max_wait_ms=5.0)
    # scripted: the FIRST result frame (writer hit 1, after HELLO_OK
    # at hit 0) is dropped on the floor
    plan = FaultPlan(points=[FaultPoint('service.writer', 'drop',
                                        at=[1])])
    sock = sock_path(3)
    svc = FFTService(
        engine=eng, persist_policy=False, policy=None, faults=plan,
        heartbeat_timeout_s=1.0,
        tenants=[TenantConfig('idem', max_inflight=16)],
    ).start(sock)

    # -- A: dropped RESULT -> reconnect -> re-delivered, not recomputed
    c1 = FFTClient(sock, tenant='idem')
    [got] = c1.transform([xs[0]], timeout=60.0, deadline_s=60.0)
    assert np.array_equal(np.asarray(got), refs[0])
    assert c1.reconnects == 1, c1.reconnects
    m = svc.metrics()
    d = m['service']['dedup']
    assert d['redelivered'] == 1 and d['hits'] == 1, d
    tm = m['tenants']['idem']
    assert tm['scheduled'] == 1 and tm['completed'] == 1, tm

    # -- B: mid-flight drop -> resubmit re-ATTACHES delivery
    eng.set_drainer(watermark=10**6, max_wait_ms=None)   # hold in queue
    c1.submit(xs[1], key='manual/7')
    deadline = time.monotonic() + 30
    while svc._inflight_total < 1:             # admitted & held
        assert time.monotonic() < deadline
        time.sleep(0.005)
    c1.close()                                 # the submitter vanishes
    c2 = FFTClient(sock, tenant='idem')
    t2 = c2.submit(xs[1], key='manual/7')      # same key: re-attach
    eng.flush()                                # now let it ripen
    assert np.array_equal(np.asarray(t2.result(timeout=60)), refs[1])
    m = svc.metrics()
    assert m['service']['dedup']['reattached'] == 1, m['service']['dedup']
    assert m['tenants']['idem']['scheduled'] == 2, m['tenants']['idem']
    c2.close()
    eng.set_drainer(watermark=1, max_wait_ms=5.0)

    # -- C: idle connections reaped; keepalive clients survive
    c3 = FFTClient(sock, tenant='idem')                    # no heartbeat
    c4 = FFTClient(sock, tenant='idem', heartbeat_s=0.2)   # keepalive
    time.sleep(1.6)                            # > heartbeat_timeout_s
    [g3] = c3.transform([xs[2]], timeout=60.0, deadline_s=60.0)
    assert np.array_equal(np.asarray(g3), refs[2])
    assert c3.reconnects >= 1, "idle connection was never reaped"
    [g4] = c4.transform([xs[3]], timeout=60.0, deadline_s=60.0)
    assert np.array_equal(np.asarray(g4), refs[3])
    assert c4.reconnects == 0, "keepalive client should have survived"
    c3.close()
    c4.close()
    svc.close(drain=True)
    eng.faults = None
    print("PASS case3: dropped RESULT re-delivered (1 dispatch), "
          "mid-flight drop re-attached, idle conn reaped, keepalive "
          "survived")


# ---------------------------------------------------------------------------
# CASE 4 — brownout: breaker trips, sheds batch, recovers
# ---------------------------------------------------------------------------

def case4_brownout(eng, plans):
    shape = SHAPES[0]
    xb, xl = creq(shape), creq(shape)
    rb = ref_forward(plans, shape, xb)
    rl = ref_forward(plans, shape, xl)

    eng.set_drainer(watermark=1, max_wait_ms=2.0)
    # the engine itself retries a blamed group once (retries=1), so a
    # ticket only fails after TWO consecutive dispatch faults: six
    # scripted fires = three consecutive ticket failures
    plan = FaultPlan(points=[FaultPoint('engine.dispatch', 'raise',
                                        at=[0, 1, 2, 3, 4, 5])])
    breaker = BrownoutBreaker(failure_threshold=3, overload_trip=10**6,
                              cooldown_s=0.5, probe_quota=1)
    sock = sock_path(4)
    svc = FFTService(
        engine=eng, persist_policy=False, policy=None, faults=plan,
        brownout=breaker,
        tenants=[TenantConfig('bat', slo='batch', max_inflight=16),
                 TenantConfig('live', slo='interactive', max_inflight=16)],
    ).start(sock)
    with FFTClient(sock, tenant='bat') as cb, \
            FFTClient(sock, tenant='live') as cl:
        for i in range(3):                     # injected dispatch faults
            t = cb.submit(xb, key=f'k{i}')
            try:
                t.result(timeout=60)
                raise AssertionError("injected dispatch fault vanished")
            except RuntimeError as exc:
                assert 'FaultInjected' in str(exc), exc
        # tripped: batch sheds with a typed reason, interactive serves
        try:
            cb.submit(xb).result(timeout=60)
            raise AssertionError("open breaker did not shed batch")
        except RetryAfter as ra:
            assert ra.reason == 'brownout' and ra.retry_after_ms >= 1.0
        assert np.array_equal(
            np.asarray(cl.submit(xl).result(timeout=60)), rl)
        m = svc.metrics()
        br = m['service']['breaker']
        assert br['state'] == 'open', br
        assert br['transitions'].get('closed_to_open') == 1, br
        assert m['tenants']['bat']['rejected'].get('brownout', 0) >= 1
        assert m['tenants']['live']['rejected'] == {}

        time.sleep(0.6)                        # past the cooldown
        # the failed keys were FORGOTTEN (failures are never cached):
        # the same keys now recompute — and the first is the half-open
        # probe whose success closes the breaker
        for i in range(3):
            got = np.asarray(cb.submit(xb, key=f'k{i}').result(timeout=60))
            assert np.array_equal(got, rb), f"k{i} retry not identical"
        m = svc.metrics()
        br = m['service']['breaker']
        assert br['state'] == 'closed', br
        assert br['transitions'].get('open_to_half_open') == 1, br
        assert br['transitions'].get('half_open_to_closed') == 1, br
        assert m['tenants']['bat']['completed'] == 3
        assert m['tenants']['bat']['failed'] == 3
    svc.close(drain=True)
    eng.faults = None
    print("PASS case4: 3 injected dispatch faults tripped the breaker, "
          "batch shed typed 'brownout', interactive served, half-open "
          "probe closed it and the failed keys recomputed bit-identical")


# ---------------------------------------------------------------------------
# CASE 5 — hot tenant-config reload
# ---------------------------------------------------------------------------

def case5_hot_reload(eng, plans):
    shape = SHAPES[0]
    xo, xw = creq(shape), creq(shape)
    ro = ref_forward(plans, shape, xo)
    rw = ref_forward(plans, shape, xw)

    eng.set_drainer(watermark=10**6, max_wait_ms=None)   # hold inflight
    sock = sock_path(5)
    svc = FFTService(
        engine=eng, persist_policy=False, policy=None,
        tenants=[TenantConfig('root', admin=True),
                 TenantConfig('w1'),
                 TenantConfig('old')],
    ).start(sock)
    c_old = FFTClient(sock, tenant='old')
    held = c_old.submit(xo)                    # inflight across the reload
    deadline = time.monotonic() + 30
    while svc._inflight_total < 1:
        assert time.monotonic() < deadline
        time.sleep(0.005)

    with FFTClient(sock, tenant='root') as c_root, \
            FFTClient(sock, tenant='w1') as c_w1:
        new_cfgs = [TenantConfig('root', admin=True),
                    TenantConfig('w1', weight=5.0, max_inflight=32)]
        try:                                   # non-admins are refused
            c_w1.reload(new_cfgs)
            raise AssertionError("non-admin RELOAD accepted")
        except RuntimeError as exc:
            assert 'admin' in str(exc), exc
        ok = c_root.reload(new_cfgs, retire_missing=True)
        assert ok['generation'] == 1, ok
        assert sorted(ok['tenants']) == ['root', 'w1'], ok

        m = svc.metrics()
        assert m['service']['reload_generation'] == 1
        assert m['tenants']['w1']['weight'] == 5.0
        assert m['tenants']['old']['retired'] is True

        # retired: new connections refused, new submits refused ...
        try:
            FFTClient(sock, tenant='old')
            raise AssertionError("retired tenant reconnected")
        except PermissionError as exc:
            assert 'retired' in str(exc), exc
        try:
            c_old.submit(xo).result(timeout=60)
            raise AssertionError("retired tenant submitted")
        except RuntimeError as exc:
            assert 'retired' in str(exc), exc
        # ... but the request admitted BEFORE the reload still serves
        eng.flush()
        assert np.array_equal(np.asarray(held.result(timeout=60)), ro)

        # the re-weighted tenant keeps serving; a second reload bumps
        # the generation again
        eng.set_drainer(watermark=1, max_wait_ms=5.0)
        assert np.array_equal(
            np.asarray(c_w1.submit(xw).result(timeout=60)), rw)
        assert c_root.reload(new_cfgs)['generation'] == 2
    c_old.close()
    svc.close(drain=True)
    print("PASS case5: RELOAD generation 1->2, w1 re-weighted to 5.0, "
          "'old' retired atomically with its inflight request served")


def main():
    mesh = make_mesh((4, 4), ("x", "y"))
    plans = ref_plans(mesh)
    with FFTEngine(mesh=mesh, max_wait_ms=20.0,
                   schedule_table=None) as eng:
        case1_chaos_soak(eng, plans)
        case2_fairness_under_flood(eng, plans)
        case3_idempotent_resubmit(eng, plans)
        case4_brownout(eng, plans)
        case5_hot_reload(eng, plans)
    print("SERVICE_CHAOS_WORKER_OK")


if __name__ == "__main__":
    main()
