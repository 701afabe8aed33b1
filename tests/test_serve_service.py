"""The multi-tenant FFT service stack: wire protocol, adaptive drainer
policy, admission control / SLO / backpressure semantics, and the
engine+cache seams they ride on.

In-process tests run on a 1x1 mesh over real unix sockets (handshake,
round trips, typed RETRY_AFTER, token auth, metrics, drain). The
16-fake-device matrix — 3 tenants x mixed shapes/kinds bit-identical
to direct plan execution, quota saturation isolation, SLO-class
ordering — runs in a subprocess (tests/_serve_service_worker.py)."""
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.comm import cost as ccost
from repro.serve import (AdaptivePolicy, FFTClient, FFTEngine, FFTService,
                         LRUPlanCache, RateEstimator, ResultTimeout,
                         RetryAfter, SLOClass, TenantConfig)
from repro.serve import protocol as proto
from repro.launch.mesh import make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RNG = np.random.default_rng(29)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


@pytest.fixture()
def sock_path(tmp_path):
    return str(tmp_path / "fft.sock")


def _creq(shape):
    return (RNG.standard_normal(shape)
            + 1j * RNG.standard_normal(shape)).astype(np.complex64)


# ---------------------------------------------------------------------------
# Protocol: frame round trips and rejections
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", sorted(proto.WIRE_DTYPES))
def test_frame_round_trip_every_wire_dtype(dtype):
    x = np.arange(24, dtype=dtype).reshape(2, 3, 4)
    buf = proto.pack_frame(proto.SUBMIT, {'req_id': 7, 'direction': 'fwd'},
                           [x])
    msg_type, meta, arrays, consumed = proto.unpack_frame(buf)
    assert consumed == len(buf)
    assert msg_type == proto.SUBMIT
    assert meta == {'req_id': 7, 'direction': 'fwd'}
    assert arrays[0].dtype == np.dtype(dtype)
    np.testing.assert_array_equal(arrays[0], x)


def test_frame_round_trip_forms():
    # no arrays, one array, planar pair, scalar-shaped array
    for arrays in ([], [np.array(3.5, dtype=np.float32)],
                   [_creq((4, 4))],
                   [RNG.standard_normal((4, 4)).astype(np.float32),
                    RNG.standard_normal((4, 4)).astype(np.float32)]):
        buf = proto.pack_frame(proto.RESULT, {'req_id': 1}, arrays)
        _, _, out, _ = proto.unpack_frame(buf)
        assert len(out) == len(arrays)
        for a, b in zip(arrays, out):
            np.testing.assert_array_equal(np.asarray(a), b)


def test_decoded_arrays_are_zero_copy_read_only():
    buf = proto.pack_frame(proto.RESULT, {}, [_creq((8, 8))])
    _, _, [a], _ = proto.unpack_frame(buf)
    assert not a.flags.writeable
    with pytest.raises((ValueError, RuntimeError)):
        a[0, 0] = 0


def test_truncated_frames_rejected():
    buf = proto.pack_frame(proto.SUBMIT, {'req_id': 1}, [_creq((4, 4))])
    for cut in (3, proto._HEADER.size - 1, proto._HEADER.size + 2,
                len(buf) - 1):
        with pytest.raises(proto.ProtocolError, match="truncated"):
            proto.unpack_frame(buf[:cut])


def test_version_mismatch_is_typed():
    buf = bytearray(proto.pack_frame(proto.HELLO, {'tenant': 'a'}))
    buf[4] = proto.PROTOCOL_VERSION + 1      # the version byte
    with pytest.raises(proto.VersionMismatch):
        proto.unpack_frame(bytes(buf))
    # and VersionMismatch IS a ProtocolError (one except clause catches
    # both when the caller does not care)
    assert issubclass(proto.VersionMismatch, proto.ProtocolError)


def test_bad_magic_rejected():
    buf = bytearray(proto.pack_frame(proto.HELLO, {}))
    buf[:4] = b'EVIL'
    with pytest.raises(proto.ProtocolError, match="magic"):
        proto.unpack_frame(bytes(buf))


def test_non_wire_dtypes_rejected_both_ways():
    with pytest.raises(proto.ProtocolError, match="not wire-safe"):
        proto.encode_arrays([np.array(['a', 'b'])])
    with pytest.raises(proto.ProtocolError, match="not wire-safe"):
        proto.encode_arrays([np.array([object()])])
    # a frame *declaring* a non-wire dtype is rejected on decode even
    # though the bytes themselves are innocuous
    with pytest.raises(proto.ProtocolError, match="non-wire dtype"):
        proto.decode_arrays([{'dtype': 'object', 'shape': [1],
                              'nbytes': 8}], b'\0' * 8, 0)


def test_lying_descriptors_rejected():
    with pytest.raises(proto.ProtocolError, match="claims"):
        proto.decode_arrays([{'dtype': 'float32', 'shape': [4],
                              'nbytes': 12}], b'\0' * 12, 0)
    with pytest.raises(proto.ProtocolError, match="trailing"):
        proto.decode_arrays([{'dtype': 'float32', 'shape': [2],
                              'nbytes': 8}], b'\0' * 12, 0)
    with pytest.raises(proto.ProtocolError, match="negative"):
        proto.decode_arrays([{'dtype': 'float32', 'shape': [-2],
                              'nbytes': 8}], b'\0' * 8, 0)


def test_oversize_frame_rejected_without_allocation():
    head = proto._HEADER.pack(proto.MAGIC, proto.PROTOCOL_VERSION,
                              proto.SUBMIT, 0, proto.MAX_FRAME_BYTES + 1)
    with pytest.raises(proto.ProtocolError, match="cap"):
        proto._parse_header(head)


def test_socket_eof_semantics():
    a, b = socket.socketpair()
    # clean close at a frame boundary: None, not an exception
    frame = proto.pack_frame(proto.HELLO, {'tenant': 't'})
    a.sendall(frame)
    a.close()
    assert proto.recv_frame(b)[0] == proto.HELLO
    assert proto.recv_frame(b) is None
    b.close()
    # EOF mid-frame: a typed truncation error
    a, b = socket.socketpair()
    a.sendall(frame[:len(frame) - 3])
    a.close()
    with pytest.raises(proto.ProtocolError, match="EOF|truncated"):
        proto.recv_frame(b)
    b.close()


# ---------------------------------------------------------------------------
# Adaptive policy: rate estimator + decisions + persistence
# ---------------------------------------------------------------------------

def test_rate_estimator_monotone_in_events():
    t0 = 1000.0
    a, b = RateEstimator(tau_s=0.5), RateEstimator(tau_s=0.5)
    a.observe(5, t0)
    b.observe(9, t0)
    assert b.rate(t0) > a.rate(t0)
    # more events at the same instant never lower the estimate
    r_before = a.rate(t0)
    a.observe(1, t0)
    assert a.rate(t0) > r_before


def test_rate_estimator_decays_while_idle():
    est = RateEstimator(tau_s=0.5)
    est.observe(50, 1000.0)
    r0 = est.rate(1000.0)
    r1 = est.rate(1000.5)
    r2 = est.rate(1002.0)
    assert r0 > r1 > r2 > 0
    assert RateEstimator().rate() == 0.0     # before any observation


def test_rate_estimator_converges_to_arrival_rate():
    est = RateEstimator(tau_s=0.5)
    for i in range(2000):                    # 100 events/s for 20s
        est.observe(1, 1000.0 + i * 0.01)
    assert est.rate(1020.0) == pytest.approx(100.0, rel=0.1)


def test_policy_never_exceeds_max_coalesce():
    pol = AdaptivePolicy(max_coalesce=8, max_wait_ms=50.0)
    t = 1000.0
    for burst in (0, 1, 10, 1000, 100000):
        pol.observe(burst, t)
        d = pol.decide(t)
        assert 1 <= d.watermark <= 8
        assert (pol.min_wait_ms <= d.max_wait_ms <= pol.max_wait_ms)
        t += 0.001
    # even a seeded row beyond the cap is clamped
    pol2 = AdaptivePolicy(max_coalesce=4)
    pol2._levels[2] = (64, 10.0)
    pol2.observe(100000, t)
    assert pol2.decide(t).watermark <= 4


def test_policy_load_levels_monotone_in_rate():
    pol = AdaptivePolicy(max_coalesce=16, max_wait_ms=50.0)
    rates = [0.0, 10.0, 100.0, 1000.0, 100000.0]
    levels = [pol.load_level(r) for r in rates]
    assert levels == sorted(levels)
    assert levels[0] == 0
    assert levels[-1] == pol.n_levels - 1


def test_policy_rows_persist_and_seed_round_trip(tmp_path):
    path = str(tmp_path / "sched.json")
    pol = AdaptivePolicy(max_coalesce=16, max_wait_ms=50.0)
    t = 1000.0
    for burst in (0, 40, 4000):              # visit several load levels
        pol.observe(burst, t)
        pol.decide(t)
        pol.note_latency(123.0, t)
        t += 0.0005
    rows = pol.rows({'x': 4, 'y': 4}, (32, 32), 'complex', 'auto',
                    backend='cpu')
    assert len(rows) >= 2
    assert all(isinstance(r['load'], int) for r in rows)
    ccost.persist_schedule_rows(rows, path)

    table = ccost.ScheduleTable.load(path)
    fresh = AdaptivePolicy(max_coalesce=16, max_wait_ms=50.0)
    seeded = fresh.seed(table, {'x': 4, 'y': 4}, (32, 32), 'complex',
                        'auto', backend='cpu')
    assert seeded == len(rows)
    assert fresh._levels == pol._levels
    # the engine's load-less lookup NEVER sees policy rows: the load
    # tag separates the namespaces
    assert table.lookup({'x': 4, 'y': 4}, (32, 32), 'complex',
                        'auto') is None


def test_schedule_table_load_keyed_lookup():
    base = dict(mesh='4x4', shape='32x32', kind='complex',
                strategy='auto', overlap_chunks=1)
    table = ccost.ScheduleTable([
        dict(base, coalesce_width=2, us_per_request=10.0),
        dict(base, coalesce_width=4, load=1, us_per_request=20.0),
        dict(base, coalesce_width=8, load=3, us_per_request=30.0),
    ])
    ms, sh = {'x': 4, 'y': 4}, (32, 32)
    # load=None -> only the untagged row
    assert table.lookup(ms, sh, 'complex', 'auto')['coalesce_width'] == 2
    # exact tagged level
    assert table.lookup(ms, sh, 'complex', 'auto',
                        load=1)['coalesce_width'] == 4
    # nearest tagged level when the exact one is absent
    assert table.lookup(ms, sh, 'complex', 'auto',
                        load=2)['coalesce_width'] == 4
    assert table.lookup(ms, sh, 'complex', 'auto',
                        load=7)['coalesce_width'] == 8
    # tagged query with only untagged rows: fall back, never miss
    t2 = ccost.ScheduleTable([dict(base, coalesce_width=2)])
    assert t2.lookup(ms, sh, 'complex', 'auto',
                     load=3)['coalesce_width'] == 2


# ---------------------------------------------------------------------------
# Satellite regressions: cache poison, ticket timeout, dead drainer
# ---------------------------------------------------------------------------

def test_lru_on_evict_exception_does_not_poison_cache():
    calls = []

    def bad_hook(key, value):
        calls.append(key)
        raise RuntimeError("hook boom")

    cache = LRUPlanCache(max_entries=2, on_evict=bad_hook)
    cache.put('a', 1, nbytes=10)
    cache.put('b', 2, nbytes=10)
    with pytest.warns(RuntimeWarning, match="on_evict hook failed"):
        cache.put('c', 3, nbytes=10)         # evicts 'a', hook raises
    assert calls == ['a']
    assert cache.evict_errors == 1 and cache.evictions == 1
    # the cache is NOT poisoned: entry gone, bytes consistent, still
    # serving inserts and evictions
    assert 'a' not in cache and cache.total_bytes == 20
    with pytest.warns(RuntimeWarning):
        cache.put('d', 4, nbytes=10)
    assert cache.keys() == ['c', 'd'] and cache.total_bytes == 20


def test_lru_on_evict_exception_under_byte_budget():
    cache = LRUPlanCache(max_bytes=100,
                         on_evict=lambda k, v: 1 / 0)
    cache.put('a', 1, nbytes=60)
    cache.grow('a', 50)                      # alone over budget: spared,
    assert 'a' in cache                      # no eviction, no hook call
    with pytest.warns(RuntimeWarning, match="on_evict hook failed"):
        cache.put('b', 2, nbytes=60)         # now eviction fires + raises
    assert cache.keys() == ['b'] and cache.total_bytes == 60
    assert cache.evict_errors == 1


def test_result_timeout_is_typed_and_ticket_stays_valid(mesh):
    with FFTEngine((8, 8), mesh, watermark=10**6,
                   schedule_table=None) as eng:
        x = _creq((8, 8))
        t = eng.submit(x)                    # watermark never trips
        with pytest.raises(ResultTimeout):
            t.result(timeout=0.05)
        assert issubclass(ResultTimeout, TimeoutError)
        assert not t.done and not t.failed   # still queued, still valid
        eng.flush()                          # now serve it
        np.testing.assert_allclose(np.asarray(t.result(timeout=60)),
                                   np.fft.fftn(x), atol=1e-3)


def test_submit_raises_when_drainer_died_without_error(mesh):
    eng = FFTEngine((8, 8), mesh, max_wait_ms=5.0, schedule_table=None)
    orig = eng._drainer
    try:
        dead = threading.Thread(target=lambda: None)
        dead.start()
        dead.join()
        eng._drainer = dead                  # simulate a silent death
        with pytest.raises(RuntimeError, match="not running"):
            eng.submit(_creq((8, 8)))
    finally:
        eng._drainer = orig
        eng.close()


def test_submit_raises_after_drainer_crash_reported(mesh):
    eng = FFTEngine((8, 8), mesh, max_wait_ms=5.0, schedule_table=None)
    try:
        eng._drainer_error = RuntimeError("injected crash")
        with pytest.raises(RuntimeError, match="drainer died"):
            eng.submit(_creq((8, 8)))
    finally:
        eng._drainer_error = None
        eng.close()


# ---------------------------------------------------------------------------
# Service over a unix socket (1x1 mesh)
# ---------------------------------------------------------------------------

def test_service_round_trip_complex_real_planar(mesh, sock_path):
    with FFTService(mesh, schedule_table=None).start(sock_path) as svc:
        with svc.local_client('t0') as c:
            xc = _creq((8, 8))
            yc = c.transform([xc])[0]
            np.testing.assert_allclose(yc, np.fft.fftn(xc), atol=1e-3)

            xr = RNG.standard_normal((8, 8)).astype(np.float32)
            yr = c.transform([xr], real=True)[0]
            assert yr.shape == (8, 5)        # half spectrum on the wire
            np.testing.assert_allclose(yr, np.fft.rfftn(xr), atol=1e-3)

            re = RNG.standard_normal((8, 8)).astype(np.float32)
            im = RNG.standard_normal((8, 8)).astype(np.float32)
            ore, oim = c.transform([(re, im)])[0]
            np.testing.assert_allclose(
                ore + 1j * oim, np.fft.fftn(re + 1j * im), atol=1e-3)

            # inverse round trip through the service
            xi = c.transform([yc], direction='inv', real=False)[0]
            np.testing.assert_allclose(xi, xc, atol=1e-3)
            c.drain(timeout=60)


def test_service_retry_after_on_tenant_quota(mesh, sock_path):
    slos = {'hold': SLOClass('hold', deadline_ms=60000, max_wait_ms=800)}
    svc = FFTService(
        mesh, schedule_table=None, policy=None, watermark=10**6,
        tenants=[TenantConfig('cap1', max_inflight=1, slo='hold')],
        slo_classes=slos,
    ).start(sock_path)
    with svc, svc.local_client('cap1') as c:
        x = _creq((8, 8))
        t1 = c.submit(x)                     # held by the huge watermark
        t2 = c.submit(x)                     # quota: typed backpressure
        with pytest.raises(RetryAfter) as ei:
            t2.result(timeout=30)
        assert ei.value.reason == 'tenant_quota'
        assert ei.value.retry_after_ms > 0
        # the admitted request is NOT degraded: it completes normally
        np.testing.assert_allclose(t1.result(timeout=60),
                                   np.fft.fftn(x), atol=1e-3)
        m = c.metrics()
        assert m['tenants']['cap1']['rejected'] == {'tenant_quota': 1}


def test_service_retry_after_on_rate_and_window(mesh, sock_path):
    slos = {'hold': SLOClass('hold', deadline_ms=60000, max_wait_ms=800)}
    svc = FFTService(
        mesh, schedule_table=None, policy=None, watermark=10**6,
        max_inflight=1,                      # service-wide window of 1
        tenants=[TenantConfig('slow', rate_per_s=0.001, burst=1),
                 TenantConfig('other', max_inflight=4, slo='hold')],
        slo_classes={**slos, 'standard': SLOClass('standard', 250, 20)},
    ).start(sock_path)
    with svc:
        with svc.local_client('other') as co, \
                svc.local_client('slow') as cs:
            x = _creq((8, 8))
            held = co.submit(x, slo='hold')  # occupies the whole window
            with pytest.raises(RetryAfter) as ei:
                co.submit(x, slo='hold').result(timeout=30)
            assert ei.value.reason == 'inflight_window'
            # admission order is rate -> quota -> window: slow's first
            # request spends its only token but dies on the full
            # window; the second dies on the empty bucket (~no refill)
            with pytest.raises(RetryAfter) as ei1:
                cs.submit(x).result(timeout=30)
            assert ei1.value.reason == 'inflight_window'
            with pytest.raises(RetryAfter) as ei2:
                cs.submit(x).result(timeout=30)
            assert ei2.value.reason == 'rate'
            held.result(timeout=60)


def test_service_auth_and_unknown_tenants(mesh, sock_path):
    svc = FFTService(
        mesh, schedule_table=None,
        tenants=[TenantConfig('sec', token='s3cret')],
    ).start(sock_path)
    with svc:
        with pytest.raises(PermissionError, match="unknown tenant"):
            FFTClient(sock_path, tenant='nobody')
        with pytest.raises(PermissionError, match="token"):
            FFTClient(sock_path, tenant='sec', token='wrong')
        with FFTClient(sock_path, tenant='sec', token='s3cret') as c:
            assert c.server_info['tenant'] == 'sec'
            x = _creq((8, 8))
            np.testing.assert_allclose(c.transform([x])[0],
                                       np.fft.fftn(x), atol=1e-3)


def test_service_version_mismatch_answered_typed(mesh, sock_path):
    with FFTService(mesh, schedule_table=None).start(sock_path):
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.connect(sock_path)
        try:
            frame = bytearray(proto.pack_frame(proto.HELLO,
                                               {'tenant': 'v'}))
            frame[4] = proto.PROTOCOL_VERSION + 1
            s.sendall(bytes(frame))
            msg_type, meta, _ = proto.recv_frame(s)
            assert msg_type == proto.ERROR
            assert meta['kind'] == 'version'
            assert 'protocol v' in meta['error']
            assert proto.recv_frame(s) is None   # then the close
        finally:
            s.close()


def test_service_metrics_schema_and_slo_accounting(mesh, sock_path):
    svc = FFTService(mesh, schedule_table=None).start(sock_path)
    with svc, svc.local_client('m0') as c:
        c.transform([_creq((8, 8)) for _ in range(3)], slo='interactive')
        c.drain(timeout=60)
        m = c.metrics()
    assert set(m) == {'service', 'tenants', 'shapes'}
    s = m['service']
    assert s['inflight'] == 0 and s['max_inflight'] == 64
    assert 'queue_depths' in s and 'dispatch' in s
    assert sum(s['dispatch']['width_hist'].values()) == s['dispatch']['groups'] > 0
    assert s['policy'] is not None and s['policy']['watermark'] >= 1
    t = m['tenants']['m0']
    assert t['completed'] == 3 and t['failed'] == 0
    lat = t['latency_ms']['interactive']
    assert lat['count'] == 3
    assert 0 < lat['p50_ms'] <= lat['p99_ms']
    assert lat['slo_deadline_ms'] == 50.0
    assert isinstance(lat['violations'], int)
    assert m['shapes'] and all(v['count'] for v in m['shapes'].values())


def test_service_unknown_slo_is_request_error(mesh, sock_path):
    with FFTService(mesh, schedule_table=None).start(sock_path) as svc:
        with svc.local_client('t') as c:
            t = c.submit(_creq((8, 8)), slo='platinum')
            with pytest.raises(RuntimeError, match="unknown SLO"):
                t.result(timeout=30)


def test_service_graceful_drain_on_close(mesh, sock_path):
    # requests that sit in the coalescing queue (huge watermark, 800 ms
    # wait): close(drain=True) must serve them and FLUSH their result
    # frames before tearing the connections down
    slos = {'hold': SLOClass('hold', deadline_ms=60000, max_wait_ms=800)}
    svc = FFTService(mesh, schedule_table=None, policy=None,
                     watermark=10**6, slo_classes=slos,
                     tenants=[TenantConfig('d0', slo='hold')],
                     ).start(sock_path)
    c = svc.local_client('d0')
    tickets = [c.submit(_creq((8, 8))) for _ in range(4)]
    deadline = time.monotonic() + 30
    while svc._inflight_total < 4:           # all four admitted & held
        assert time.monotonic() < deadline
        time.sleep(0.005)
    svc.close(drain=True)                    # serves + flushes all 4
    assert svc._inflight_total == 0
    assert svc.engine.closed
    for t in tickets:
        assert t.result(timeout=30).shape == (8, 8)
    c.close()
    assert not os.path.exists(sock_path)     # socket path cleaned up
    svc.close()                              # idempotent


def test_service_adaptive_policy_retargets_engine(mesh, sock_path):
    svc = FFTService(mesh, schedule_table=None).start(sock_path)
    with svc, svc.local_client('load') as c:
        lo = svc._last_decision
        assert lo is not None and lo.watermark == 1     # idle: narrow
        # a burst of offered requests raises the load level and the
        # engine's watermark with it
        for _ in range(400):
            svc.policy.observe(4)
        svc._apply_policy()
        hi = svc._last_decision
        assert hi.load_level > lo.load_level
        assert hi.watermark > lo.watermark
        assert svc.engine.watermark == hi.watermark
        # decisions persist as load-tagged rows on close
        rows = svc.policy.rows(dict(svc.engine.mesh.shape), (8, 8),
                               'complex', 'auto')
        assert {r['load'] for r in rows} >= {lo.load_level, hi.load_level}
        c.transform([_creq((8, 8))])


def test_client_ticket_timeout_leaves_request_pending(mesh, sock_path):
    slos = {'hold': SLOClass('hold', deadline_ms=60000, max_wait_ms=700)}
    svc = FFTService(mesh, schedule_table=None, policy=None,
                     watermark=10**6, slo_classes=slos,
                     tenants=[TenantConfig('t', slo='hold')]).start(sock_path)
    with svc, svc.local_client('t') as c:
        x = _creq((8, 8))
        t = c.submit(x)
        with pytest.raises(ResultTimeout):
            t.result(timeout=0.05)           # still queued server-side
        np.testing.assert_allclose(t.result(timeout=60),
                                   np.fft.fftn(x), atol=1e-3)


# ---------------------------------------------------------------------------
# 16-device multi-tenant matrix (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_service_worker_16_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["REPRO_SERVE_SCHEDULES"] = ""        # deterministic picks
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "_serve_service_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-4000:] + "\n" + proc.stderr[-4000:]
    assert "SERVE_SERVICE_WORKER_OK" in proc.stdout
    assert proc.stdout.count("PASS") >= 5
