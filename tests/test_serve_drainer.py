"""Continuous serving: the background drainer, the multi-shape LRU
plan cache, and the donated-operand retry snapshots.

In-process tests run on a 1x1 mesh (fast paths: deadline/watermark
triggers, close semantics, failure re-queue + retry, LRU eviction).
The 16-fake-device concurrency matrix — N producer threads x mixed
shapes/kinds/directions, deadline-only and watermark-only loads,
bit-identity to per-request execution, drainer exception injection —
runs in a subprocess (tests/_serve_drainer_worker.py)."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest
import jax.numpy as jnp

from repro.comm import overlap as ov
from repro.serve import FFTEngine, LRUPlanCache
from repro.launch.mesh import make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RNG = np.random.default_rng(37)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


def _creq(shape):
    return (RNG.standard_normal(shape)
            + 1j * RNG.standard_normal(shape)).astype(np.complex64)


# ---------------------------------------------------------------------------
# Background drainer: triggers, close, context manager
# ---------------------------------------------------------------------------

def test_deadline_serves_without_flush(mesh):
    with FFTEngine((8, 8), mesh, max_wait_ms=5.0, watermark=10**6,
                   schedule_table=None) as eng:
        x = _creq((8, 8))
        t = eng.submit(x)
        got = t.result(timeout=60)            # no flush() anywhere
        np.testing.assert_allclose(np.asarray(got), np.fft.fftn(x),
                                   atol=1e-3)
        assert t.done


def test_watermark_serves_without_flush(mesh):
    # no deadline at all: dispatch happens only when a kind's queue
    # reaches the watermark (or at close)
    with FFTEngine((8, 8), mesh, watermark=2, schedule_table=None) as eng:
        xs = [_creq((8, 8)) for _ in range(2)]
        t0 = eng.submit(xs[0])
        time.sleep(0.05)
        assert not t0.done                    # below watermark: queued
        t1 = eng.submit(xs[1])                # trips the watermark
        for t, x in zip((t0, t1), xs):
            np.testing.assert_allclose(np.asarray(t.result(timeout=60)),
                                       np.fft.fftn(x), atol=1e-3)


def test_close_drains_and_submit_after_close_raises(mesh):
    eng = FFTEngine((8, 8), mesh, watermark=10**6, schedule_table=None)
    xs = [_creq((8, 8)) for _ in range(3)]
    tickets = [eng.submit(x) for x in xs]
    eng.close()                               # final pass drains the queue
    for t, x in zip(tickets, xs):
        np.testing.assert_allclose(np.asarray(t.result(timeout=60)),
                                   np.fft.fftn(x), atol=1e-3)
    with pytest.raises(RuntimeError, match="close"):
        eng.submit(xs[0])
    eng.close()                               # idempotent
    assert eng.closed


def test_foreground_close_flushes(mesh):
    eng = FFTEngine((8, 8), mesh, schedule_table=None)
    x = _creq((8, 8))
    t = eng.submit(x)
    eng.close()
    assert t.done
    with pytest.raises(RuntimeError, match="close"):
        eng.submit(x)


def test_mixed_shapes_and_kinds_no_flush(mesh):
    """One background engine serves >= 3 distinct shapes, complex and
    real, forward and inverse, with no explicit flush()."""
    shapes = [(8, 8), (4, 4), (8, 8, 8)]
    with FFTEngine(mesh=mesh, max_wait_ms=5.0, schedule_table=None) as eng:
        tickets, want = [], []
        for shape in shapes:
            xc = _creq(shape)
            xr = RNG.standard_normal(shape).astype(np.float32)
            tickets.append(eng.submit(xc))
            want.append(np.fft.fftn(xc))
            tickets.append(eng.submit(xr))
            want.append(np.fft.rfftn(xr))
        for t, w in zip(tickets, want):
            got = np.asarray(t.result(timeout=120))
            np.testing.assert_allclose(got, w,
                                       atol=3e-4 * np.max(np.abs(w)))
        # inverse serving: round-trip one of each kind through result()
        spec = tickets[0].result()
        # read the spectrum before submitting it: complex plans donate
        # their operand, so the engine consumes it
        want_back = np.fft.ifftn(np.asarray(spec))
        back = eng.submit(spec, direction='inv').result(timeout=120)
        np.testing.assert_allclose(np.asarray(back), want_back, atol=1e-4)
        rspec = tickets[1].result()
        rback = eng.submit(rspec, direction='inv').result(timeout=120)
        assert not np.iscomplexobj(np.asarray(rback))
        assert np.asarray(rback).shape == shapes[0]


def test_engine_without_default_shape_requires_operands(mesh):
    eng = FFTEngine(mesh=mesh, schedule_table=None)
    with pytest.raises(ValueError, match="no default shape"):
        eng.schedule()
    x = _creq((4, 4))
    got = eng.transform([x])[0]
    np.testing.assert_allclose(np.asarray(got), np.fft.fftn(x), atol=1e-3)
    assert eng.serving_shapes() == [((4, 4), False)]


def test_transform_below_watermark_makes_progress(mesh):
    """A synchronous transform() must never depend on the drainer's
    triggers: one request below the watermark of a deadline-less
    engine would otherwise hang forever."""
    with FFTEngine((8, 8), mesh, watermark=8, schedule_table=None) as eng:
        x = _creq((8, 8))
        got = eng.transform([x], timeout=60)[0]
        np.testing.assert_allclose(np.asarray(got), np.fft.fftn(x),
                                   atol=1e-3)


def test_dropped_engine_is_reclaimed(mesh):
    """An engine dropped WITHOUT close() must not pin its drainer
    thread (and the whole plan cache) forever: the drainer holds the
    engine only via a weakref between passes, so the cyclic GC can
    collect it and the orphaned thread exits."""
    import gc
    import threading
    import weakref

    before = threading.active_count()
    eng = FFTEngine((8, 8), mesh, max_wait_ms=5.0, schedule_table=None)
    t = eng.submit(_creq((8, 8)))
    t.result(timeout=60)
    ref = weakref.ref(eng)
    del eng, t
    deadline = time.time() + 30
    while time.time() < deadline and (ref() is not None
                                      or threading.active_count() > before):
        gc.collect()
        time.sleep(0.2)
    assert ref() is None
    assert threading.active_count() == before


# ---------------------------------------------------------------------------
# Drainer failure handling: re-queue, retry, surface on result()
# ---------------------------------------------------------------------------

def test_drainer_failure_requeues_then_retry_succeeds(mesh, monkeypatch):
    eng = FFTEngine((8, 8), mesh, max_wait_ms=5.0, retries=3,
                    schedule_table=None)
    real_run = eng._run_group
    fails = {'left': 2}

    def flaky(*a, **k):
        if fails['left'] > 0:
            fails['left'] -= 1
            raise RuntimeError("injected drainer fault")
        return real_run(*a, **k)

    monkeypatch.setattr(eng, '_run_group', flaky)
    with eng:
        x = _creq((8, 8))
        got = eng.submit(x).result(timeout=60)   # retried, never dropped
        np.testing.assert_allclose(np.asarray(got), np.fft.fftn(x),
                                   atol=1e-3)
    assert fails['left'] == 0


def test_drainer_persistent_failure_surfaces_on_result(mesh, monkeypatch):
    eng = FFTEngine((8, 8), mesh, max_wait_ms=5.0, retries=1,
                    schedule_table=None)

    def boom(*a, **k):
        raise RuntimeError("persistent drainer fault")

    monkeypatch.setattr(eng, '_run_group', boom)
    with eng:
        t = eng.submit(_creq((8, 8)))
        with pytest.raises(RuntimeError, match="persistent drainer fault"):
            t.result(timeout=60)
    assert not t.done                          # failed, not silently None


def test_bystander_groups_survive_culprit_failure(mesh, monkeypatch):
    """A pipeline failure tears down every in-flight group, but only
    the CULPRIT group's requests burn retries: a persistently failing
    kind must not poison healthy traffic dispatched alongside it."""
    eng = FFTEngine((8, 8), mesh, max_wait_ms=5.0, retries=1,
                    schedule_table=None)
    real_run = eng._run_group

    def selective(plan, direction, planar, ops, *a, **k):
        if plan.real:
            raise RuntimeError("culprit kind")
        return real_run(plan, direction, planar, ops, *a, **k)

    monkeypatch.setattr(eng, '_run_group', selective)
    with eng:
        xc = _creq((8, 8))
        tc = eng.submit(xc)
        tr = eng.submit(RNG.standard_normal((8, 8)).astype(np.float32))
        with pytest.raises(RuntimeError, match="culprit kind"):
            tr.result(timeout=60)
        got = np.asarray(tc.result(timeout=60))   # healthy kind survives
        np.testing.assert_allclose(got, np.fft.fftn(xc), atol=1e-3)


def test_result_timeout(mesh):
    with FFTEngine((8, 8), mesh, watermark=10**6,
                   schedule_table=None) as eng:
        t = eng.submit(_creq((8, 8)))          # never ripe before close
        with pytest.raises(TimeoutError):
            t.result(timeout=0.05)
    assert t.done                              # close() drained it


# ---------------------------------------------------------------------------
# Donated-operand snapshots: a failed group's requests stay runnable
# ---------------------------------------------------------------------------

def test_failed_group_donated_operand_retries_cleanly(mesh, monkeypatch):
    """Regression (PR-4 UX): a donated operand consumed by a failed
    group used to leave the ticket poisoned — the re-queued request
    held a deleted buffer, so no retry could succeed. The engine now
    snapshots donated operands while their group is in flight and
    re-queues the snapshot."""
    eng = FFTEngine((8, 8), mesh, schedule_table=None)
    p = eng.plan_for(False)
    assert p.donates_input
    x_host = _creq((8, 8))
    x = jnp.asarray(x_host)
    t = eng.submit(x)
    real_run = eng._run_group

    def run_then_fail(*a, **k):
        real_run(*a, **k)                      # CONSUMES the donated input
        raise RuntimeError("post-dispatch fault")

    monkeypatch.setattr(eng, '_run_group', run_then_fail)
    with pytest.raises(RuntimeError, match="post-dispatch fault"):
        eng.flush()
    assert x.is_deleted()                      # the group really donated
    assert not t.done
    monkeypatch.undo()
    got = np.asarray(t.result())               # retry runs on the snapshot
    np.testing.assert_allclose(got, np.fft.fftn(x_host), atol=1e-3)


def test_snapshot_dropped_on_success(mesh):
    eng = FFTEngine((8, 8), mesh, schedule_table=None)
    x = jnp.asarray(_creq((8, 8)))
    t = eng.submit(x)
    eng.flush()
    assert t.done and x.is_deleted()           # donation contract intact


# ---------------------------------------------------------------------------
# Multi-shape LRU plan cache
# ---------------------------------------------------------------------------

def test_plan_lru_eviction_order_and_recompile_once(mesh):
    evicted = []
    eng = FFTEngine(mesh=mesh, max_plans=2, schedule_table=None,
                    on_plan_evict=lambda key, plan: evicted.append(key))
    for shape in ((8, 8), (4, 4), (16, 16)):
        eng.transform([_creq(shape)])
    # LRU evicted the first-served shape, kept the two most recent
    assert evicted == [((8, 8), False)]
    assert eng.serving_shapes() == [((4, 4), False), ((16, 16), False)]
    assert eng.plan_builds[((8, 8), False)] == 1
    # re-request the evicted shape: recompiles exactly once...
    eng.transform([_creq((8, 8))])
    eng.transform([_creq((8, 8))])
    assert eng.plan_builds[((8, 8), False)] == 2
    # ...and the eviction hook saw the next LRU victim go
    assert evicted == [((8, 8), False), ((4, 4), False)]


def test_plan_cache_byte_budget_evicts(mesh):
    eng = FFTEngine(mesh=mesh, plan_cache_bytes=1, schedule_table=None)
    eng.transform([_creq((8, 8))])
    assert len(eng._states) == 1               # sole entry may bust budget
    eng.transform([_creq((4, 4))])
    assert len(eng._states) == 1               # old shape evicted
    assert eng.serving_shapes() == [((4, 4), False)]


def test_inverse_inference_never_evicts_served_plans(mesh):
    """Regression: inferring an inverse's kind used to build (and
    LRU-insert) the default shape's real plan as a side effect, which
    could evict the very served plan the inference was about to match.
    Inference is now side-effect free."""
    eng = FFTEngine((8, 8), mesh, max_plans=2, schedule_table=None)
    y44 = eng.transform([_creq((4, 4))])[0]
    y44_host = np.asarray(y44)      # the donating inverse consumes y44
    eng.transform([_creq((8, 8))])
    cached = eng.serving_shapes()
    # the (4,4) inverse resolves against the served complex plan, and
    # the cache is untouched by the inference itself
    back = eng.transform([y44], direction='inv')[0]
    np.testing.assert_allclose(np.asarray(back),
                               np.fft.ifftn(y44_host), atol=1e-4)
    assert set(eng.serving_shapes()) == set(cached)
    # the default shape's np-layout real spectrum still infers real
    # without a real plan ever having been served
    spec = np.zeros((8, 5), np.complex64)
    t = eng.submit(spec, direction='inv')
    assert np.asarray(t.result()).shape == (8, 8)


def test_autotune_persist_disabled_raises(mesh):
    eng = FFTEngine((8, 8), mesh, max_coalesce=2, schedule_table=None)
    with pytest.raises(ValueError, match="persist"):
        eng.autotune([_creq((8, 8))], repeats=1, widths=(1,), chunks=(1,),
                     persist=True)


def test_set_schedule_resets_entry_bytes(mesh):
    """Regression: clearing a plan's group executables on reschedule
    must release their accounted bytes, or every autotune/set_schedule
    inflates the entry and evicts innocent siblings."""
    eng = FFTEngine((8, 8), mesh, schedule_table=None)
    eng.transform([_creq((8, 8))])
    key = ((8, 8), False)
    before = eng._states.nbytes(key)
    assert before > 0
    w, c = eng.schedule(False)
    eng.set_schedule(max(w, 2), 2)             # clears the executables
    assert eng._states.nbytes(key) == 0
    eng.transform([_creq((8, 8))])             # re-grows from zero
    assert 0 < eng._states.nbytes(key) <= 2 * before


def test_lru_plan_cache_unit():
    evicted = []
    c = LRUPlanCache(max_entries=2, on_evict=lambda k, v: evicted.append(k))
    c.put('a', 1)
    c.put('b', 2)
    assert c.get('a') == 1                     # 'a' now MRU
    c.put('c', 3)
    assert evicted == ['b'] and c.keys() == ['a', 'c']
    assert c.get('b') is None
    # byte budget with growth
    cb = LRUPlanCache(max_bytes=100)
    cb.put('x', 'X', nbytes=60)
    cb.put('y', 'Y', nbytes=30)
    cb.grow('y', 40)                           # 60 + 70 > 100 -> evict x
    assert cb.keys() == ['y'] and cb.total_bytes == 70
    cb.grow('y', 1000)                         # sole entry never evicted
    assert cb.keys() == ['y']
    with pytest.raises(ValueError, match="max_entries"):
        LRUPlanCache(max_entries=0)


# ---------------------------------------------------------------------------
# StreamPipeline (the drainer's persistent bounded window)
# ---------------------------------------------------------------------------

def test_stream_pipeline_push_drain_abort():
    forced = []
    pipe = ov.StreamPipeline(depth=2)
    for i in range(3):
        pipe.push(lambda i=i: jnp.asarray(float(i)),
                  lambda r, i=i: forced.append((i, float(r))))
    assert len(pipe) == 2                      # one was forced by the bound
    assert forced == [(0, 0.0)]
    pipe.drain()
    assert forced == [(0, 0.0), (1, 1.0), (2, 2.0)] and len(pipe) == 0
    pipe.push(lambda: jnp.asarray(9.0), lambda r: forced.append('no'))
    assert pipe.abort() == 1 and len(pipe) == 0
    assert forced[-1] != 'no'                  # aborted callbacks never run
    with pytest.raises(ValueError, match="depth"):
        ov.StreamPipeline(depth=0)


# ---------------------------------------------------------------------------
# 16-device concurrency matrix (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_drainer_worker_16_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["REPRO_SERVE_SCHEDULES"] = ""          # deterministic picks
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "_serve_drainer_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-4000:] + "\n" + proc.stderr[-4000:]
    assert "SERVE_DRAINER_WORKER_OK" in proc.stdout
    assert proc.stdout.count("PASS") >= 4
