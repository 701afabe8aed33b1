"""Batched FFT serving engine: coalescing, tickets, the throughput
model, donated buffers, and the overlap machinery's host-level stream
pipeline.

In-process tests run on a 1x1 mesh; the 16-fake-device matrix (engine
outputs bit-identical to per-request execution, complex and real,
remainder groups, donation on a real mesh) runs in a subprocess
(tests/_serve_fft_worker.py)."""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

import repro.fft as fft
from repro.comm import cost as ccost
from repro.comm import overlap as ov
from repro.serve import FFTEngine
from repro.launch.mesh import make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RNG = np.random.default_rng(29)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


# ---------------------------------------------------------------------------
# Engine correctness (1x1 mesh)
# ---------------------------------------------------------------------------

def test_engine_mixed_stream(mesh):
    shape = (8, 8, 8)
    eng = FFTEngine(shape, mesh)
    reqs = []
    for i in range(7):                        # odd count: remainder group
        x = RNG.standard_normal(shape).astype(np.float32)
        if i % 2:
            x = (x + 1j * RNG.standard_normal(shape)).astype(np.complex64)
        reqs.append(x)
    tickets = [eng.submit(x) for x in reqs]
    assert not any(t.done for t in tickets)
    outs = eng.flush()
    assert all(t.done for t in tickets)
    for x, t, o in zip(reqs, tickets, outs):
        assert t.result() is o
        got = np.asarray(t.result())
        if np.iscomplexobj(x):
            want = np.fft.fftn(x)
            assert got.shape == shape
        else:
            want = np.fft.rfftn(x)
            assert got.shape == (8, 8, 5)
        np.testing.assert_allclose(got, want,
                                   atol=3e-4 * np.max(np.abs(want)))


def test_engine_inverse_and_ticket_flush(mesh):
    shape = (8, 8)
    eng = FFTEngine(shape, mesh)
    x = (RNG.standard_normal(shape)
         + 1j * RNG.standard_normal(shape)).astype(np.complex64)
    y = eng.submit(x).result()                 # result() flushes lazily
    back = eng.transform([y], direction='inv')[0]
    np.testing.assert_allclose(np.asarray(back), x, atol=1e-4)
    # real inverse is inferred from the spectrum shape
    xr = RNG.standard_normal(shape).astype(np.float32)
    spec = eng.submit(xr).result()
    assert spec.shape == (8, 5)
    br = eng.transform([spec], direction='inv')[0]
    assert not np.iscomplexobj(np.asarray(br))
    np.testing.assert_allclose(np.asarray(br), xr, atol=1e-4)


def test_engine_validation(mesh):
    eng = FFTEngine((8, 8), mesh)
    # a rank-3 operand now plans a rank-3 transform (multi-shape
    # serving); only rank > 3 — a batch of transforms — is rejected
    with pytest.raises(ValueError, match="owns batching"):
        eng.submit(np.zeros((2, 2, 8, 8), np.complex64))
    with pytest.raises(ValueError, match="direction"):
        eng.submit(np.zeros((8, 8), np.complex64), direction='back')
    with pytest.raises(ValueError, match="real plan forward"):
        eng.submit((np.zeros((8, 8)), np.zeros((8, 8))), real=True)
    with pytest.raises(ValueError, match="pass real= explicitly"):
        eng.submit(np.zeros((3, 3), np.complex64), direction='inv')
    with pytest.raises(ValueError, match="batch_spec"):
        FFTEngine((8, 8), mesh, batch_spec='x')
    with pytest.raises(ValueError, match="mesh is required"):
        FFTEngine((8, 8))
    with pytest.raises(ValueError, match="max_coalesce"):
        FFTEngine((8, 8), mesh, max_coalesce=0)
    p = fft.plan((8, 8), mesh, batch_spec='x')
    with pytest.raises(ValueError, match="batch_spec"):
        FFTEngine(p)


def test_engine_from_existing_plan(mesh):
    p = fft.rplan((8, 8, 8), mesh, method='stockham')
    eng = FFTEngine(p)
    assert eng.shape == (8, 8, 8)
    sp = eng.plan_for(True)
    assert sp.real and sp.method == 'stockham'
    # the complex sibling adopts the resolved settings
    cp = eng.plan_for(False)
    assert not cp.real and cp.method == 'stockham'
    x = RNG.standard_normal((8, 8, 8)).astype(np.float32)
    got = np.asarray(eng.transform([x])[0])
    want = np.fft.rfftn(x)
    np.testing.assert_allclose(got, want, atol=3e-4 * np.max(np.abs(want)))


def test_engine_schedule_knobs(mesh):
    eng = FFTEngine((8, 8, 8), mesh, max_coalesce=4, overlap_chunks=2)
    w, c = eng.schedule(False)
    assert 1 <= w <= 4 and c in (1, 2)
    # a latency budget of ~zero forces the un-coalesced schedule
    eng2 = FFTEngine((8, 8, 8), mesh, latency_budget_us=1e-9)
    assert eng2.schedule(False) == (1, 1)


def test_engine_executable_cache_shared(mesh):
    eng = FFTEngine((8, 8), mesh, max_coalesce=4)
    w, _ = eng.schedule(False)
    reqs = [(RNG.standard_normal((8, 8))
             + 1j * RNG.standard_normal((8, 8))).astype(np.complex64)
            for _ in range(2 * w)]
    eng.transform(reqs)
    p = eng.plan_for(False)
    n0 = len(p._exec_cache)
    eng.transform(reqs)                        # same widths -> no retrace
    assert len(p._exec_cache) == n0


def test_flush_failure_requeues_instead_of_silent_none(mesh, monkeypatch):
    """A failed group must not drop its tickets: the entries go back on
    the queue, result() re-raises (never returns a silent None), and a
    retry after the fault clears succeeds."""
    eng = FFTEngine((8, 8), mesh)
    x = (RNG.standard_normal((8, 8))
         + 1j * RNG.standard_normal((8, 8))).astype(np.complex64)
    t = eng.submit(x)

    def boom(*a, **k):
        raise RuntimeError("boom")

    monkeypatch.setattr(eng, '_run_group', boom)
    with pytest.raises(RuntimeError, match="boom"):
        eng.flush()
    assert not t.done
    assert sum(len(q) for q in eng._queues.values()) == 1
    with pytest.raises(RuntimeError, match="boom"):   # retried, re-raised
        t.result()
    monkeypatch.undo()
    got = np.asarray(t.result())                      # retry succeeds
    np.testing.assert_allclose(got, np.fft.fftn(x), atol=1e-3)


def test_engine_autotune(mesh):
    eng = FFTEngine((8, 8), mesh, max_coalesce=2)
    reqs = [(RNG.standard_normal((8, 8))
             + 1j * RNG.standard_normal((8, 8))).astype(np.complex64)
            for _ in range(4)]
    w, c = eng.autotune(reqs, repeats=1, widths=(1, 2), chunks=(1, 2))
    assert eng.schedule(False) == (w, c)
    assert w in (1, 2) and c in (1, 2)
    got = np.asarray(eng.transform([reqs[0]])[0])
    np.testing.assert_allclose(got, np.fft.fftn(reqs[0]), atol=1e-3)


# ---------------------------------------------------------------------------
# Persisted serving schedules (BENCH_serve_schedule.json)
# ---------------------------------------------------------------------------

def test_schedule_table_lookup_prefers_dtype():
    rows = [dict(mesh='4x4', shape='8x8', kind='complex',
                 strategy='all_to_all', dtype='complex64',
                 coalesce_width=8, overlap_chunks=2, us_per_request=10.0),
            dict(mesh='4x4', shape='8x8', kind='complex',
                 strategy='all_to_all', dtype='complex128',
                 coalesce_width=4, overlap_chunks=4, us_per_request=5.0)]
    tbl = ccost.ScheduleTable(rows)
    mesh_shape = {'x': 4, 'y': 4}
    got = tbl.lookup(mesh_shape, (8, 8), 'complex', 'all_to_all',
                     dtype='complex64')
    assert (got['coalesce_width'], got['overlap_chunks']) == (8, 2)
    # unmeasured dtype: the fastest row of the key answers
    got = tbl.lookup(mesh_shape, (8, 8), 'complex', 'all_to_all',
                     dtype='float32')
    assert got['coalesce_width'] == 4
    assert tbl.lookup(mesh_shape, (8, 8), 'real', 'all_to_all') is None
    assert tbl.lookup({'x': 2}, (8, 8), 'complex', 'all_to_all') is None


def test_schedule_table_backend_isolation():
    """Rows from different backends merge independently and never
    answer for each other — a CPU refresh must not clobber or shadow a
    GPU host's persisted measurement."""
    mk = dict(mesh='4x4', shape='8x8', kind='complex',
              strategy='all_to_all', dtype='complex64')
    tbl = ccost.ScheduleTable([
        dict(mk, coalesce_width=4, overlap_chunks=2, us_per_request=1.0,
             backend='gpu'),
        dict(mk, coalesce_width=2, overlap_chunks=1, us_per_request=9.0,
             backend='cpu')])
    assert len(tbl) == 2                       # same config, both survive
    mesh_shape = {'x': 4, 'y': 4}
    got = tbl.lookup(mesh_shape, (8, 8), 'complex', 'all_to_all',
                     backend='cpu')
    assert got['coalesce_width'] == 2          # never the gpu row
    got = tbl.lookup(mesh_shape, (8, 8), 'complex', 'all_to_all',
                     backend='tpu')
    assert got is None                         # unmeasured backend: model


def test_autotune_persists_and_seeds_next_engine(mesh, tmp_path):
    path = str(tmp_path / "BENCH_serve_schedule.json")
    eng = FFTEngine((8, 8), mesh, max_coalesce=2, schedule_table=path)
    reqs = [(RNG.standard_normal((8, 8))
             + 1j * RNG.standard_normal((8, 8))).astype(np.complex64)
            for _ in range(4)]
    w, c = eng.autotune(reqs, repeats=1, widths=(1, 2), chunks=(1, 2),
                        persist=True)
    assert os.path.exists(path)
    tbl = ccost.ScheduleTable.load(path)
    row = tbl.lookup(dict(mesh.shape), (8, 8), 'complex',
                     eng.plan_for(False).comm, dtype='complex64')
    assert (row['coalesce_width'], row['overlap_chunks']) == (w, c)
    assert row['us_per_request'] > 0
    # a NEW engine on the same config seeds its pick from the table...
    eng2 = FFTEngine((8, 8), mesh, max_coalesce=2, schedule_table=path)
    assert eng2.schedule(False) == (w, c)
    # ...still serving correctly
    got = np.asarray(eng2.transform([reqs[0]])[0])
    np.testing.assert_allclose(got, np.fft.fftn(reqs[0]), atol=1e-3)
    # an engine whose knobs the row does not fit falls back to the model
    eng3 = FFTEngine((8, 8), mesh, max_coalesce=max(w - 1, 1),
                     schedule_table=path)
    w3, _ = eng3.schedule(False)
    assert w3 <= max(w - 1, 1)


def test_schedule_table_env_override(mesh, tmp_path, monkeypatch):
    path = str(tmp_path / "alt_schedules.json")
    ccost.persist_schedule_rows(
        [dict(mesh='1x1', shape='8x8', kind='complex',
              strategy='all_to_all', dtype='complex64', coalesce_width=2,
              overlap_chunks=1, us_per_request=1.0)], path)
    monkeypatch.setenv(ccost.SCHEDULE_ENV, path)
    eng = FFTEngine((8, 8), mesh, max_coalesce=4, comm='all_to_all')
    assert eng.schedule(False) == (2, 1)       # seeded from the env table
    monkeypatch.setenv(ccost.SCHEDULE_ENV, '')  # '' disables persistence
    assert ccost.schedule_table_path() is None
    assert ccost.persist_schedule_rows([]) is None


# ---------------------------------------------------------------------------
# Donation semantics (the no-reuse-after-donate contract)
# ---------------------------------------------------------------------------

def test_donated_plan_consumes_input(mesh):
    p = fft.plan((8, 8), mesh)
    assert p.donate and p.donates_input
    x = jnp.asarray(RNG.standard_normal((8, 8)), jnp.complex64)
    y = p.forward(x)
    assert x.is_deleted()
    with pytest.raises(RuntimeError, match="deleted"):
        _ = x + 1
    # the output is alive; the inverse consumes IT in turn
    back = p.inverse(y)
    assert y.is_deleted()
    assert not back.is_deleted()


def test_donate_false_escape_hatch(mesh):
    p = fft.plan((8, 8), mesh, donate=False)
    assert not p.donates_input
    x = jnp.asarray(RNG.standard_normal((8, 8)), jnp.complex64)
    y1 = p.forward(x)
    y2 = p.forward(x)                          # reusable FFTW-style buffer
    assert not x.is_deleted()
    assert np.array_equal(np.asarray(y1), np.asarray(y2))


def test_planar_donation_consumes_both(mesh):
    p = fft.plan((8, 8), mesh)
    re = jnp.asarray(RNG.standard_normal((8, 8)), jnp.float32)
    im = jnp.asarray(RNG.standard_normal((8, 8)), jnp.float32)
    p.forward((re, im))
    assert re.is_deleted() and im.is_deleted()


def test_real_plans_never_donate(mesh):
    p = fft.rplan((8, 8), mesh)
    assert p.donate and not p.donates_input    # requested but structurally n/a
    x = jnp.asarray(RNG.standard_normal((8, 8)), jnp.float32)
    y = p.forward(x)
    assert not x.is_deleted()
    p.inverse(y)
    assert not y.is_deleted()


def test_engine_donation_follows_plan_contract(mesh):
    # donate=True: submitted jax arrays are consumed (same contract as
    # plan.forward), each request aliasing its own output in the group
    eng = FFTEngine((8, 8), mesh)
    assert eng.donate
    x = jnp.asarray(RNG.standard_normal((8, 8)), jnp.complex64)
    eng.transform([x])
    assert x.is_deleted()
    # numpy submissions are copied to device — caller data untouched
    xnp = RNG.standard_normal((8, 8)).astype(np.complex64)
    ref = xnp.copy()
    y = eng.transform([xnp])[0]
    assert np.array_equal(xnp, ref)            # unmodified and readable
    np.testing.assert_allclose(np.asarray(y), np.fft.fftn(ref), atol=1e-3)
    # donate=False escape hatch keeps submitted jax arrays alive
    eng2 = FFTEngine((8, 8), mesh, donate=False)
    x2 = jnp.asarray(RNG.standard_normal((8, 8)), jnp.complex64)
    eng2.transform([x2])
    assert not x2.is_deleted()
    # real requests are never donated (no aliasing across r2c)
    xr = jnp.asarray(RNG.standard_normal((8, 8)), jnp.float32)
    eng.transform([xr])
    assert not xr.is_deleted()


def test_with_options_carries_donate(mesh):
    p = fft.plan((8, 8), mesh, donate=False)
    assert not p.with_options(overlap_chunks=2).donates_input
    assert p.with_options(donate=True).donates_input


def test_with_options_real_to_complex_drops_padded(mesh):
    """padded_spectrum is a real-plan-only knob: a real -> complex
    re-plan must drop it instead of tripping plan() validation."""
    p = fft.rplan((8, 8), mesh, padded_spectrum=True)
    c = p.with_options(real=False)
    assert not c.real and not c.padded_spectrum
    # and a round trip back to real keeps working
    r = c.with_options(real=True, padded_spectrum=True)
    assert r.real and r.padded_spectrum


# ---------------------------------------------------------------------------
# Throughput model + stream pipeline machinery
# ---------------------------------------------------------------------------

def test_pipeline_model():
    pc = ccost.pencil_plan_cost((64,) * 3, ('x', 'y', None),
                                {'x': 8, 'y': 8}, measured=None)
    # one request, one chunk: exactly the serial schedule
    assert pc.pipeline_cycles(1) == pytest.approx(pc.serial_cycles)
    assert pc.pipeline_cycles(4, 1) == pytest.approx(4 * pc.serial_cycles)
    # coalescing strictly improves per-request cost...
    assert pc.pipeline_us(8) < pc.pipeline_us(1)
    # ...approaching the steady-state bound max(compute, wire)/request
    comp = pc.serial_cycles - pc.wire_cycles
    bound = max(comp, pc.wire_cycles)
    assert pc.pipeline_cycles(64) / 64 > bound
    assert pc.pipeline_cycles(64) / 64 < 1.2 * bound + ccost.OVERLAP_CHUNK_OVERHEAD
    # ...while whole-batch latency grows
    assert pc.pipeline_latency_us(8) > pc.pipeline_latency_us(2)
    # priced per strategy: a different wire schedule changes the
    # fill/drain term, so the throughput curve moves with the strategy
    ring = ccost.pencil_plan_cost((64,) * 3, ('x', 'y', None),
                                  {'x': 8, 'y': 8}, strategy='ppermute',
                                  measured=None)
    assert ring.wire_cycles != pc.wire_cycles
    assert ring.pipeline_us(8) != pc.pipeline_us(8)


def test_pipelined_stream_order_and_depth():
    calls = []

    def fn(x):
        calls.append(x)
        return jnp.asarray(x * 2.0)

    out = ov.pipelined_stream(fn, [1.0, 2.0, 3.0, 4.0, 5.0], depth=2)
    assert calls == [1.0, 2.0, 3.0, 4.0, 5.0]
    assert [float(o) for o in out] == [2.0, 4.0, 6.0, 8.0, 10.0]
    assert ov.pipelined_stream(fn, []) == []
    with pytest.raises(ValueError, match="depth"):
        ov.pipelined_stream(fn, [1.0], depth=0)


def test_pick_chunk_axis_fallbacks():
    # no overlap requested
    assert ov.pick_chunk_axis((8, 8), (), 1) is None
    # every axis excluded
    assert ov.pick_chunk_axis((8, 8), (0, 1), 2) is None
    # nothing divides
    assert ov.pick_chunk_axis((4, 4, 16), (), 3) is None
    # n_chunks larger than every free axis
    assert ov.pick_chunk_axis((4, 4), (0,), 8) is None
    # first qualifying axis wins (leading batch axis preferred)
    assert ov.pick_chunk_axis((8, 4, 16), (1,), 4) == 0
    assert ov.pick_chunk_axis((3, 4, 16), (1,), 4) == 2


# ---------------------------------------------------------------------------
# 16-device matrix (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serve_fft_worker_16_devices():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["REPRO_SERVE_SCHEDULES"] = ""          # deterministic picks
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_serve_fft_worker.py")],
        capture_output=True, text=True, env=env, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-4000:] + "\n" + proc.stderr[-4000:]
    assert "SERVE_FFT_WORKER_OK" in proc.stdout
    assert proc.stdout.count("PASS") >= 6
