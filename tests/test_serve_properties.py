"""Properties of the serving layer via hypothesis (optional dev
dependency; the whole module is skipped when hypothesis is not
installed — deterministic coverage of the same machinery lives in
test_serve_fft.py / test_serve_drainer.py).

Covered invariants:

* the serving throughput model: steady-state ``pipeline_us`` is
  monotone non-increasing in the coalesce width (until a latency
  budget binds, which the schedule picker must respect),
* the LRU plan cache: never exceeds its byte budget, eviction order is
  least-recently-used, and a re-requested key rebuilds at most once
  per eviction,
* the persisted schedule table: merge replaces same-key rows and keeps
  the rest, and save/load round-trips exactly.
"""
import os
import tempfile

import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.comm import cost as ccost  # noqa: E402
from repro.launch.mesh import make_abstract_mesh  # noqa: E402
from repro.serve import FFTEngine, LRUPlanCache  # noqa: E402

# ---------------------------------------------------------------------------
# Throughput model: pipeline_us monotone in width; budget binds the pick
# ---------------------------------------------------------------------------

_MESHES = st.sampled_from([{'x': 2, 'y': 2}, {'x': 4, 'y': 4},
                           {'x': 2, 'y': 8}])
_STRATEGIES = st.sampled_from(['all_to_all', 'ppermute', 'hierarchical'])


def _best_us(pc, w):
    """The picker's view of one width: the best feasible chunk depth."""
    return min(pc.pipeline_us(w, c) for c in (1, 2, 4, 8, 16)
               if c <= w and w % c == 0)


@settings(max_examples=40, deadline=None)
@given(n=st.sampled_from([16, 32, 64]), mesh=_MESHES,
       strategy=_STRATEGIES, real=st.booleans(),
       chunks=st.sampled_from([1, 2, 4]))
def test_pipeline_us_monotone_in_width(n, mesh, strategy, real, chunks):
    """Coalescing more requests never costs more per request in steady
    state — at a FIXED chunk depth (the batch amortizes the per-chunk
    dispatch overhead), and for the best-over-chunks schedule the
    picker optimizes (a power-of-two width's divisors nest). One chunk
    per request (``overlap_chunks=None``) is deliberately excluded:
    there the chunk overhead grows with the batch, which is exactly why
    the picker searches (width, chunks) jointly."""
    pc = ccost.pencil_plan_cost((n, n, n), ('x', 'y', None), mesh,
                                strategy=strategy, real=real,
                                measured=None)
    widths = [w for w in (1, 2, 4, 8, 16, 32, 64) if w >= chunks]
    for prev_w, w in zip(widths, widths[1:]):
        assert (pc.pipeline_us(w, chunks)
                <= pc.pipeline_us(prev_w, chunks) * (1 + 1e-9) + 1e-9)
    best = [_best_us(pc, w) for w in (1, 2, 4, 8, 16, 32, 64)]
    for prev, cur in zip(best, best[1:]):
        assert cur <= prev * (1 + 1e-9) + 1e-9
    # and the whole-batch latency grows with the batch, so a latency
    # budget must eventually bind the width
    assert (pc.pipeline_latency_us(64, chunks)
            > pc.pipeline_latency_us(1, chunks))


@settings(max_examples=40, deadline=None)
@given(n=st.sampled_from([16, 64]), maxc=st.integers(1, 32),
       budget=st.one_of(st.none(), st.floats(0.5, 1e5)))
def test_schedule_pick_respects_knobs(n, maxc, budget):
    """The engine's (width, chunks) pick: width within max_coalesce,
    chunks dividing the width, the latency budget honored whenever any
    coalesced schedule can honor it, and the steady-state objective
    never worse than the un-coalesced schedule."""
    sharding = pytest.importorskip("jax.sharding")
    if not hasattr(sharding, 'AbstractMesh'):
        pytest.skip("jax.sharding.AbstractMesh unavailable")
    mesh = make_abstract_mesh((4, 4), ('x', 'y'))
    eng = FFTEngine((n, n, n), mesh, max_coalesce=maxc,
                    latency_budget_us=budget, schedule_table=None)
    w, c = eng.schedule(False)
    assert 1 <= w <= maxc and 1 <= c <= w and w % c == 0
    pc = eng.plan_for(False).plan_cost()
    if budget is not None and (w, c) != (1, 1):
        assert pc.pipeline_latency_us(w, c) <= budget
    assert pc.pipeline_us(w, c) <= pc.pipeline_us(1, 1) * (1 + 1e-9)


# ---------------------------------------------------------------------------
# LRU plan cache
# ---------------------------------------------------------------------------

_KEYS = 'abcde'


@settings(max_examples=60, deadline=None)
@given(budget=st.integers(60, 200),
       ops=st.lists(st.tuples(st.sampled_from(_KEYS),
                              st.integers(1, 60)),
                    min_size=1, max_size=60))
def test_lru_cache_budget_order_rebuilds(budget, ops):
    """Get-or-build over a byte-budgeted cache (every entry fits the
    budget alone): the cache never exceeds its budget, the key just
    served always survives, surviving keys keep exact recency order,
    and a key rebuilds at most once per eviction."""
    evicted = []
    cache = LRUPlanCache(max_bytes=budget,
                         on_evict=lambda k, v: evicted.append(k))
    recency = []                       # oldest first, surviving keys
    builds = {k: 0 for k in _KEYS}
    for key, size in ops:
        if cache.get(key) is None:
            builds[key] += 1
            cache.put(key, object(), nbytes=size)
        if key in recency:
            recency.remove(key)
        recency.append(key)
        recency = [k for k in recency if k in cache]
        assert cache.total_bytes <= budget
        assert key in cache            # the entry in use is never evicted
        assert cache.keys() == recency  # eviction order is exactly LRU
        assert cache.get(key) is not None   # immediate re-request hits
    for k in _KEYS:                    # at most one (re)build per residency
        assert builds[k] <= evicted.count(k) + 1


@settings(max_examples=40, deadline=None)
@given(cap=st.integers(1, 4),
       ops=st.lists(st.sampled_from(_KEYS), min_size=1, max_size=40))
def test_lru_cache_entry_cap(cap, ops):
    cache = LRUPlanCache(max_entries=cap)
    for key in ops:
        if cache.get(key) is None:
            cache.put(key, key)
        assert len(cache) <= cap
        assert cache.get(key) == key


# ---------------------------------------------------------------------------
# Persisted serving-schedule table
# ---------------------------------------------------------------------------

_ROW = st.fixed_dictionaries(dict(
    mesh=st.sampled_from(['4x4', '2x8']),
    shape=st.sampled_from(['16x16', '8x8x8']),
    kind=st.sampled_from(['complex', 'real']),
    strategy=st.sampled_from(['all_to_all', 'ppermute']),
    dtype=st.sampled_from([None, 'complex64', 'float32']),
    coalesce_width=st.integers(1, 32),
    overlap_chunks=st.integers(1, 8),
    us_per_request=st.floats(0.1, 1e4),
))


@settings(max_examples=40, deadline=None)
@given(rows=st.lists(_ROW, max_size=16))
def test_schedule_table_merge_and_roundtrip(rows):
    """Merging row-by-row equals merging at once; the LAST row of each
    key wins (the --refresh replace-same-key contract); save/load
    round-trips exactly."""
    tbl = ccost.ScheduleTable(rows)
    inc = ccost.ScheduleTable()
    for r in rows:
        inc.merge([r])
    assert tbl.rows() == inc.rows()
    key_of = ccost.ScheduleTable._row_key
    for r in tbl.rows():
        last = [x for x in rows if key_of(x) == key_of(r)][-1]
        assert r['coalesce_width'] == int(last['coalesce_width'])
        assert r['overlap_chunks'] == int(last['overlap_chunks'])
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, 'BENCH_serve_schedule.json')
        tbl.save(path)
        back = ccost.ScheduleTable.load(path)
        if len(tbl):
            assert back is not None and back.rows() == tbl.rows()
        else:
            assert back is None     # empty tables never shadow the model
