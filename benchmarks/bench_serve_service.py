"""Multi-tenant service front-end: socket overhead + adaptive drainer.

Two questions, one JSON:

1. **What does the socket front-end cost?** The same sequential
   request stream is served (a) directly on an in-process
   :class:`FFTEngine` and (b) through :class:`FFTService` over a unix
   socket — wire framing, admission, writer threads and all. The
   ``overhead`` row reports both us/request and the ratio.

2. **Does the adaptive drainer policy earn its keep?** Three arrival
   traces — ``steady_slow`` (a trickle), ``steady_fast`` (a dense
   stream), ``bursty`` (burst/gap) — are each served under every fixed
   (watermark, max_wait_ms) setting and under the adaptive policy,
   which retargets the drainer from its EWMA arrival-rate estimate.
   Per cell: client-observed mean and p99 latency (timestamped at
   frame arrival by the reader thread) and wall time. The summary row
   lists the traces where the adaptive policy beat EVERY fixed setting
   on mean latency — a fixed-wide drainer donates deadline stalls to a
   trickle, a fixed-narrow one burns a dispatch per request under
   load; no single fixed point wins every trace, which is the point.

Each cell runs once untimed (compiles, plan/group warmup) and then
``--repeats`` timed passes; the reported numbers are the best pass
(the uncontended floor, timeit style). In full mode the run FAILS if
the adaptive policy beats every fixed setting on no trace; ``--smoke``
reports without asserting (CI hosts are noisy). Emits
``BENCH_serve_service.json`` at the repo root; ``--refresh`` merges
rows (replace same-key rows, keep the rest) and persists the adaptive
policy's load-level rows into ``BENCH_serve_schedule.json``.

Run:  PYTHONPATH=src python benchmarks/bench_serve_service.py
          [--requests 50] [--repeats 2] [--refresh] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                   # noqa: E402
import numpy as np                           # noqa: E402

from repro.comm import cost as ccost         # noqa: E402
from repro.serve import (AdaptivePolicy, FaultPlan, FaultPoint,  # noqa: E402
                         FFTEngine, FFTService, SLOClass, TenantConfig)
from benchmarks.common import emit           # noqa: E402
from repro.launch.mesh import make_mesh      # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..",
                   "BENCH_serve_service.json")
SHAPE = (8, 8, 8)
MAX_COALESCE = 8
FIXED = [(1, 1.0), (4, 5.0), (8, 20.0)]      # (watermark, max_wait_ms)


def make_requests(count, seed=11):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(SHAPE)
             + 1j * rng.standard_normal(SHAPE)).astype(np.complex64)
            for _ in range(count)]


def traces(smoke: bool):
    """trace name -> arrival offsets in seconds (relative to t0)."""
    if smoke:
        return {
            'steady_slow': [i * 0.030 for i in range(8)],
            'steady_fast': [i * 0.001 for i in range(18)],
            'bursty': [b * 0.120 for b in range(2) for _ in range(6)],
        }
    return {
        'steady_slow': [i * 0.040 for i in range(24)],
        'steady_fast': [i * 0.001 for i in range(50)],
        'bursty': [b * 0.150 for b in range(5) for _ in range(8)],
    }


def serve_trace(svc, client, reqs, offsets):
    """Submit one request per arrival offset; return (latencies_ms,
    wall_s), latency stamped at the result frame's arrival."""
    t0 = time.perf_counter()
    submits, tickets = [], []
    for x, off in zip(reqs, offsets):
        wait = t0 + off - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        submits.append(time.monotonic())
        tickets.append(client.submit(x))
    outs = [t.result(timeout=600) for t in tickets]
    wall = time.perf_counter() - t0
    assert all(o.shape == SHAPE for o in outs)
    lats = [(t.done_at - s) * 1e3 for t, s in zip(tickets, submits)]
    return lats, wall


def p99(vals):
    s = sorted(vals)
    return s[min(len(s) - 1, int(0.99 * len(s)))]


def run_cell(eng, sock, config, reqs, offsets, repeats):
    """One (trace, drainer-config) cell: a fresh service on the shared
    engine, one warm pass, then the best of ``repeats`` timed passes.
    Returns (row fields, policy or None)."""
    name, watermark, wait_ms = config
    if name == 'adaptive':
        policy = AdaptivePolicy(max_coalesce=MAX_COALESCE,
                                max_wait_ms=50.0)
        slo_wait = 50.0
    else:
        policy = None
        eng.set_drainer(watermark=watermark, max_wait_ms=wait_ms)
        slo_wait = wait_ms
    svc = FFTService(
        engine=eng, policy=policy, persist_policy=False,
        max_inflight=1000,
        slo_classes={'bench': SLOClass('bench', deadline_ms=1e9,
                                       max_wait_ms=slo_wait)},
        tenants=[TenantConfig('bench', max_inflight=1000, slo='bench')],
    ).start(sock)
    try:
        with svc.local_client('bench') as c:
            best = None
            for i in range(repeats + 1):     # pass 0 warms compiles
                lats, wall = serve_trace(svc, c, reqs, offsets)
                if i == 0:
                    continue
                row = dict(mean_ms=sum(lats) / len(lats),
                           p99_ms=p99(lats), wall_s=wall)
                if best is None or row['mean_ms'] < best['mean_ms']:
                    best = row
            c.drain(timeout=120)
    finally:
        svc.close(drain=True)
    best = {k: round(v, 3) for k, v in best.items()}
    return best, policy


def _chaos_plan():
    """The degraded-mode schedule: scripted (every-Nth) faults so the
    row is reproducible — no fire at hit 0, the handshake survives."""
    return FaultPlan(seed=3, points=[
        FaultPoint('service.writer', 'drop', every=7, limit=8),
        FaultPoint('service.writer', 'truncate', every=11, limit=4),
        FaultPoint('engine.drainer', 'stall', every=9, delay_s=0.02,
                   limit=6),
        FaultPoint('engine.dispatch', 'raise', every=13, limit=2),
    ])


def run_chaos_cell(eng, sock, reqs, plan):
    """One degraded-mode cell: the resilient client loop
    (reconnect + idempotent resubmit) against an armed fault plan;
    per-request latency measured around ``transform``. The cell
    asserts exactly-once delivery — every request served, none
    failed — and reports how much the faults cost."""
    eng.set_drainer(watermark=4, max_wait_ms=5.0)
    svc = FFTService(
        engine=eng, policy=None, persist_policy=False, faults=plan,
        slo_classes={'bench': SLOClass('bench', 1e9, 5.0)},
        tenants=[TenantConfig('bench', max_inflight=1000, slo='bench')],
    ).start(sock)
    lats = []
    try:
        with svc.local_client('bench') as c:
            c.transform(reqs[:2], timeout=120.0)       # warm compiles
            t0 = time.perf_counter()
            for x in reqs:
                s = time.perf_counter()
                c.transform([x], timeout=120.0, deadline_s=120.0)
                lats.append((time.perf_counter() - s) * 1e3)
            wall = time.perf_counter() - t0
            reconnects = c.reconnects
        tm = svc.metrics()['tenants']['bench']
        assert tm['failed'] == 0, f"degraded mode lost work: {tm}"
        fired = 0 if plan is None else plan.total_fired()
    finally:
        svc.close(drain=True)
        eng.faults = None
    return dict(mean_ms=round(sum(lats) / len(lats), 3),
                p99_ms=round(p99(lats), 3), wall_s=round(wall, 3),
                reconnects=reconnects, faults_fired=fired)


def _row_key(r):
    return (r.get('mode'), r.get('trace'), r.get('config'),
            str(r.get('shape')))


def _write_results(args, results):
    """Write (or --refresh merge) the rows into the BENCH JSON."""
    if args.refresh and os.path.exists(OUT):
        try:
            with open(OUT) as f:
                old = json.load(f).get('results', [])
        except (OSError, ValueError):
            old = []
        fresh = {_row_key(r) for r in results}
        kept = [r for r in old if _row_key(r) not in fresh]
        results = kept + results
        print(f"# --refresh: kept {len(kept)} existing rows")
    with open(OUT, "w") as f:
        json.dump(dict(benchmark="serve_service",
                       backend=jax.default_backend(),
                       results=results), f, indent=1)
    print(f"wrote {os.path.normpath(OUT)} ({len(results)} rows)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--requests', type=int, default=64,
                    help='request count for the overhead cell')
    ap.add_argument('--repeats', type=int, default=2)
    ap.add_argument('--refresh', action='store_true',
                    help='merge rows into the existing BENCH JSON and '
                         'persist adaptive load-level rows into '
                         'BENCH_serve_schedule.json')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny traces, 1 repeat, no win assertion (CI)')
    ap.add_argument('--chaos', action='store_true',
                    help='degraded-mode rows only: the resilient client '
                         'against an armed fault plan vs a clean run')
    args = ap.parse_args(argv)
    repeats = 1 if args.smoke else args.repeats
    n_overhead = 12 if args.smoke else args.requests

    mesh = make_mesh((4, 4), ("x", "y"))
    sock = os.path.join(tempfile.mkdtemp(prefix="bench_serve_service_"),
                        "s.sock")
    shape_s = 'x'.join(map(str, SHAPE))
    print(f"# bench_serve_service: {shape_s} complex on 4x4 "
          f"({jax.default_backend()})")
    results = []

    beats = []
    with FFTEngine(mesh=mesh, max_coalesce=MAX_COALESCE, max_wait_ms=20.0,
                   schedule_table=None) as eng:
        if args.chaos:
            # -- degraded mode: same stream, clean vs armed fault plan.
            # The interesting numbers are the latency cost of riding
            # out drops/truncations/stalls and that NOTHING is lost.
            n = 24 if args.smoke else 48
            reqs = make_requests(n, seed=23)
            cells = {}
            for label in ('clean', 'degraded'):
                plan = _chaos_plan() if label == 'degraded' else None
                cell = run_chaos_cell(eng, sock, reqs, plan)
                cells[label] = cell
                results.append(dict(mode='chaos', trace='degraded_mode',
                                    config=label, shape=list(SHAPE),
                                    mesh="4x4", n_requests=n, **cell))
                emit(f"serve_service/chaos/{label}",
                     cell['mean_ms'] * 1e3,
                     f"p99={cell['p99_ms']:.1f}ms "
                     f"reconnects={cell['reconnects']} "
                     f"faults={cell['faults_fired']}")
            slow = cells['degraded']['mean_ms'] / max(
                cells['clean']['mean_ms'], 1e-9)
            print(f"# chaos: degraded {cells['degraded']['mean_ms']:.2f}ms"
                  f" vs clean {cells['clean']['mean_ms']:.2f}ms "
                  f"({slow:.2f}x, {cells['degraded']['reconnects']} "
                  f"reconnects, {cells['degraded']['faults_fired']} "
                  f"faults fired, zero lost)")
            assert cells['degraded']['faults_fired'] > 0, \
                "chaos cell fired no faults"
            _write_results(args, results)
            return
        # -- 1. socket front-end overhead (sequential stream) ------------
        reqs = make_requests(n_overhead)
        eng.set_drainer(watermark=1, max_wait_ms=1.0)
        for x in reqs[:2]:                   # warm compiles
            eng.submit(x).result(timeout=600)
        t0 = time.perf_counter()
        for x in reqs:
            eng.submit(x).result(timeout=600)
        eng_us = (time.perf_counter() - t0) / len(reqs) * 1e6

        svc = FFTService(
            engine=eng, policy=None, persist_policy=False,
            slo_classes={'bench': SLOClass('bench', 1e9, 1.0)},
            tenants=[TenantConfig('bench', max_inflight=1000,
                                  slo='bench')],
        ).start(sock)
        with svc.local_client('bench') as c:
            c.transform(reqs[:2])            # warm the wire path
            t0 = time.perf_counter()
            c.transform(reqs)
            svc_us = (time.perf_counter() - t0) / len(reqs) * 1e6
        svc.close(drain=True)
        row = dict(mode='overhead', shape=list(SHAPE), mesh="4x4",
                   n_requests=len(reqs),
                   engine_us_per_req=round(eng_us, 1),
                   service_us_per_req=round(svc_us, 1),
                   overhead_ratio=round(svc_us / eng_us, 3))
        results.append(row)
        emit(f"serve_service/overhead/{shape_s}", svc_us,
             f"engine_us={eng_us:.1f} ratio={row['overhead_ratio']:.2f}x")

        # -- 2. adaptive vs fixed drainer under arrival traces -----------
        configs = ([(f"fixed_w{w}_{ms:g}ms", w, ms) for w, ms in FIXED]
                   + [('adaptive', None, None)])
        beats = []
        last_policy = None
        for trace, offsets in traces(args.smoke).items():
            reqs = make_requests(len(offsets), seed=17)
            means = {}
            for config in configs:
                cell, policy = run_cell(eng, sock, config, reqs,
                                        offsets, repeats)
                if policy is not None:
                    last_policy = policy
                means[config[0]] = cell['mean_ms']
                results.append(dict(mode='policy', trace=trace,
                                    config=config[0], shape=list(SHAPE),
                                    mesh="4x4", n_requests=len(offsets),
                                    watermark=config[1],
                                    max_wait_ms=config[2], **cell))
                emit(f"serve_service/{trace}/{config[0]}",
                     cell['mean_ms'] * 1e3,
                     f"p99={cell['p99_ms']:.1f}ms wall={cell['wall_s']:.2f}s")
            fixed_best = min(v for k, v in means.items()
                             if k != 'adaptive')
            if means['adaptive'] < fixed_best:
                beats.append(trace)
            print(f"# {trace}: adaptive {means['adaptive']:.2f}ms vs "
                  f"best fixed {fixed_best:.2f}ms")

        results.append(dict(mode='summary',
                            adaptive_beats_all_fixed_on=beats,
                            fixed_settings=[list(f) for f in FIXED]))

        if args.refresh and last_policy is not None:
            rows = last_policy.rows(dict(eng.mesh.shape), SHAPE,
                                    'complex', 'auto',
                                    backend=jax.default_backend())
            path = ccost.persist_schedule_rows(
                rows, ccost.schedule_table_path())
            if path:
                print(f"# persisted {len(rows)} load-level rows into "
                      f"{os.path.normpath(path)}")

    _write_results(args, results)
    if beats:
        print(f"# adaptive beat every fixed setting on: {beats}")
    if not args.smoke:
        assert beats, ("the adaptive policy beat every fixed "
                       "(watermark, max_wait_ms) setting on NO trace")


if __name__ == "__main__":
    main()
