"""Serving throughput: sequential per-request loop vs the FFTEngine.

A stream of independent transform requests is the serving workload the
ROADMAP's north star cares about; the paper's steady-state pipelining
(§V) only pays off across requests if something coalesces them. This
benchmark times, per comm strategy and for complex AND real requests:

* ``sequential`` — one ``plan.forward`` per request, blocking each
  (the honest no-engine serving loop; ``donate=False`` so the caller's
  buffer survives, as a user's would),
* ``engine``     — the same requests through :class:`FFTEngine`:
  measured-autotuned (FFTW_MEASURE-style) coalesce width and
  ``overlap_chunks`` over the request axis, double-buffered dispatch,
  donated staged batches.

With ``--shapes`` the benchmark adds the CONTINUOUS serving mode: one
multi-shape engine with a background drainer (50 ms deadline by
default) serves an interleaved stream of several transform shapes with
no ``flush()`` anywhere — per-shape and aggregate engine/sequential
ratios land in the same JSON. ``--smoke`` includes a small drainer run
so CI exercises the background thread.

Outputs are asserted BIT-IDENTICAL between the two paths before any
number is reported; the two loops are timed INTERLEAVED and reported
as medians, because wall time on a shared host machine drifts by more
than the effect under test. Emits ``BENCH_serve_fft.json`` at the repo
root; ``--refresh`` MERGES new rows into it (replace same-key rows,
keep the rest) and persists each autotuned schedule into
``BENCH_serve_schedule.json`` (same merge semantics), which seeds the
(width, chunks) pick of every later ``FFTEngine`` on this host.

Run:  PYTHONPATH=src python benchmarks/bench_serve_fft.py [--n 32]
          [--shapes 16,8x8x8,32x32] [--refresh] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np                           # noqa: E402

import repro.fft as fft                      # noqa: E402
from repro import comm                       # noqa: E402
from repro.serve import FFTEngine            # noqa: E402
from benchmarks.common import emit           # noqa: E402
from repro.launch.mesh import make_mesh      # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_serve_fft.json")


def make_requests(shape, kind, n_requests):
    rng = np.random.default_rng(3)
    reqs = []
    for _ in range(n_requests):
        x = rng.standard_normal(shape).astype(np.float32)
        if kind == 'complex':
            x = (x + 1j * rng.standard_normal(shape)).astype(np.complex64)
        reqs.append(x)
    return reqs


def run_sequential(plan, reqs):
    """One blocking plan call per request — each request's transposes
    serialize against the next request's pencils."""
    outs = []
    t0 = time.perf_counter()
    for x in reqs:
        y = plan.forward(jax.device_put(jnp.asarray(x), plan.in_sharding))
        jax.block_until_ready(y)
        outs.append(y)
    return outs, (time.perf_counter() - t0) / len(reqs) * 1e6


def run_engine(eng, reqs):
    # submit() inside the timed region: it pays the per-request
    # host->device copy the sequential loop's device_put also pays
    t0 = time.perf_counter()
    tickets = [eng.submit(x) for x in reqs]
    eng.flush()
    outs = [t.result() for t in tickets]
    jax.block_until_ready(outs)
    return outs, (time.perf_counter() - t0) / len(reqs) * 1e6


def bench_one(mesh, shape, strategy, kind, n_requests, repeats,
              persist=False):
    reqs = make_requests(shape, kind, n_requests)
    if kind == 'complex':
        plan = fft.plan(shape, mesh, comm=strategy, donate=False)
    else:
        plan = fft.rplan(shape, mesh, comm=strategy)
    eng = FFTEngine(shape, mesh, comm=strategy)
    # persist=True merges the measured winner into
    # BENCH_serve_schedule.json, seeding every later engine's pick
    eng.autotune(reqs, repeats=max(repeats - 1, 1), persist=persist)
    # warm both paths (compile outside the timed region)
    run_sequential(plan, reqs[:1])
    run_engine(eng, reqs)
    seq_outs, _ = run_sequential(plan, reqs)
    eng_outs, _ = run_engine(eng, reqs)
    for i, (a, b) in enumerate(zip(seq_outs, eng_outs)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(
                f"engine output {i} differs from per-request execution "
                f"({kind}, {strategy})")
    seq_ts, eng_ts = [], []
    for _ in range(repeats):                       # interleaved timing
        seq_ts.append(run_sequential(plan, reqs)[1])
        eng_ts.append(run_engine(eng, reqs)[1])
    # host wall time drifts in multi-second phases, so: interleave the
    # two loops, take each loop's min (the uncontended floor, timeit
    # style) for the headline ratio, and keep the median of adjacent
    # (seq, engine) pair ratios as the load-inclusive cross-check
    seq_us, eng_us = min(seq_ts), min(eng_ts)
    ratios = sorted(s / e for s, e in zip(seq_ts, eng_ts))
    w, c = eng.schedule(kind == 'real')
    return dict(kind=kind, strategy=strategy, n_requests=n_requests,
                seq_us_per_req=seq_us, engine_us_per_req=eng_us,
                speedup=seq_us / eng_us,
                speedup_median_pairs=ratios[len(ratios) // 2],
                coalesce_width=w, overlap_chunks=c, bit_identical=True)


def parse_shapes(spec):
    """'16,8x8x8,32x32' -> [(16, 16, 16), (8, 8, 8), (32, 32)]; a bare
    integer means a cube."""
    shapes = []
    for tok in spec.split(','):
        tok = tok.strip()
        if not tok:
            continue
        if 'x' in tok:
            shapes.append(tuple(int(s) for s in tok.split('x')))
        else:
            shapes.append((int(tok),) * 3)
    return shapes


def bench_mixed(mesh, shapes, strategy, n_requests, repeats, deadline_ms):
    """Continuous multi-shape serving: ONE background engine (drainer
    deadline, no flush() anywhere) vs the per-shape sequential blocking
    loops. Returns one aggregate row plus a row per shape."""
    per_shape = max(n_requests // len(shapes), 2)
    per_shape += 1 - per_shape % 2              # odd: leaves a remainder
    reqs = []                                   # interleaved mixed stream
    for i in range(per_shape):
        for j, shape in enumerate(shapes):
            reqs.append((shape, make_requests(shape, 'complex'
                                              if (i + j) % 2 else 'real',
                                              1)[0]))
    plans = {}
    for shape in shapes:
        plans[(shape, False)] = fft.plan(shape, mesh, comm=strategy,
                                         donate=False)
        plans[(shape, True)] = fft.rplan(shape, mesh, comm=strategy)

    def run_sequential_mixed():
        outs = []
        t0 = time.perf_counter()
        for shape, x in reqs:
            p = plans[(shape, not np.iscomplexobj(x))]
            y = p.forward(jax.device_put(jnp.asarray(x), p.in_sharding))
            jax.block_until_ready(y)
            outs.append(y)
        return outs, (time.perf_counter() - t0) / len(reqs) * 1e6

    def run_drainer(eng):
        t0 = time.perf_counter()
        tickets = [eng.submit(x) for _, x in reqs]
        outs = [t.result(timeout=600) for t in tickets]
        jax.block_until_ready(outs)
        return outs, (time.perf_counter() - t0) / len(reqs) * 1e6

    per_shape_seq = {}
    # watermark 2 + the deadline: full pairs dispatch on the watermark,
    # the odd remainder of every (shape, kind) queue rides the deadline
    # — both drainer triggers are exercised every run
    with FFTEngine(mesh=mesh, comm=strategy, watermark=2,
                   max_wait_ms=deadline_ms) as eng:
        run_sequential_mixed()                  # warm both paths
        run_drainer(eng)
        seq_outs, _ = run_sequential_mixed()
        eng_outs, _ = run_drainer(eng)
        for i, ((shape, _), a, b) in enumerate(zip(reqs, seq_outs,
                                                   eng_outs)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise AssertionError(
                    f"drainer output {i} ({shape}) differs from "
                    f"per-request execution ({strategy})")
        seq_ts, eng_ts = [], []
        for _ in range(repeats):                # interleaved timing
            seq_ts.append(run_sequential_mixed()[1])
            eng_ts.append(run_drainer(eng)[1])
        # per-shape sequential floor (the engine serves the mixed
        # stream as a whole, so per-shape ratios share its us/request)
        for shape in shapes:
            sub = [(s, x) for s, x in reqs if s == shape]
            t0 = time.perf_counter()
            for s, x in sub:
                p = plans[(s, not np.iscomplexobj(x))]
                jax.block_until_ready(p.forward(
                    jax.device_put(jnp.asarray(x), p.in_sharding)))
            per_shape_seq[shape] = ((time.perf_counter() - t0)
                                    / len(sub) * 1e6)
        served = {f"{'x'.join(map(str, s))}{'/real' if r else ''}"
                  for s, r in eng.serving_shapes()}
    seq_us, eng_us = min(seq_ts), min(eng_ts)
    ratios = sorted(s / e for s, e in zip(seq_ts, eng_ts))
    rows = [dict(mode='drainer', kind='mixed', strategy=strategy,
                 shape=[list(s) for s in shapes], mesh="4x4",
                 n_requests=len(reqs), deadline_ms=deadline_ms,
                 seq_us_per_req=seq_us, engine_us_per_req=eng_us,
                 speedup=seq_us / eng_us,
                 speedup_median_pairs=ratios[len(ratios) // 2],
                 served_plans=sorted(served), bit_identical=True)]
    for shape in shapes:
        rows.append(dict(
            mode='drainer', kind='per_shape', strategy=strategy,
            shape=list(shape), mesh="4x4",
            seq_us_per_req=per_shape_seq[shape],
            engine_us_per_req=eng_us,
            speedup=per_shape_seq[shape] / eng_us, bit_identical=True))
    return rows


def _row_key(r):
    shape = r.get('shape')
    return (r.get('mode', 'batch'), str(shape), r.get('mesh'),
            r.get('strategy'), r.get('kind'))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--n', type=int, default=32)
    ap.add_argument('--requests', type=int, default=16)
    ap.add_argument('--repeats', type=int, default=9)
    ap.add_argument('--shapes', type=str, default=None,
                    help='comma-separated shapes (16 = cube, 8x8 = rank '
                         '2) for the continuous multi-shape drainer mode')
    ap.add_argument('--deadline-ms', type=float, default=50.0,
                    help='drainer max-wait deadline for the mixed mode')
    ap.add_argument('--refresh', action='store_true',
                    help='merge rows into the existing BENCH JSONs '
                         '(replace same-key rows, keep the rest) and '
                         'persist autotuned schedules into '
                         'BENCH_serve_schedule.json')
    ap.add_argument('--smoke', action='store_true',
                    help='tiny size / single strategy + a drainer run '
                         'with a 50 ms deadline (CI)')
    args = ap.parse_args(argv)
    n = 16 if args.smoke else args.n
    n_requests = 8 if args.smoke else args.requests
    repeats = 2 if args.smoke else args.repeats
    strategies = ('all_to_all',) if args.smoke else comm.names()
    shapes_spec = args.shapes
    if args.smoke and shapes_spec is None:
        shapes_spec = '8,16x16'                # exercise the drainer in CI

    mesh = make_mesh((4, 4), ("x", "y"))
    shape = (n, n, n)
    print(f"# bench_serve_fft: {n_requests} requests of {n}^3 on 4x4 "
          f"({jax.default_backend()})")
    print("kind,strategy,us,derived")
    results = []
    for strategy in strategies:
        for kind in ('complex', 'real'):
            r = bench_one(mesh, shape, strategy, kind, n_requests, repeats,
                          persist=args.refresh)
            results.append(dict(mode='batch', shape=list(shape),
                                mesh="4x4", **r))
            emit(f"serve_fft/{n}/{strategy}/{kind}/engine",
                 r['engine_us_per_req'],
                 f"seq_us={r['seq_us_per_req']:.1f} "
                 f"speedup={r['speedup']:.2f}x "
                 f"w={r['coalesce_width']} c={r['overlap_chunks']}")
    if shapes_spec:
        shapes = parse_shapes(shapes_spec)
        for strategy in strategies:
            rows = bench_mixed(mesh, shapes, strategy, n_requests,
                               repeats, args.deadline_ms)
            results.extend(rows)
            agg = rows[0]
            emit(f"serve_fft/mixed/{strategy}/drainer",
                 agg['engine_us_per_req'],
                 f"seq_us={agg['seq_us_per_req']:.1f} "
                 f"speedup={agg['speedup']:.2f}x "
                 f"shapes={len(shapes)} deadline={args.deadline_ms}ms")
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
        json.dump(dict(benchmark="serve_fft", backend=jax.default_backend(),
                       results=results), f, indent=1)
    print(f"wrote {os.path.normpath(OUT)} ({len(results)} rows)")
    batch = [r['speedup'] for r in results if r.get('mode') == 'batch']
    if batch:
        print(f"# worst engine speedup vs sequential loop (batch mode): "
              f"{min(batch):.2f}x")
    drainer = [r['speedup'] for r in results
               if r.get('mode') == 'drainer' and r.get('kind') == 'mixed']
    if drainer:
        print(f"# continuous mode (deadline-stall included): "
              f"{min(drainer):.2f}x vs the blocking loop")


if __name__ == "__main__":
    main()
