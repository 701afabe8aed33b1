"""Complex vs real (rfft) plans: measured wall time AND measured wire
bytes, per comm strategy, on the 16-fake-device 4x4 mesh.

The rfft half-spectrum pipeline claims ~half the wire bytes and pencil
flops from the first superstep on; this benchmark checks the claim on
real executables, not just the cycle model: wall-us from
block-until-ready timing, wire bytes by parsing the compiled HLO for
collective operand bytes (``repro.launch.hlostats``). Three plan kinds
per strategy:

* ``complex``     — the baseline complex plan fed the real field as
                    complex (what a user does without rfft support)
* ``real``        — ``fft.rplan``: np.rfftn-layout output (includes the
                    truncated-axis boundary gather)
* ``real_padded`` — ``fft.rplan(..., padded_spectrum=True)``: the
                    native distributed half spectrum (pure pipeline)

Emits ``BENCH_rfft.json`` at the repo root.

Run:  PYTHONPATH=src python benchmarks/bench_rfft.py [--n 32] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
import numpy as np                           # noqa: E402

import repro.fft as fft                      # noqa: E402
from repro import comm                       # noqa: E402
from repro.launch import hlostats            # noqa: E402
from benchmarks.common import time_jax, emit  # noqa: E402
from repro.launch.mesh import make_mesh      # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_rfft.json")


def roundtrip_fn(plan):
    def f(x):
        return plan.inverse(plan.forward(x))
    return jax.jit(f)


def wire_bytes(fn, x) -> float:
    txt = fn.lower(x).compile().as_text()
    return hlostats.analyze(txt)['collective_bytes_total']


def bench_one(mesh, shape, strategy, kind, iters):
    rng = np.random.default_rng(0)
    xr = rng.standard_normal(shape).astype(np.float32)
    if kind == 'complex':
        p = fft.plan(shape, mesh, comm=strategy)
        x = jax.device_put(jnp.asarray(xr, jnp.complex64), p.in_sharding)
    else:
        p = fft.rplan(shape, mesh, comm=strategy,
                      padded_spectrum=(kind == 'real_padded'))
        x = jax.device_put(jnp.asarray(xr), p.in_sharding)
    fn = roundtrip_fn(p)
    us = time_jax(fn, x, warmup=2, iters=iters)
    wb = wire_bytes(fn, x)
    # analytic (WSE) model — the measured table reflects host-CPU
    # collective latency, not the wire claim under test here
    model = p.plan_cost('fp32', measured=None).wire_cycles
    return dict(kind=kind, strategy=strategy, us=us, wire_bytes=wb,
                model_wire_cycles=model)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--n', type=int, default=32)
    ap.add_argument('--iters', type=int, default=5)
    ap.add_argument('--smoke', action='store_true',
                    help='tiny size / single strategy (CI)')
    args = ap.parse_args(argv)
    n = 16 if args.smoke else args.n
    iters = 3 if args.smoke else args.iters
    strategies = ('all_to_all',) if args.smoke else comm.names()

    mesh = make_mesh((4, 4), ("x", "y"))
    shape = (n, n, n)
    print(f"# bench_rfft: fwd+inv round trip, {n}^3 on 4x4 "
          f"({jax.default_backend()})")
    print("kind,strategy,us,wire_bytes,model_wire_cycles")
    results = []
    for strategy in strategies:
        rows = {}
        for kind in ('complex', 'real', 'real_padded'):
            r = bench_one(mesh, shape, strategy, kind, iters)
            rows[kind] = r
            results.append(dict(shape=list(shape), mesh="4x4", **r))
            emit(f"rfft/{n}/{strategy}/{kind}", r['us'],
                 f"wire_bytes={r['wire_bytes']:.0f}")
        cb = rows['complex']
        for kind in ('real', 'real_padded'):
            rb = rows[kind]
            print(f"#   {strategy}/{kind}: wire {rb['wire_bytes'] / max(cb['wire_bytes'], 1):.2f}x"
                  f"  wall {rb['us'] / cb['us']:.2f}x"
                  f"  model-wire {rb['model_wire_cycles'] / cb['model_wire_cycles']:.2f}x"
                  " (vs complex)")
    with open(OUT, "w") as f:
        json.dump(dict(benchmark="rfft", backend=jax.default_backend(),
                       results=results), f, indent=1)
    print(f"wrote {os.path.normpath(OUT)} ({len(results)} rows)")


if __name__ == "__main__":
    main()
