"""Fused operator plan vs unfused plan composition for the FFT-conv
mixer shape: dispatch count, HLO-parsed wire bytes and wall time, per
comm strategy, on the 16-fake-device 4x4 mesh.

Three execution modes of the same causal convolution (the
``models/ssd.py:fftconv_apply`` workload — a ``(B, d, n)`` batch of
rank-1 length-n real transforms):

* ``unfused``     — the pre-operator-plan serving shape: forward(x),
                    forward(k), a jitted pointwise stage, inverse —
                    FOUR separately dispatched executables, the
                    spectrum crossing the rfft truncated-axis boundary
                    gather in between.
* ``fused``       — ``fft.plan_op(..., n_spectra=1)``: the training
                    path, kernel spectrum as a runtime operand of the
                    SAME single dispatch.
* ``fused_baked`` — ``fft.plan_op(..., spectra=(k,))``: the eval path,
                    kernel FFT baked once per plan; the per-call work
                    no longer transforms the kernel at all.

Wire bytes are parsed from the compiled HLO (deterministic); wall-us
from block-until-ready timing (host-latency noisy). The structural
claims are asserted on every run: fused wire bytes <= unfused, and
strictly fewer dispatches.

Emits ``BENCH_fftconv.json`` at the repo root.

Run:  PYTHONPATH=src python benchmarks/bench_fftconv.py [--seq 512] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

import repro.fft as fft                       # noqa: E402
from repro.launch import hlostats             # noqa: E402
from benchmarks.common import time_jax, emit  # noqa: E402
from repro.launch.mesh import make_mesh       # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_fftconv.json")

STRATEGIES = ("all_to_all", "ppermute", "hierarchical")


def _wire_bytes(jitted, *args) -> float:
    txt = jitted.lower(*args).compile().as_text()
    return hlostats.analyze(txt)["collective_bytes_total"]


@jax.jit
def _pw(y, k):
    re, im = fft.spectral_mul(jnp.real(y), jnp.imag(y),
                              (jnp.real(k), jnp.imag(k)))
    return jax.lax.complex(re, im)


def bench_one(mesh, n, batch, strategy, iters):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(batch + (n,)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((batch[-1], n)), jnp.float32)
    rows = []

    # -- unfused: 4 dispatches (fwd x, fwd k, pointwise, inverse) ------
    rp = fft.rplan((n,), mesh, comm=strategy, donate=False)
    fwd = jax.jit(rp.forward)
    inv = jax.jit(rp.inverse)

    def unfused(x, k):
        return inv(_pw(fwd(x), fwd(k)))

    us = time_jax(unfused, x, k, warmup=2, iters=iters)
    spec_x, spec_k = fwd(x), fwd(k)
    wb = (_wire_bytes(fwd, x) + _wire_bytes(fwd, k)
          + _wire_bytes(_pw, spec_x, spec_k)
          + _wire_bytes(inv, _pw(spec_x, spec_k)))
    rows.append(dict(kind="unfused", strategy=strategy, dispatches=4,
                     us=us, wire_bytes=wb))

    # -- fused, runtime kernel operand (training path): ONE dispatch ---
    op = fft.plan_op((n,), mesh, op=fft.spectral_mul, real=True,
                     n_spectra=1, comm=strategy, donate=False)
    fused = jax.jit(op.apply)
    us = time_jax(fused, x, k, warmup=2, iters=iters)
    rows.append(dict(kind="fused", strategy=strategy, dispatches=1,
                     us=us, wire_bytes=_wire_bytes(fused, x, k)))

    # -- fused, kernel spectrum baked (eval path): ONE dispatch --------
    opb = fft.plan_op((n,), mesh, op=fft.spectral_mul, real=True,
                      comm=strategy, donate=False, spectra=(k,))
    opb.apply(x)                    # bake outside the timed region
    fused_b = jax.jit(opb.apply)
    us = time_jax(fused_b, x, warmup=2, iters=iters)
    rows.append(dict(kind="fused_baked", strategy=strategy, dispatches=1,
                     us=us, wire_bytes=_wire_bytes(fused_b, x)))
    assert opb.bake_count == 1, opb.bake_count
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512,
                    help="sequence length S; conv transform is n=2S")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny size / single strategy (CI)")
    args = ap.parse_args(argv)
    S = 128 if args.smoke else args.seq
    iters = 3 if args.smoke else args.iters
    strategies = STRATEGIES[:1] if args.smoke else STRATEGIES
    n = 2 * S
    batch = (2, 4) if args.smoke else (4, 8)      # (B, d)

    mesh = make_mesh((4, 4), ("x", "y"))
    print(f"# bench_fftconv: causal conv len n={n}, batch {batch}, "
          f"4x4 mesh ({jax.default_backend()})")
    print("kind,strategy,us,dispatches,wire_bytes")
    results = []
    for strategy in strategies:
        rows = bench_one(mesh, n, batch, strategy, iters)
        by = {r["kind"]: r for r in rows}
        for r in rows:
            results.append(dict(n=n, batch=list(batch), mesh="4x4", **r))
            emit(f"fftconv/{n}/{strategy}/{r['kind']}", r["us"],
                 f"dispatches={r['dispatches']} "
                 f"wire_bytes={r['wire_bytes']:.0f}")
        un = by["unfused"]
        for kind in ("fused", "fused_baked"):
            fb = by[kind]
            # the structural contract, asserted on every run: fusion
            # never adds wire traffic and always removes dispatches
            assert fb["wire_bytes"] <= un["wire_bytes"], (strategy, kind)
            assert fb["dispatches"] < un["dispatches"], (strategy, kind)
            print(f"#   {strategy}/{kind}: wire "
                  f"{fb['wire_bytes'] / max(un['wire_bytes'], 1):.2f}x  "
                  f"dispatches {fb['dispatches']}/{un['dispatches']}  "
                  f"wall {fb['us'] / un['us']:.2f}x (vs unfused)")
    with open(OUT, "w") as f:
        json.dump(dict(benchmark="fftconv", backend=jax.default_backend(),
                       results=results), f, indent=1)
    print(f"wrote {os.path.normpath(OUT)} ({len(results)} rows)")


if __name__ == "__main__":
    main()
