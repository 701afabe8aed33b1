"""Microbenchmark of the kernel tier: local pencil methods per tier,
per-backend cost-model rows, and the fused twiddle+transpose superstep
A/B on the distributed 32^3 plan.

Emits ``BENCH_kernels.json`` at the repo root so the perf trajectory
accumulates data across PRs. Three row sections:

* ``local`` — wall us of ``repro.fft.methods.apply`` per (method,
  kernel tier) on this host's backend, next to the
  ``wse_model.pencil_cycles_backend`` prediction. On CPU the Pallas
  tier runs in interpret mode, so these rows quantify the interpret
  penalty the cost model prices via ``interpret_penalty``.
* ``model`` — deterministic per-backend cycle predictions (cpu / gpu /
  tpu / wse x reference / pallas): what the scheduler would price on
  hardware this container doesn't have. ``us`` is null by design.
* ``superstep`` — fused (default) vs unfused re-plan of the full
  distributed 32^3 stockham FFT on the 4x4 fake-device mesh, per
  kernel tier: median wall us plus loop-aware HLO statistics
  (instruction count, HBM traffic proxy) from
  :mod:`repro.launch.hlostats`.

With ``--refresh`` new grid points are MERGED into the existing file
(same-key rows replaced, everything else kept). ``--smoke`` runs a
seconds-long CI subset and does not write the JSON.

In full mode the run asserts the PR's headline claim: on the Pallas
tier the fused superstep beats the unfused re-plan at 32^3 on the host
mesh — on HLO instruction count and/or median wall us. (The reference
tier is exempt: XLA already fuses the pure-jnp path, so explicit
fusion is only a wash there; the win comes from folding the twiddle
and transpose into the kernel's emit, which XLA cannot do across a
``pallas_call`` boundary.)

Run:  PYTHONPATH=src python benchmarks/bench_kernels.py \
          [--refresh | --smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                  # noqa: E402
import jax.numpy as jnp                     # noqa: E402
import numpy as np                          # noqa: E402

import repro.fft as fft                     # noqa: E402
from repro.core import wse_model as wm      # noqa: E402
from repro.fft import methods               # noqa: E402
from repro.fft import pencil as fpencil     # noqa: E402
from repro.launch import hlostats           # noqa: E402
from benchmarks.common import time_jax, emit  # noqa: E402
from repro.launch.mesh import make_mesh     # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")

TIERS = ("reference", "pallas")
#: local grid: (method, batch, n) — b*n is the per-PE working set
LOCAL = [("stockham", 64, 1024), ("stockham", 256, 256),
         ("four_step", 64, 1024), ("block", 64, 1024)]
#: deterministic model rows: every costed backend at the paper's n
MODEL_N = 4096
#: the fused-beats-unfused acceptance gate reads this transform size
GATE_N = 32


def bench_local(method, b, n, tier):
    rng = np.random.default_rng(1)
    re = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
    im = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)

    def f(r, i):
        return methods.apply(r, i, method=method, kernel=tier)

    return time_jax(jax.jit(f), re, im)


def bench_superstep(tier, n):
    mesh = make_mesh((4, 4), ("x", "y"))
    plan = fft.plan((n, n, n), mesh, method="stockham", kernel=tier,
                    donate=False)
    rng = np.random.default_rng(2)
    re = jax.device_put(
        jnp.asarray(rng.standard_normal((n, n, n)), jnp.float32),
        plan._pplan.sharding())
    im = jax.device_put(jnp.zeros((n, n, n), jnp.float32),
                        plan._pplan.sharding())
    out = {}
    for fused in (True, False):
        fn, _, _ = fpencil.make_fft(plan._pplan, fused=fused)
        jf = jax.jit(fn)
        txt = jf.lower(re, im).compile().as_text()
        comps = hlostats.parse_computations(txt)
        stats = hlostats.analyze(txt)
        out[fused] = dict(
            us=time_jax(jf, re, im),
            hlo_ops=sum(len(v) for v in comps.values()),
            hbm_bytes_proxy=stats["hbm_bytes_proxy"])
    return out


def _row_key(r):
    return (r.get("section"), r.get("backend"), r.get("mesh"),
            r.get("method"), r.get("kernel"), r.get("fused"),
            r.get("n"), r.get("b"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--refresh", action="store_true",
                    help="merge new grid points into the existing JSON "
                         "(replace same-key rows, keep the rest) instead "
                         "of overwriting it")
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: one local config per tier and one "
                         "tiny fused A/B; no JSON, no gate")
    args = ap.parse_args(argv)
    bk = jax.default_backend()
    local = [("stockham", 16, 128)] if args.smoke else LOCAL
    gate_n = 16 if args.smoke else GATE_N
    sup_tiers = ("pallas",) if args.smoke else TIERS

    print("# bench_kernels: kernel tier + fused superstep A/B")
    print("section,backend,method,kernel,fused,n,b,us,derived")
    results = []

    # ---- local pencil methods per tier (this backend) ----
    for method, b, n in local:
        for tier in TIERS:
            us = bench_local(method, b, n, tier)
            model = wm.pencil_cycles_backend(n, "fp32", method,
                                             backend=bk, kernel=tier)
            emit(f"kernels/local/{bk}/{method}/{tier}/n{n}b{b}", us,
                 f"model_cycles={model:.0f}")
            results.append(dict(section="local", backend=bk,
                                method=method, kernel=tier, n=n, b=b,
                                us=us, model_cycles=model))

    # ---- deterministic per-backend model rows ----
    if not args.smoke:
        for backend in sorted(wm.BACKEND_COMPUTE):
            for tier in TIERS:
                model = wm.pencil_cycles_backend(
                    MODEL_N, "fp32", "stockham",
                    backend=backend, kernel=tier)
                results.append(dict(section="model", backend=backend,
                                    method="stockham", kernel=tier,
                                    n=MODEL_N, us=None,
                                    model_cycles=model))

    # ---- fused vs unfused distributed superstep A/B ----
    ab_by_tier = {}
    for tier in sup_tiers:
        ab = bench_superstep(tier, gate_n)
        ab_by_tier[tier] = ab
        for fused, r in sorted(ab.items(), reverse=True):
            emit(f"kernels/superstep/4x4/{tier}/"
                 f"{'fused' if fused else 'unfused'}/n{gate_n}",
                 r["us"],
                 f"hlo_ops={r['hlo_ops']} "
                 f"hbm_mb={r['hbm_bytes_proxy'] / 1e6:.2f}")
            results.append(dict(section="superstep", backend=bk,
                                mesh="4x4", method="stockham",
                                kernel=tier, fused=fused, n=gate_n,
                                us=r["us"], hlo_ops=r["hlo_ops"],
                                hbm_bytes_proxy=r["hbm_bytes_proxy"]))

    if not args.smoke:
        ab = ab_by_tier["pallas"]
        ops_win = ab[True]["hlo_ops"] < ab[False]["hlo_ops"]
        us_win = ab[True]["us"] < ab[False]["us"]
        assert ops_win or us_win, (
            f"fused superstep beat unfused on NEITHER HLO op count "
            f"({ab[True]['hlo_ops']} vs {ab[False]['hlo_ops']}) nor "
            f"wall us ({ab[True]['us']:.0f} vs {ab[False]['us']:.0f}) "
            f"on the pallas tier at {gate_n}^3")
        print(f"# fused beats unfused (pallas, {gate_n}^3): "
              f"hlo_ops {ab[True]['hlo_ops']} vs {ab[False]['hlo_ops']}"
              f"{' (win)' if ops_win else ''}, "
              f"us {ab[True]['us']:.0f} vs {ab[False]['us']:.0f}"
              f"{' (win)' if us_win else ''}")

    if args.smoke:
        print("# --smoke: JSON not written")
        return
    if args.refresh and os.path.exists(OUT):
        try:
            with open(OUT) as f:
                old = json.load(f).get("results", [])
        except (OSError, ValueError):
            old = []
        fresh = {_row_key(r) for r in results}
        kept = [r for r in old if _row_key(r) not in fresh]
        results = kept + results
        print(f"# --refresh: kept {len(kept)} existing rows")
    with open(OUT, "w") as f:
        json.dump(dict(benchmark="kernels", backend=bk,
                       results=results), f, indent=1)
    print(f"wrote {os.path.normpath(OUT)} ({len(results)} rows)")


if __name__ == "__main__":
    main()
