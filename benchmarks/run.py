"""Benchmark driver: one section per paper table/figure, plus host-mode
measurements of our implementation and (when present) the dry-run
roofline tables. CSV convention: ``name,us_per_call,derived``.

``--smoke`` skips the paper sections and runs only the wall-clock
benchmark scripts at their tiny CI sizes.

This parent process never imports jax: every section runs in a child
of its own, one after the other, so on an accelerator each child holds
the device alone. A failed child makes this script exit non-zero.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _section(title: str) -> None:
    print(f"\n==== {title} " + "=" * max(0, 60 - len(title)), flush=True)


def _child(env, label: str, argv) -> bool:
    r = subprocess.run([sys.executable, *argv], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(HERE))
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stdout.write(f"{label},nan,FAILED\n")
        sys.stderr.write(r.stderr[-2000:])
    sys.stdout.flush()
    return r.returncode == 0


def _module(env, name: str, *args: str) -> bool:
    return _child(env, name, ["-m", f"benchmarks.{name}", *args])


def _script(env, name: str, *args: str) -> bool:
    return _child(env, os.path.splitext(name)[0],
                  [os.path.join(HERE, name), *args])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--smoke', action='store_true',
                    help='tiny sizes, wall-clock scripts only (CI)')
    args = ap.parse_args(argv)
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    ok = []

    if not args.smoke:
        for title, mod in (
                ("Paper Table 1 (cycle counts, model vs measured)",
                 "paper_table1"),
                ("Paper Figure 3 (pencil throughput)", "paper_fig3"),
                ("Paper Figure 4 (comm/compute breakdown)", "paper_fig4"),
                ("Paper Figures 5/6/7 (weak/strong scaling, bandwidth)",
                 "paper_fig567"),
                ("Paper Table 2 (cross-machine comparison)",
                 "paper_table2")):
            _section(title)
            ok.append(_module(env, mod))

        _section("Host-mode distributed wsFFT (fake-device mesh, "
                 "wall clock)")
        for wargs in (["4", "4", "32", "auto"], ["4", "4", "64", "auto"],
                      ["4", "4", "64", "stockham"]):
            ok.append(_child(env, f"wsfft_host/{'x'.join(wargs)}",
                             ["-m", "benchmarks._wsfft_worker", *wargs]))

    size = ['--smoke'] if args.smoke else ['--n', '32']
    smoke = ['--smoke'] if args.smoke else []
    for title, script, sargs in (
            ("rfft vs complex plans (wire bytes + wall us, 4x4 mesh)",
             "bench_rfft.py", size),
            ("FFT-conv operator plans: fused vs unfused (4x4 mesh)",
             "bench_fftconv.py", smoke),
            ("FFT serving: sequential loop vs batched engine (4x4 mesh)",
             "bench_serve_fft.py", size),
            ("FFT service: socket overhead + adaptive drainer policy",
             "bench_serve_service.py", smoke),
            ("Kernel tier: local methods + fused superstep A/B",
             "bench_kernels.py", smoke)):
        _section(title)
        ok.append(_script(env, script, *sargs))

    # Roofline tables are produced by the dry-run pipeline (launch/dryrun
    # + benchmarks/roofline_fft); aggregate whatever artifacts exist.
    base = os.path.dirname(HERE)
    if any(os.path.isdir(os.path.join(base, "results", d)) and
           os.listdir(os.path.join(base, "results", d))
           for d in ("dryrun_final", "dryrun")):
        _section("Roofline summary (from dry-run artifacts)")
        ok.append(_module(env, "roofline"))

    failed = ok.count(False)
    if failed:
        print(f"\n{failed} benchmark section(s) FAILED", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
