"""Roofline dry-run for the paper's own artifact: distributed 3-D FFT
on the production meshes.

The paper's 512^3-on-512x512-PEs cell maps to TPU as 512^3 on 16x16
chips — each chip owns m^2 = 32^2 = 1024 pencils per superstep, i.e.
the §4.4 multi-pencil regime the paper analyzes but never runs. The
multi-pod mesh folds a batch of independent transforms over the 'pod'
axis (each FFT instance stays inside one pod — no transpose crosses the
slow inter-pod boundary, mirroring the paper's §8 multi-system note).

Usage:  PYTHONPATH=src python -m benchmarks.roofline_fft [--n 512]
"""
import os
os.environ['XLA_FLAGS'] = ('--xla_force_host_platform_device_count=512 '
                           + os.environ.get('XLA_FLAGS', ''))

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.fft as fft                          # noqa: E402
from repro.core import wse_model as wm           # noqa: E402
from repro.launch import hlostats                # noqa: E402
from repro.launch.dryrun import (HBM_BW, ICI_BW, PEAK_FLOPS,  # noqa: E402
                                 roofline_terms)
from repro.launch.mesh import make_fft_mesh      # noqa: E402


def lower_fft(n: int, *, pods: int = 1, method: str = 'auto',
              dtype=jnp.float32, overlap_chunks: int = 1,
              fwd_and_inv: bool = True):
    """Lower fft3d (+ifft3d: the paper's measured loop) for n^3 on a
    16x16 chip grid (x pods)."""
    mesh = make_fft_mesh(16, 16, pods=pods)
    batched = pods > 1
    with mesh:
        p = fft.plan((n, n, n), mesh, method=method,
                     mesh_axes=('x', 'y'), overlap_chunks=overlap_chunks,
                     batch_spec='pod' if batched else None)

        def loop(re, im):
            fr, fi = p.forward((re, im))
            if fwd_and_inv:
                fr, fi = p.inverse((fr, fi))
            return fr, fi

        shape = ((pods, n, n, n) if batched else (n, n, n))
        sds = jax.ShapeDtypeStruct(shape, dtype)
        sh = p.in_sharding
        osh = sh if fwd_and_inv else p.out_sharding
        jitted = jax.jit(loop, in_shardings=(sh, sh), out_shardings=(osh, osh))
        lowered = jitted.lower(sds, sds)
    n_chips = 256 * pods
    return lowered, n_chips


def fft_model_flops(n: int, *, pods: int = 1, loop: int = 2) -> float:
    """Useful flops: the paper's 3 * n^2 * 5 n log2 n per transform
    (x2 for fwd+inv, x pods batched instances)."""
    return wm.fft_flops_3d(n) * loop * pods


def run(n: int, *, pods: int = 1, method: str = 'auto',
        dtype=jnp.float32, overlap_chunks: int = 1,
        out_dir: str = 'results/dryrun', tag: str = '') -> dict:
    t0 = time.time()
    lowered, n_chips = lower_fft(n, pods=pods, method=method, dtype=dtype,
                                 overlap_chunks=overlap_chunks)
    compiled, spmd_txt = hlostats.compile_with_spmd_dump(lowered)
    t1 = time.time()
    stats = hlostats.analyze(compiled.as_text())
    wire = hlostats.wire_ratio_from_spmd(stats, spmd_txt)
    stats['collective_bytes_raw_total'] = stats['collective_bytes_total']
    stats['collective_bytes'] = wire['collective_bytes']
    stats['collective_bytes_total'] = wire['collective_bytes_total']
    cost = compiled.cost_analysis()
    roof = roofline_terms(stats, n_chips,
                          cost_flops=float(cost.get('flops', 0.0)),
                          cost_bytes=float(cost.get('bytes accessed', 0.0)))
    mf = fft_model_flops(n, pods=pods)
    ideal = mf / (n_chips * PEAK_FLOPS)
    rec = {
        'arch': f'wsfft-{n}cubed' + (f'-x{pods}pods' if pods > 1 else ''),
        'shape': f'fft_{n}',
        'mesh': f'{"multipod_2x16x16" if pods > 1 else "pod_16x16"}',
        'kind': 'fft', 'method': method, 'dtype': str(dtype.__name__),
        'overlap_chunks': overlap_chunks, 'status': 'ok',
        'n_chips': n_chips, 'compile_s': round(t1 - t0, 2),
        'hlo': stats, 'cost_flops': float(cost.get('flops', 0.0)),
        'cost_bytes': float(cost.get('bytes accessed', 0.0)),
        'model_flops': mf, 'roofline': roof,
        'roofline_fraction': ideal / roof['bound_s'] if roof['bound_s'] else 0,
        'memory': {k: int(getattr(compiled.memory_analysis(), k, 0))
                   for k in ('temp_size_in_bytes', 'argument_size_in_bytes')},
        # link-utilization view: how close the collective term is to the
        # pure-bisection lower bound for 2 transposes of the global array
        'transpose_bytes_min': 2 * 2 * (n ** 3) * (8 if dtype == jnp.float32
                                                   else 4) / n_chips,
    }
    os.makedirs(out_dir, exist_ok=True)
    tagtxt = f'__{tag}' if tag else ''
    fn = os.path.join(out_dir, f"{rec['mesh']}__wsfft__{n}"
                      f"__{method}{tagtxt}.json")
    with open(fn, 'w') as f:
        json.dump(rec, f, indent=1)
    r = roof
    print(f"[fft-roofline] n={n} pods={pods} method={method} "
          f"dtype={dtype.__name__} chips={n_chips}: "
          f"compute={r['compute_s']*1e6:.1f}us memory={r['memory_s']*1e6:.1f}us "
          f"collective={r['collective_s']*1e6:.1f}us dom={r['dominant']} "
          f"frac={rec['roofline_fraction']:.4f} compile={rec['compile_s']}s",
          flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--n', type=int, default=0, help='0 = sweep')
    ap.add_argument('--method', default='auto')
    ap.add_argument('--pods', type=int, default=1)
    ap.add_argument('--overlap', type=int, default=1)
    ap.add_argument('--tag', default='')
    args = ap.parse_args()
    if args.n:
        run(args.n, pods=args.pods, method=args.method,
            overlap_chunks=args.overlap, tag=args.tag)
        return
    # default sweep: paper sizes on single pod, fp32 (paper's headline),
    # plus the stockham-faithful variant and the multi-pod batch
    for n in (256, 512):
        run(n, method='auto')                       # MXU four-step
    run(512, method='stockham', tag='faithful')     # paper-faithful radix-2
    run(512, pods=2)                                # multi-pod batch of 2


if __name__ == '__main__':
    main()
