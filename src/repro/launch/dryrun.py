"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
against the production meshes and extract the roofline terms.

MUST set the fake-device count before any other import — jax locks the
device count on first init.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ARCHS, SHAPES, get_config, input_specs,  # noqa: E402
                           skip_reason)
from repro.launch import hlostats                                   # noqa: E402
from repro.launch.mesh import make_production_mesh                  # noqa: E402
from repro.models import model as M                                 # noqa: E402

# TPU v5e-class hardware constants (per chip), per the assignment.
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link


def lower_cell(cfg, shape, mesh, *, sp: bool = False, microbatches: int = 1,
               serve_fsdp: bool = True):
    """Build + lower the right step function for one cell.
    Returns (lowered, n_chips)."""
    batch_sds, batch_axes = input_specs(cfg, shape)
    if shape.kind == 'train':
        from repro.train.trainstep import jit_train_step
        with mesh:
            jitted, aux = jit_train_step(cfg, mesh, batch_sds, batch_axes,
                                         sp=sp, microbatches=microbatches)
            from repro.train.optim import abstract_opt
            lowered = jitted.lower(aux['params'], aux['opt'], batch_sds)
    elif shape.kind == 'prefill':
        from repro.serve.engine import make_prefill_step
        with mesh:
            jitted, aux = make_prefill_step(cfg, mesh, batch_sds, batch_axes,
                                            sp=sp)
            lowered = jitted.lower(aux['params'], batch_sds)
    else:                                        # decode
        from repro.serve.engine import make_decode_step
        B = shape.global_batch
        with mesh:
            jitted, aux = make_decode_step(cfg, mesh, batch=B,
                                           cache_cap=shape.seq_len)
            tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
            ln = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jitted.lower(aux['params'], aux['caches'], tok, ln)
    n_chips = 1
    for v in mesh.shape.values():
        n_chips *= v
    return lowered, n_chips


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D prefill, 2*N*B decode
    (N = active params for MoE)."""
    n = M.active_param_count(cfg)
    if shape.kind == 'train':
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == 'prefill':
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def roofline_terms(stats, n_chips: int, *, cost_flops: float = 0.0,
                   cost_bytes: float = 0.0) -> dict:
    """Three per-step time lower bounds (seconds). HLO stats are
    per-device (SPMD), so per-chip terms divide by per-chip rates.

    Memory term: XLA's fusion-aware 'bytes accessed' counts loop bodies
    once; scale it by the loop factor measured on the flops side
    (dot_flops are trip-adjusted, cost_flops are not). The raw
    every-op proxy (hbm_bytes_proxy) is kept in the record but known to
    overcount fused elementwise chains ~5x.
    """
    loop_factor = max(1.0, stats['dot_flops'] / cost_flops) \
        if cost_flops else 1.0
    mem_bytes = cost_bytes * loop_factor if cost_bytes \
        else stats['hbm_bytes_proxy']
    compute_s = stats['dot_flops'] / PEAK_FLOPS
    memory_s = mem_bytes / HBM_BW
    collective_s = stats['collective_bytes_total'] / ICI_BW
    terms = {'compute_s': compute_s, 'memory_s': memory_s,
             'collective_s': collective_s}
    dom = max(terms, key=terms.get)
    bound = max(terms.values())
    frac = {k: (v / bound if bound else 0.0) for k, v in terms.items()}
    return {**terms, 'dominant': dom, 'bound_s': bound,
            'fraction_of_bound': frac,
            'mem_bytes_est': mem_bytes, 'loop_factor': loop_factor}


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             sp: bool = False, microbatches: int = 0,
             out_dir: str = 'results/dryrun') -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if microbatches <= 0:        # default: 4 microbatches keeps training
        microbatches = 4 if shape.kind == 'train' else 1
        # activations inside the 16 GB/chip HBM budget (measured)
    mesh_tag = 'multipod_2x16x16' if multi_pod else 'pod_16x16'
    rec = {'arch': arch, 'shape': shape_name, 'mesh': mesh_tag,
           'kind': shape.kind, 'sp': sp, 'microbatches': microbatches}
    skip = skip_reason(cfg, shape)
    if skip:
        rec['status'] = 'skipped'
        rec['skip_reason'] = skip
        return _emit(rec, out_dir)
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        t0 = time.time()
        lowered, n_chips = lower_cell(cfg, shape, mesh, sp=sp,
                                      microbatches=microbatches)
        t1 = time.time()
        compiled, spmd_txt = hlostats.compile_with_spmd_dump(lowered)
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        txt = compiled.as_text()
        stats = hlostats.analyze(txt)
        # true-wire dtypes: CPU float-normalization widens bf16/f8
        # collectives to f32 in the final HLO; correct from the
        # post-SPMD-partitioning dump (see hlostats.wire_ratio_from_spmd)
        wire = hlostats.wire_ratio_from_spmd(stats, spmd_txt)
        stats['collective_bytes_raw_total'] = stats['collective_bytes_total']
        stats['collective_bytes'] = wire['collective_bytes']
        stats['collective_bytes_total'] = wire['collective_bytes_total']
        stats['wire_ratio'] = wire['wire_ratio']
        rec.update(
            status='ok', n_chips=n_chips,
            lower_s=round(t1 - t0, 2), compile_s=round(t2 - t1, 2),
            memory=_mem_dict(mem),
            cost_flops=float(cost.get('flops', 0.0)),
            cost_bytes=float(cost.get('bytes accessed', 0.0)),
            hlo=stats,
            model_flops=model_flops(cfg, shape),
            params=M.param_count(cfg),
            active_params=M.active_param_count(cfg),
        )
        roof = roofline_terms(stats, n_chips,
                              cost_flops=rec['cost_flops'],
                              cost_bytes=rec['cost_bytes'])
        rec['roofline'] = roof
        total_hlo_flops = stats['dot_flops'] * n_chips
        rec['useful_flop_ratio'] = (rec['model_flops'] / total_hlo_flops
                                    if total_hlo_flops else 0.0)
        # roofline fraction: model-flops time at peak / bound time
        ideal_s = rec['model_flops'] / (n_chips * PEAK_FLOPS)
        rec['roofline_fraction'] = (ideal_s / roof['bound_s']
                                    if roof['bound_s'] else 0.0)
    except Exception as e:
        rec['status'] = 'failed'
        rec['error'] = f'{type(e).__name__}: {e}'
        rec['traceback'] = traceback.format_exc()[-4000:]
    return _emit(rec, out_dir)


def _mem_dict(mem) -> dict:
    out = {}
    for k in ('argument_size_in_bytes', 'output_size_in_bytes',
              'temp_size_in_bytes', 'generated_code_size_in_bytes',
              'alias_size_in_bytes'):
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _emit(rec: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir,
                      f"{rec['mesh']}__{rec['arch']}__{rec['shape']}"
                      + ('__sp' if rec.get('sp') else '') + '.json')
    slim = {k: v for k, v in rec.items() if k != 'traceback'}
    with open(fn, 'w') as f:
        json.dump(slim, f, indent=1)
    status = rec['status']
    extra = ''
    if status == 'ok':
        r = rec['roofline']
        extra = (f" dom={r['dominant']} bound={r['bound_s']*1e3:.2f}ms"
                 f" frac={rec['roofline_fraction']:.3f}"
                 f" compile={rec['compile_s']:.0f}s")
    elif status == 'failed':
        extra = ' ' + rec['error'][:120]
    elif status == 'skipped':
        extra = ' ' + rec['skip_reason']
    print(f"[dryrun] {rec['mesh']} {rec['arch']} {rec['shape']}: "
          f"{status}{extra}", flush=True)
    if rec.get('traceback'):
        print(rec['traceback'], file=sys.stderr)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='all')
    ap.add_argument('--shape', default='all')
    ap.add_argument('--mesh', default='both',
                    choices=['single', 'multi', 'both'])
    ap.add_argument('--sp', action='store_true',
                    help='Ulysses sequence parallelism for prefill')
    ap.add_argument('--microbatches', type=int, default=0,
                    help='0 = auto (4 for train, 1 otherwise)')
    ap.add_argument('--out', default='results/dryrun')
    args = ap.parse_args()
    archs = list(ARCHS) if args.arch == 'all' else args.arch.split(',')
    shapes = list(SHAPES) if args.shape == 'all' else args.shape.split(',')
    meshes = {'single': [False], 'multi': [True],
              'both': [False, True]}[args.mesh]
    failed = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mp, sp=args.sp,
                               microbatches=args.microbatches, out_dir=args.out)
                failed += rec['status'] == 'failed'
    sys.exit(1 if failed else 0)


if __name__ == '__main__':
    main()
