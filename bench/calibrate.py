"""Readings that the limits of ``correct`` are set from, in one process.

    python bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 1,2,... [--control-seeds 7,8,9] [--control-seconds <s>]

Runs the cell's program once per seed (a full window each, as
``bench/run.py`` does) and the lower-precision control
(:mod:`bench.control`) once per control seed, all in one process so the
set-up is paid once. Prints one JSON line per run: the seed, whether it
was the control, the numbers compared and the steps in its window. Needs
the chips the cell asks for; the benchmark's own runs never run it.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def seeds(text: str):
    return [int(s) for s in text.split(',') if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--seeds', type=seeds, default=[])
    ap.add_argument('--control-seeds', type=seeds, default=[])
    ap.add_argument('--control-seconds', type=float, default=None)
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.Bench(ROOT)
    wl = bench.workload(args.workload)
    devices = harness.accelerator(wl['chips'])
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    dtype = bench.config(wl['config'])['control']
    runs = ([(s, None, args.seconds) for s in args.seeds]
            + [(s, dtype, args.control_seconds or args.seconds)
               for s in args.control_seeds])
    for seed, control, seconds in runs:
        r = harness.run_cell(bench, args.workload, seed, seconds, False,
                             devices=devices, t0=time.perf_counter(),
                             control=control)
        print(json.dumps({'workload': args.workload, 'seed': seed,
                          'control': control, 'correct': r['correct'],
                          'steps': r['attempted'], 'checks': r['checks'],
                          'metrics': r['metrics']}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
