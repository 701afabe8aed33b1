"""Record a cell's profile and reduce it by the program's own names.

    python bench/record.py --workload <name> --seed <n> --seconds <s> \
        [--repeat <r>] [--out <file.xplane.pb>]

Run from the root of a checkout, on the chips the cell asks for. Builds
the cell's plan and warms it as ``bench/run.py`` does, then runs
``--repeat`` rounds (default 1) of two windows of ``--seconds`` each:
one with the profiler off, one with it on. Each round prints one JSON
line: both windows' pair times (host clock), and the traced window's
device time by part of the work and by superstep
(:mod:`bench.scoped`), the host time inside the plan's calls, and the
longest idle gaps named by what the host was doing. ``--out`` keeps the
last traced window's ``.xplane.pb``. No check of the results is made:
``bench/run.py`` does that. Without a TPU, or with fewer chips than
the cell asks for, it exits 1.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def traced_window(calls, x, seconds: float, trace_dir: str):
    import jax
    from bench import harness
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        return harness.run_steps(calls, x, seconds=seconds)


def reduce(path: str, steps: int, calls) -> dict:
    from bench import scoped
    t = scoped.from_xplane(path, steps, calls)
    dev = t.busiest()
    return {'busy_ms': t.busy_ns(dev) / 1e6 / steps,
            'parts_ms': {p: scoped.part_ms(t, p) for p in scoped.PARTS},
            'dispatch_ms': scoped.dispatch_ms(t),
            'supersteps_ms': scoped.supersteps(t),
            'idle_gaps': t.breakdown()['idle_gaps']}


def record(bench, workload: str, seed: int, seconds: float, repeat: int,
           out, devices, config=None):
    """The rounds of ``main``; ``config`` replaces the configuration
    file, as in :func:`bench.harness.run_cell`."""
    from bench import harness
    wl = bench.workload(workload)
    cfg = config if config is not None else bench.config(wl['config'])
    traffic = bench.traffic(wl['traffic'])
    p = harness.build_plan(cfg, devices)
    harness.log(f'[plan] {wl["config"]} {harness.describe(p)}')
    calls = harness.step_calls(p, traffic)
    x = harness.input_maker(cfg, p.in_sharding)(seed)
    for _ in range(traffic['warm_steps']):
        x, _, _ = harness.run_steps(calls, x, seconds=0)
    for r in range(repeat):
        x, off, off_s = harness.run_steps(calls, x, seconds=seconds)
        trace_dir = tempfile.mkdtemp(prefix='bench_record_')
        try:
            x, on, on_s = traced_window(calls, x, seconds, trace_dir)
            (path,) = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                                recursive=True)
            line = {'round': r, 'steps_untraced': len(off),
                    'pair_ms_untraced': off_s / len(off) * 1e3,
                    'steps_traced': len(on),
                    'pair_ms_traced': on_s / len(on) * 1e3}
            line.update(reduce(path, len(on), traffic['calls']))
            if out and r == repeat - 1:
                shutil.copyfile(path, out)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--repeat', type=int, default=1)
    ap.add_argument('--out')
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.Bench(ROOT)
    try:
        devices = harness.accelerator(bench.workload(args.workload)['chips'])
    except harness.NoChip as e:
        harness.log(f'bench: {e}; profiles are recorded only on the chip')
        return 1
    import jax
    from repro.launch.cache import enable_compile_cache
    harness.log(f'[cache] {enable_compile_cache()}')
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    record(bench, args.workload, args.seed, args.seconds, args.repeat,
           args.out, devices)
    return 0


if __name__ == '__main__':
    sys.exit(main())
