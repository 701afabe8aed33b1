"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared beside its limit, which also end standard error.
Without a TPU, or with fewer chips than the cell asks for, the run
exits 1 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    bench = harness.Bench(ROOT)
    chips = bench.workload(args.workload)['chips']
    try:
        devices = harness.accelerator(chips)
    except harness.NoChip as e:
        harness.log(f'bench: {e}; the benchmark runs only on the chip')
        return 1
    import jax
    from repro.launch.cache import enable_compile_cache
    harness.log(f'[cache] {enable_compile_cache()}')
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)

    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), devices=devices, t0=T0)
    for name, c in result['checks'].items():
        harness.log(f'{name} {c["value"]!r} limit {c["limit"]!r}')
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
