"""Runs the 2x2 cell at 16^3 on four CPU devices, with or without the
ownership swap's exchange left out; prints the run's result as JSON.

    python -m bench.tests._mesh_worker [--no-exchange]
"""
import os
import sys

os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
os.environ['JAX_PLATFORMS'] = 'cpu'

import json  # noqa: E402
import time  # noqa: E402


def main() -> int:
    import jax
    import pytest
    from bench import harness
    from bench.tests import _faults
    b = harness.Bench(harness.ROOT)
    cell = 'c2c512-pair-2x2'
    cfg = b.config(b.workload(cell)['config'])
    cfg['shape'] = [16, 16, 16]
    with pytest.MonkeyPatch.context() as mp:
        if '--no-exchange' in sys.argv:
            stack = _faults.no_exchange(mp)
        else:
            import contextlib
            stack = contextlib.nullcontext()
        with stack:
            r = harness.run_cell(b, cell, 2 ** 33 + 17, 0.3, False,
                                 devices=jax.devices()[:4],
                                 t0=time.perf_counter(), config=cfg)
    print(json.dumps(r))
    return 0


if __name__ == '__main__':
    sys.exit(main())
