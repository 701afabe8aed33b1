"""The comparison that decides ``correct``.

After the window has closed, the window's final answer (the output of
its last step, which chains every step before it) is copied to the
host, and one more step is run through the same jitted entries that the
window drove, at the timed sizes. Rows of its outputs along the first
axis, a sample drawn from the seed (an eighth of them, at least eight),
are copied to the host. Then the configuration's float64 reference
(``bench/references``) transforms the whole host copy, and:

* ``forward_err``: the sampled rows of the first call's output against
  the reference's, as max|got - want| / max|want|;
* ``roundtrip_err`` (mixes that return to their input, such as the
  forward+inverse pair): the sampled rows of the step's last output
  against the step's input, which the exact inverse of the forward
  returns;
* ``drift_err`` (the same mixes): the window's final answer against the
  window's first input, drawn again from the seed, as max|final - first|
  / max|first| (reduced on the device, in the input's precision). The
  inverse undoes the forward, so after k steps the two differ by
  rounding alone; a step gone wrong anywhere in the window shows here.

Each number has its limit in the configuration file (``limits``); the
run is correct when every number is at or under its limit.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict

import numpy as np

#: rows per block of the error reduction, to bound host temporaries
BLOCK = 8


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """max|got - want| / max|want| (NaN anywhere gives NaN), in blocks
    of rows on the host's threads."""
    def block(i):
        w = want[i:i + BLOCK]
        return (float(np.max(np.abs(got[i:i + BLOCK] - w))),
                float(np.max(np.abs(w))))
    with ThreadPoolExecutor(os.cpu_count()) as ex:
        parts = list(ex.map(block, range(0, want.shape[0], BLOCK)))
    nums, dens = zip(*parts)
    if any(np.isnan(nums)):
        return float('nan')
    return max(nums) / max(dens)


def drift(final, first) -> float:
    """max|final - first| / max|first| of two device arrays, reduced on
    the device."""
    num, den = (float(v) for v in _drift()(final, first))
    return num / den


@functools.cache
def _drift():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda a, b: (jnp.max(jnp.abs(a - b)),
                                 jnp.max(jnp.abs(b))))


@dataclasses.dataclass
class Outcome:
    correct: bool
    numbers: Dict[str, Dict[str, float]]   # name -> {'value', 'limit'}


def host(a) -> np.ndarray:
    return np.array(a, copy=True)


def sample_rows(seed: int, n: int) -> np.ndarray:
    k = min(n, max(n // 8, 8))
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=k, replace=False))


@functools.cache
def _take():
    import jax
    return jax.jit(lambda a, rows: a[rows])


def check(cfg: dict, ref, calls, x_final, make_input, seed: int, *,
          round_trip: bool) -> Outcome:
    """Compare the timed executables' outputs with the reference; see
    the module docstring. Consumes ``x_final``."""
    from bench.harness import log
    shape = tuple(cfg['shape'])
    rows = sample_rows(seed, shape[0])
    t = time.perf_counter()
    values = {}
    if round_trip:
        values['drift_err'] = drift(x_final, make_input(seed))
    x_in = host(x_final)
    y, outs = x_final, []
    del x_final
    for _, fn in calls:
        y = fn(y)
        outs.append(host(_take()(y, rows)))
    del y
    t_dev = time.perf_counter() - t

    t = time.perf_counter()
    want = getattr(ref, calls[0][0])(x_in, shape)[rows]
    values[f'{calls[0][0]}_err'] = rel_err(outs[0], want)
    if round_trip:
        values['roundtrip_err'] = rel_err(outs[-1], x_in[rows])
    t_ref = time.perf_counter() - t
    log(f'[check] drift, device step and copies {t_dev:.3f} s, float64 '
        f'reference {t_ref:.3f} s')

    limits = cfg['limits']
    numbers = {k: {'value': v, 'limit': limits[k]}
               for k, v in sorted(values.items())}
    correct = all(v <= limits[k] for k, v in values.items())
    return Outcome(correct=correct, numbers=numbers)
