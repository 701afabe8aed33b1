"""The algorithm's minimum work per transform, whatever implements it.

Nominal operations: 5 N log2 N real floating-point operations per
complex N-point transform (the radix-2 count every FFT benchmark
reports, heFFTe's speed3d among them), half that for a real-input
transform or its inverse. Minimum bytes: each device reads its share of
the input once and writes its share of the output once. Neither counts
the passes, relayouts or exchanges a given implementation makes, so a
later change that fuses or drops passes never pushes a share of this
bound past 100%.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

#: bytes per element of the dtypes a configuration's input may take
ELEM_BYTES = {'float32': 4, 'complex64': 8, 'float64': 8, 'complex128': 16}
#: the complex dtype of a real input's spectrum
SPECTRUM = {'float32': 'complex64', 'float64': 'complex128'}


def transform_work(shape: Sequence[int], real: bool, dtype: str,
                   devices: int) -> Dict[str, float]:
    """Nominal flops and minimum HBM bytes of ONE forward (or inverse)
    transform of ``shape``, per device, the work spread evenly over
    ``devices``."""
    n = math.prod(shape)
    flops = 5.0 * n * math.log2(n)
    if real:
        flops /= 2
        spec = math.prod(shape[:-1]) * (shape[-1] // 2 + 1)
        nbytes = n * ELEM_BYTES[dtype] + spec * ELEM_BYTES[SPECTRUM[dtype]]
    else:
        nbytes = 2 * n * ELEM_BYTES[dtype]
    return {'flops': flops / devices, 'bytes': nbytes / devices}


def step_bound(work: Dict[str, float], calls: int,
               peak: Dict[str, float]) -> Tuple[float, str]:
    """Least device seconds of one step of ``calls`` transforms, and the
    term that binds it ('flops' or 'bytes'). The bf16 peak stands in for
    the float32 one, which v5e does not publish: it only lowers the
    bound."""
    t_flops = calls * work['flops'] / peak['bf16_flops_per_s']
    t_bytes = calls * work['bytes'] / peak['hbm_bytes_per_s']
    return (t_bytes, 'bytes') if t_bytes >= t_flops else (t_flops, 'flops')
