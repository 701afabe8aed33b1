"""Plain float64 reference of ``numpy.fft.rfftn`` (real in, half
spectrum out along the last axis). scipy.fft runs the same pocketfft
code as numpy.fft, on several threads."""
import os

import numpy as np
import scipy.fft


def forward(x: np.ndarray, shape) -> np.ndarray:
    return scipy.fft.rfftn(x.astype(np.float64), s=shape,
                           workers=os.cpu_count())
