"""Plain float64 reference of ``numpy.fft.fftn`` (complex in, complex
out, unnormalized). scipy.fft runs the same pocketfft code as numpy.fft,
on several threads."""
import os

import numpy as np
import scipy.fft


def forward(x: np.ndarray, shape) -> np.ndarray:
    return scipy.fft.fftn(x.astype(np.complex128), s=shape,
                          workers=os.cpu_count())
