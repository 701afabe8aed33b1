"""The paper's technique inside an LM: a long-convolution token mixer
executed with the repo's own four-step FFT (the ``repro.fft`` method
registry drives the mixer in models/ssd.py).

A constant-decay SSM is exactly a causal convolution, so the sequence
mixer is y = causal_conv(x, k) computed as FFT -> pointwise multiply ->
IFFT over the (2S padded) sequence — the FFT engine from the paper
reproduction doing the work an attention/scan mixer would. DESIGN.md §5
lists this as the Mamba2 'optional exact FFT path' tie-in.

The mixer runs through a fused ``fft.plan_op`` operator plan (one
dispatch per conv; the learned kernel rides as a runtime operand of the
same dispatch during training, and its spectrum is baked once per plan
at eval) — see ``models/ssd.py:fftconv_apply``.

    PYTHONPATH=src python examples/fftconv_lm.py --steps 150
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.data import SyntheticLM
from repro.models import model as M
from repro.train.optim import adamw_init
from repro.train.trainstep import make_train_step
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--steps', type=int, default=150)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=64)
    args = ap.parse_args()

    # an attention-free LM whose every block is the FFT-conv mixer
    cfg = dataclasses.replace(
        smoke_config(get_config('mamba2-1.3b')),
        block_pattern=('fftconv',), num_layers=4, d_model=64,
        vocab_size=256, fftconv_len=args.seq)
    mesh = make_mesh((1, 1), ('data', 'model'))

    step = jax.jit(make_train_step(cfg, mesh, peak_lr=3e-3,
                                   warmup_steps=10, total_steps=args.steps,
                                   param_dtype=jnp.float32),
                   donate_argnums=(0, 1))
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    opt = adamw_init(params)

    def batch_at(i):
        """Period-3 token cycles: exactly learnable by a lag-2 conv tap
        (a content-based mixer is not needed; a relative-offset one is —
        the convolution's home turf)."""
        rng = np.random.default_rng((1000003 * i) % (2**31))
        toks = np.empty((args.batch, args.seq + 1), np.int32)
        for b in range(args.batch):
            toks[b] = np.resize(rng.integers(1, cfg.vocab_size, 3),
                                args.seq + 1)
        return {'tokens': jnp.asarray(toks[:, :-1]),
                'labels': jnp.asarray(toks[:, 1:])}

    losses = []
    for i in range(args.steps):
        batch = batch_at(i)
        params, opt, m = step(params, opt, batch)
        losses.append(float(m['ce']))
        if i % max(args.steps // 10, 1) == 0:
            print(f'step {i:4d} ce={losses[-1]:.4f}')
    first, last = np.mean(losses[:10]), np.mean(losses[-10:])
    print(f'fftconv LM loss: {first:.4f} -> {last:.4f} '
          f'(uniform {np.log(cfg.vocab_size):.4f})')
    assert last < first - 0.3, 'fftconv mixer failed to learn'
    print('fftconv_lm OK')


if __name__ == '__main__':
    main()
