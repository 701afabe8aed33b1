"""Batched serving: prefill a batch of prompts, then greedy-decode with
the sharded KV caches (dense GQA / MLA / SSM state / sliding-window ring
— pick the arch). The model is randomly initialized, so the interest is
the ENGINE: one prefill + N decode steps with donated caches; the
prefill+decode == full-forward equivalence that makes the outputs
meaningful is asserted arch-by-arch in tests/test_serve.py.

    PYTHONPATH=src python examples/serve_batched.py --arch mamba2-1.3b
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.models import model as M
from repro.serve import ServeEngine
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', default='granite-3-8b')
    ap.add_argument('--batch', type=int, default=4)
    ap.add_argument('--prompt-len', type=int, default=24)
    ap.add_argument('--gen', type=int, default=12)
    args = ap.parse_args()

    cfg = smoke_config(get_config(args.arch))
    if not cfg.causal:
        raise SystemExit(f'{cfg.name} is encoder-only — no decode step')
    mesh = make_mesh((1, 1), ('data', 'model'))
    with mesh:
        params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        eng = ServeEngine(cfg, mesh, params, batch=args.batch,
                          prompt_len=args.prompt_len,
                          max_len=args.prompt_len + args.gen,
                          param_dtype=jnp.float32)
        # cyclic prompts (each row a different cycle)
        rng = np.random.default_rng(0)
        toks = np.empty((args.batch, args.prompt_len), np.int32)
        for b in range(args.batch):
            cyc = rng.integers(1, cfg.vocab_size, size=3)
            toks[b] = np.resize(cyc, args.prompt_len)
        batch = {'tokens': jnp.asarray(toks)}
        if cfg.input_mode == 'embeds':
            emb = M.init_params(jax.random.PRNGKey(0), cfg,
                                jnp.float32)['embed']['table']
            batch = {'embeds': jnp.take(emb, batch['tokens'], axis=0)}
            if cfg.pos_kind == 'mrope':
                batch['positions'] = jnp.broadcast_to(
                    jnp.arange(args.prompt_len, dtype=jnp.int32)[None, None],
                    (3, args.batch, args.prompt_len))
        t0 = time.perf_counter()
        out = eng.generate(batch, args.gen)
        dt = time.perf_counter() - t0
    print(f'[serve_batched] {cfg.name}: {args.batch} prompts x {args.gen} '
          f'tokens in {dt:.2f}s ({args.batch*args.gen/dt:.1f} tok/s)')
    for b in range(args.batch):
        print(f'  prompt …{toks[b, -6:].tolist()} -> {out[b].tolist()}')
    print('serve_batched OK')


if __name__ == '__main__':
    main()
