"""Serving launcher: batched prefill + greedy decode on a mesh.

Smoke-scale on CPU; the decode_32k / long_500k production cells are
exercised via launch/dryrun.py on the 16x16 and 2x16x16 meshes.
"""
from __future__ import annotations

import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', required=True)
    ap.add_argument('--smoke', action='store_true', default=True)
    ap.add_argument('--batch', type=int, default=4)
    ap.add_argument('--prompt-len', type=int, default=32)
    ap.add_argument('--gen', type=int, default=16)
    ap.add_argument('--devices', type=int, default=0)
    ap.add_argument('--mesh', default='1x1')
    args = ap.parse_args()

    if args.devices:
        os.environ['XLA_FLAGS'] = (
            f'--xla_force_host_platform_device_count={args.devices} '
            + os.environ.get('XLA_FLAGS', ''))

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, smoke_config, make_batch
    from repro.models import model as M
    from repro.serve import ServeEngine
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if not cfg.causal:
        raise SystemExit(f'{cfg.name} is encoder-only: no decode step')
    rows, cols = (int(t) for t in args.mesh.split('x'))
    mesh = make_mesh((rows, cols), ('data', 'model'))

    with mesh:
        params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        eng = ServeEngine(cfg, mesh, params, batch=args.batch,
                          prompt_len=args.prompt_len,
                          max_len=args.prompt_len + args.gen,
                          param_dtype=jnp.float32)
        batch = make_batch(cfg, batch=args.batch, seq=args.prompt_len,
                           dtype=jnp.float32)
        batch.pop('labels')
        t0 = time.perf_counter()
        toks = eng.generate(batch, args.gen)
        dt = time.perf_counter() - t0
        print(f'[serve] arch={cfg.name} batch={args.batch} '
              f'gen={args.gen} tokens in {dt:.2f}s '
              f'({args.batch * args.gen / dt:.1f} tok/s)')
        print('[serve] first row:', toks[0].tolist())


if __name__ == '__main__':
    main()
