"""Training launcher: --arch <id> [--smoke] with the full
fault-tolerant runtime (checkpoint/restart, straggler monitor).

On this CPU container run reduced configs (--smoke, the default); on a
fleet the same entrypoint takes the full config + production mesh (the
dry-run proves those lower+compile).
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--arch', required=True)
    ap.add_argument('--smoke', action='store_true', default=True)
    ap.add_argument('--full', dest='smoke', action='store_false')
    ap.add_argument('--steps', type=int, default=100)
    ap.add_argument('--batch', type=int, default=8)
    ap.add_argument('--seq', type=int, default=64)
    ap.add_argument('--devices', type=int, default=0,
                    help='fake host devices (0 = real devices only)')
    ap.add_argument('--mesh', default='1x1',
                    help='ROWSxCOLS data x model mesh')
    ap.add_argument('--ckpt-dir', default='/tmp/repro_ckpt')
    ap.add_argument('--ckpt-every', type=int, default=25)
    ap.add_argument('--microbatches', type=int, default=1)
    ap.add_argument('--lr', type=float, default=1e-3)
    ap.add_argument('--resume', action='store_true')
    ap.add_argument('--fail-at', type=int, default=-1,
                    help='inject a failure at this step (FT demo)')
    args = ap.parse_args()

    if args.devices:
        os.environ['XLA_FLAGS'] = (
            f'--xla_force_host_platform_device_count={args.devices} '
            + os.environ.get('XLA_FLAGS', ''))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.data import SyntheticLM, shard_batch
    from repro.models import model as M
    from repro.runtime import TrainDriver, FailureInjector, StragglerMonitor
    from repro.train.optim import adamw_init
    from repro.train.trainstep import jit_train_step
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    rows, cols = (int(t) for t in args.mesh.split('x'))
    mesh = make_mesh((rows, cols), ('data', 'model'))

    sds = jax.ShapeDtypeStruct
    B, S = args.batch, args.seq
    batch_sds = {'labels': sds((B, S), jnp.int32)}
    batch_axes = {'labels': ('batch', 'seq')}
    if cfg.input_mode == 'embeds':
        batch_sds['embeds'] = sds((B, S, cfg.d_model), jnp.float32)
        batch_axes['embeds'] = ('batch', 'seq', None)
    else:
        batch_sds['tokens'] = sds((B, S), jnp.int32)
        batch_axes['tokens'] = ('batch', 'seq')
    if cfg.pos_kind == 'mrope':
        batch_sds['positions'] = sds((3, B, S), jnp.int32)
        batch_axes['positions'] = (None, 'batch', 'seq')

    with mesh:
        step_fn, aux = jit_train_step(
            cfg, mesh, batch_sds, batch_axes, peak_lr=args.lr,
            warmup_steps=max(args.steps // 10, 5), total_steps=args.steps,
            microbatches=args.microbatches, param_dtype=jnp.float32)
        params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        params = jax.device_put(params, aux['p_sh'])
        opt = adamw_init(params)
        opt = jax.device_put(opt, aux['o_sh'])

        data = SyntheticLM(cfg.vocab_size, S, B,
                           input_mode=cfg.input_mode, d_model=cfg.d_model,
                           mrope=cfg.pos_kind == 'mrope')
        driver = TrainDriver(
            step_fn, args.ckpt_dir, ckpt_every=args.ckpt_every,
            injector=FailureInjector([args.fail_at] if args.fail_at >= 0
                                     else []),
            monitor=StragglerMonitor(on_trip=lambda s, dt, e: print(
                f'[straggler] step {s}: {dt:.3f}s vs EWMA {e:.3f}s')),
            log=print)

        start = 0
        if args.resume:
            restored = driver.restore(params, opt)
            if restored is not None:
                params, opt, start = restored
                print(f'[train] resumed from step {start}')

        def batches(step):
            return shard_batch(data.batch_at(step), aux['b_sh'])

        params, opt, end = driver.run(params, opt, batches,
                                      steps=args.steps, start_step=start)
        hist = driver.history
        print(f"[train] arch={cfg.name} steps={end} "
              f"loss first={hist[0]['ce']:.4f} last={hist[-1]['ce']:.4f} "
              f"restarts={driver.restarts} straggler_trips="
              f"{driver.monitor.trips}")


if __name__ == '__main__':
    main()
