"""End-to-end training integration: loss decreases, checkpoint/restart
resumes bit-compatibly, straggler monitor trips on injected delay."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.data import SyntheticLM
from repro.models import model as M
from repro.runtime import FailureInjector, StragglerMonitor, TrainDriver
from repro.train.optim import adamw_init
from repro.train.trainstep import make_train_step
from repro.launch.mesh import make_mesh


def _setup(arch='internlm2-1.8b', B=4, S=32, lr=3e-3):
    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((1, 1), ('data', 'model'))
    step = make_train_step(cfg, mesh, peak_lr=lr, warmup_steps=5,
                           total_steps=60, param_dtype=jnp.float32)
    step = jax.jit(step, donate_argnums=(0, 1))
    params = M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    opt = adamw_init(params)
    data = SyntheticLM(cfg.vocab_size, S, B, seed=4)
    return cfg, step, params, opt, data


def test_loss_decreases():
    """The Markov-permutation stream is bigram-learnable: a tiny untied
    model must drop >1 nat below its start and below uniform in ~100
    steps."""
    cfg, step, params, opt, data = _setup('codeqwen1.5-7b', B=8, lr=1e-2)
    losses = []
    for i in range(100):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, m = step(params, opt, batch)
        losses.append(float(m['ce']))
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 1.0, (first, last)
    assert last < np.log(cfg.vocab_size) - 1.0


def test_restart_reproduces_uninterrupted_run(tmp_path):
    """Train 20 steps with a failure at step 13; the restarted run must
    end with exactly the same parameters as an uninterrupted run
    (deterministic data + deterministic optimizer)."""
    def run(ckpt_dir, fail_at):
        cfg, step, params, opt, data = _setup()
        driver = TrainDriver(
            step, ckpt_dir, ckpt_every=5, async_ckpt=False,
            injector=FailureInjector([fail_at] if fail_at else []))
        def batches(i):
            return {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, end = driver.run(params, opt, batches, steps=20)
        return params, driver

    p_ref, d_ref = run(str(tmp_path / 'ref'), None)
    p_ft, d_ft = run(str(tmp_path / 'ft'), 13)
    assert d_ref.restarts == 0
    assert d_ft.restarts == 1
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_ft)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=0, rtol=0)


def test_straggler_monitor_trips():
    mon = StragglerMonitor(alpha=0.5, trip_factor=2.0, warmup=2)
    trips = []
    mon.on_trip = lambda s, dt, e: trips.append(s)
    for s, dt in enumerate([0.1, 0.1, 0.1, 0.1, 0.5, 0.1]):
        mon.observe(s, dt)
    assert trips == [4]
    assert mon.trips == 1
    # EWMA not poisoned by the straggler step
    assert mon.ewma < 0.15


def test_elastic_reshard_restore(tmp_path):
    """Checkpoint written under one sharding restores under another
    (the elastic re-mesh path) with identical values."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import restore_checkpoint, save_checkpoint
    mesh1 = make_mesh((1, 1), ('data', 'model'))
    t = {'w': jax.device_put(
        jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
        NamedSharding(mesh1, P('data', None)))}
    save_checkpoint(str(tmp_path), 1, t)
    mesh2 = make_mesh((1, 1), ('a', 'b'))      # a "different fleet"
    sh = {'w': NamedSharding(mesh2, P(None, 'b'))}
    r = restore_checkpoint(str(tmp_path), 1, t, sh)
    np.testing.assert_array_equal(np.asarray(r['w']), np.asarray(t['w']))
    assert r['w'].sharding.spec == P(None, 'b')
