"""Multi-device (fake 8-CPU-device) integration via subprocess — the
same distribution code paths (FSDP + TP + EP + SP collectives) the
production meshes use, executed for real on a 2x4 mesh."""
import subprocess
import sys

import pytest

TRAIN_WORKER = r'''
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, smoke_config, make_batch
from repro.models import model as M
from repro.train.optim import adamw_init
from repro.train.trainstep import jit_train_step
from repro.launch.mesh import make_mesh

for arch in ('internlm2-1.8b', 'dbrx-132b', 'mamba2-1.3b'):
    cfg = smoke_config(get_config(arch))
    mesh = make_mesh((2, 4), ('data', 'model'))
    sds = jax.ShapeDtypeStruct
    B, S = 4, 16
    b_sds = {'tokens': sds((B, S), jnp.int32), 'labels': sds((B, S), jnp.int32)}
    b_ax = {'tokens': ('batch', 'seq'), 'labels': ('batch', 'seq')}
    with mesh:
        step, aux = jit_train_step(cfg, mesh, b_sds, b_ax, microbatches=2,
                                   param_dtype=jnp.float32)
        params = jax.device_put(
            M.init_params(jax.random.PRNGKey(0), cfg, jnp.float32), aux['p_sh'])
        opt = jax.device_put(adamw_init(params), aux['o_sh'])
        batch = make_batch(cfg, batch=B, seq=S, dtype=jnp.float32)
        batch = {k: jax.device_put(v, aux['b_sh'][k]) for k, v in batch.items()
                 if k in b_sds}
        params, opt, m = step(params, opt, batch)
        loss = float(m['loss'])
        assert np.isfinite(loss), (arch, loss)
        print(f'MD_TRAIN_OK {arch} {loss:.4f}')
'''

SP_WORKER = r'''
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.models import attention as A
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ('data', 'model'))
B, S, H, KH, D = 2, 32, 8, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
k = jax.random.normal(ks[1], (B, S, KH, D), jnp.float32)
v = jax.random.normal(ks[2], (B, S, KH, D), jnp.float32)

with mesh:
    sh = NamedSharding(mesh, P('data', 'model', None, None))
    qd, kd, vd = (jax.device_put(t, sh) for t in (q, k, v))
    got = jax.jit(lambda a, b, c: A.ulysses_attention(
        a, b, c, mesh, batch_spec=P('data'), causal=True, chunk=8))(qd, kd, vd)
want = A.flash_attention(q, k, v, causal=True, chunk=8)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=2e-5, rtol=2e-5)
print('MD_SP_OK')

# explicit-EP MoE (shard_map path) == pjit scatter path
import dataclasses
from repro.configs import get_config, smoke_config
from repro.models import moe, layers
cfg = dataclasses.replace(smoke_config(get_config('dbrx-132b')),
                          capacity_factor=8.0, num_shared_experts=0)
p = layers.init_from_plan(jax.random.PRNGKey(0), moe.moe_plan(cfg),
                          jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
y_ref, _ = moe.moe_apply(p, cfg, x)
with mesh:
    xs = jax.device_put(x, NamedSharding(mesh, P('data', None, None)))
    ps = jax.device_put(p, NamedSharding(mesh, P()))
    ps['wi'] = jax.device_put(p['wi'], NamedSharding(mesh, P('model')))
    ps['wo'] = jax.device_put(p['wo'], NamedSharding(mesh, P('model')))
    y_ep, _ = jax.jit(lambda pp, xx: moe.moe_ep_explicit(
        pp, cfg, xx, mesh))(ps, xs)
# same expert math; dispatch pooling differs (per-device capacity pool) —
# with cf=8 nothing drops, so the results must match exactly
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           atol=1e-4, rtol=1e-4)
print('MD_EP_OK')
'''


@pytest.mark.slow
def test_multidevice_train_steps():
    r = subprocess.run([sys.executable, '-c', TRAIN_WORKER],
                       capture_output=True, text=True, timeout=1800)
    assert r.stdout.count('MD_TRAIN_OK') == 3, r.stdout + r.stderr[-3000:]


@pytest.mark.slow
def test_multidevice_sp_and_ep():
    r = subprocess.run([sys.executable, '-c', SP_WORKER],
                       capture_output=True, text=True, timeout=1800)
    assert 'MD_SP_OK' in r.stdout and 'MD_EP_OK' in r.stdout, \
        r.stdout + r.stderr[-3000:]
