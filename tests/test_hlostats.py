"""The loop-aware HLO analyzer against hand-computable modules."""
import subprocess
import sys

import pytest

WORKER = r'''
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, 'src')
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch import hlostats
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ('x', 'y'))
def f(x, w):
    def body(c, _):
        c = jnp.tanh(c @ w)
        return jax.lax.with_sharding_constraint(
            c, NamedSharding(mesh, P('x', 'y'))), None
    y, _ = jax.lax.scan(body, x, None, length=10)
    return y
x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
with mesh:
    comp = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P('x', None)),
        NamedSharding(mesh, P(None, 'y')))).lower(x, w).compile()
st = hlostats.analyze(comp.as_text())
# per-device dot: (64,256)@(256,64) = 2*64*64*256 flops, 10 iterations
assert st['dot_flops'] == 10 * 2 * 64 * 64 * 256, st['dot_flops']
# all-gather operand: the (64,64) f32 shard, 10 iterations
assert st['collective_bytes']['all-gather'] == 10 * 64 * 64 * 4
assert st['collective_counts']['all-gather'] == 10
assert st['num_partitions'] == 8
print('HLOSTATS_OK')
'''


def test_loop_aware_analysis():
    r = subprocess.run([sys.executable, '-c', WORKER], capture_output=True,
                       text=True, timeout=600)
    assert 'HLOSTATS_OK' in r.stdout, r.stdout + r.stderr


def test_shape_bytes():
    from repro.launch import hlostats as h
    assert h.shape_bytes('f32[2,3]{1,0}') == 24
    assert h.shape_bytes('bf16[128]') == 256
    assert h.shape_bytes('(s32[], f32[4,4])') == 4 + 64
    assert h.shape_bytes('pred[]') == 1
    assert h.shape_bytes('f8e4m3fn[8]') == 8


def test_multiplier_fixpoint_on_synthetic_text():
    from repro.launch import hlostats as h
    text = '''HloModule m, num_partitions=4

%inner.1 (p0: f32[8,8]) -> f32[8,8] {
  %ar = f32[8,8]{1,0} all-reduce(%p0), replica_groups=[1,4]<=[4]
}

%body.2 (p1: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %call.1 = f32[8,8]{1,0} call(%gte), to_apply=%inner.1
}

%cond.3 (p2: (s32[], f32[8,8])) -> pred[] {
  %cmp = pred[] compare(%gte2, %c5), direction=LT
}

ENTRY %main.4 (a: f32[8,8]) -> f32[8,8] {
  %w = (s32[], f32[8,8]) while(%t), condition=%cond.3, body=%body.2, backend_config={"known_trip_count":{"n":"7"}}
}
'''
    st = h.analyze(text)
    # all-reduce operand 8*8*4 bytes, in a call inside a 7-trip while
    assert st['collective_bytes']['all-reduce'] == 7 * 8 * 8 * 4
