"""Sharding rules: divisibility guard and axis-collision guard.
Hypothesis property tests over arbitrary shapes live in
test_sharding_properties.py (skipped without hypothesis)."""
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import make_rules, spec_for
from repro.launch.mesh import make_mesh


@pytest.fixture(scope='module')
def mesh():
    return make_mesh((1, 1), ('data', 'model'))


# rules bound to a *virtual* 16x16 mesh for pure spec logic (no devices)
class FakeMesh:
    shape = {'data': 16, 'model': 16}


def rules(mode='train'):
    from repro.parallel.sharding import Rules
    r = make_rules.__wrapped__ if hasattr(make_rules, '__wrapped__') else None
    # build the table against the fake mesh
    import repro.parallel.sharding as S
    table = {
        'batch': 'data', 'embed': 'data', 'heads': 'model',
        'kv_heads': 'model', 'mlp': 'model', 'vocab': 'model',
        'expert': 'model', 'seq': None, 'seq_sp': 'model',
        'kv_seq': 'model' if mode == 'serve' else None,
        'state': None, 'kv_lora': None, 'pos': None,
    }
    return S.Rules(table=table, mesh=FakeMesh())


def test_divisibility_guard_drops_axis():
    r = rules()
    # kv_heads = 8 does not divide model=16 -> replicated
    assert spec_for(r, (32, 128, 8, 64),
                    ('batch', None, 'kv_heads', None)) == P('data')
    # kv_heads = 32 divides -> sharded
    assert spec_for(r, (32, 128, 32, 64),
                    ('batch', None, 'kv_heads', None)) == \
        P('data', None, 'model')


def test_axis_collision_guard():
    r = rules()
    # two logical axes mapping to 'model': only the first is applied
    assert spec_for(r, (64, 160, 1024), ('heads', 'expert', None)) == \
        P('model')


def test_trailing_nones_trimmed():
    r = rules()
    s = spec_for(r, (4, 4), (None, None))
    assert s == P()


def test_serve_mode_kv_seq():
    r = rules('serve')
    assert spec_for(r, (128, 32768, 8, 128),
                    ('batch', 'kv_seq', 'kv_heads', None)) == \
        P('data', 'model')


