"""Layout algebra of the pencil decomposition: schedules, swap planning,
and invariants. These run with a single device — pure symbolic checks of
the redistribution engine's bookkeeping. Hypothesis-based invariants live
in test_layout_properties.py (skipped without hypothesis).

Schedules are imported from repro.fft.pencil (their home); the
core.distributed deprecation shim is checked to re-export them."""
import pytest

from repro.core import plan as planlib
from repro.fft import pencil as dist


def test_forward_schedule_3d_matches_paper():
    """Paper §4.2: z-FFT, row transpose (x<->z), x-FFT, column transpose
    (x<->y), y-FFT."""
    steps, final = dist.forward_schedule(('x', 'y', None))
    assert steps == (('fft', 2), ('swap', 'x', 2), ('fft', 0),
                     ('swap', 'y', 0), ('fft', 1))
    assert final == ('y', None, 'x')


def test_forward_schedule_2d():
    steps, final = dist.forward_schedule((('x', 'y'), None))
    assert steps == (('fft', 1), ('swap', ('x', 'y'), 1), ('fft', 0))
    assert final == (None, ('x', 'y'))


def test_inverse_schedule_mirrors_forward():
    ins, final = dist.inverse_schedule(('x', 'y', None))
    assert final == ('x', 'y', None)
    # reverse superstep order: y, swap, x, swap, z
    assert [s[0] for s in ins] == ['fft', 'swap', 'fft', 'swap', 'fft']
    assert ins[0] == ('fft', 1)
    assert ins[-1] == ('fft', 2)


def test_swap_algebra():
    lay = ('x', 'y', None)
    lay2 = planlib.swap(lay, 'x', 2)
    assert lay2 == (None, 'y', 'x')
    lay3 = planlib.swap(lay2, 'y', 0)
    assert lay3 == ('y', None, 'x')
    with pytest.raises(ValueError):
        planlib.swap(lay, 'x', 0)  # pos 0 is not a memory axis


def test_plan_swaps_roundtrip():
    src = ('x', 'y', None)
    dst = ('y', None, 'x')
    path = planlib.plan_swaps(src, dst)
    lay = src
    for ax, mp in path:
        lay = planlib.swap(lay, ax, mp)
    assert lay == dst
    assert planlib.plan_swaps(src, src) == ()


def test_plan_local_shape_and_validate():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ('x', 'y'))
    p = planlib.make_fft3d_plan(8, mesh)
    p.validate()
    assert p.local_shape() == (8, 8, 8)


def test_distributed_shim_reexports():
    """core.distributed stays importable and points at repro.fft."""
    from repro.core import distributed as shim
    assert shim.make_fft is dist.make_fft
    assert shim.forward_schedule is dist.forward_schedule
    from repro.fft import large1d
    assert shim.make_fft1d_large is large1d.make_fft1d_large
