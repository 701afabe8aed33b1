"""Every op of a compiled plan is named: the scope checks of
test_fft_scopes.py, and their run on a 2x2 mesh of four CPU devices.

    PYTHONPATH=src python tests/_fft_scopes_worker.py

An op's name stack (its HLO ``op_name``) must hold ``fft.boundary``, or
``fft.step<i>`` and, inside it, one leaf scope; the superstep indices
must be those of the plan's schedule, each holding its axis pass, and
``fft.combine`` must appear in real plans only.
"""
import os
import re
import sys

if __name__ == '__main__':
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

SHAPE = (16, 16, 16)
LEAF = re.compile(r'fft\.step(\d+)/(?:.*/)?fft\.(pass|relayout|swap|combine)'
                  r'(?:/|$)')
OPCODE = re.compile(r'([a-z][a-z0-9-]*)\(')
OP_NAME = re.compile(r'op_name="([^"]*)"')
#: ops XLA itself makes, which carry no name of the program
UNNAMED = {'parameter', 'constant', 'broadcast', 'tuple',
           'get-tuple-element', 'bitcast'}
#: and, for a sharded real plan, its gather of the odd half-spectrum
#: axis into the replicated output layout the plan's out_sharding asks
#: for (on this mesh, the one unnamed work in any plan)
RESHARD = {'all-gather', 'slice', 'fusion', 'copy', 'partition-id'}


def compiled_ops(fn, arg):
    """(opcode, op_name) of every instruction of ``fn``'s compiled
    program, fused computations included ('' where it has no name)."""
    out = []
    for line in fn.lower(arg).compile().as_text().splitlines():
        name, sep, rest = line.partition(' = ')
        if not sep or not name.lstrip().lstrip('ROOT ').startswith('%'):
            continue
        op = OPCODE.search(rest)
        opcode = op.group(1) if op else ''
        if opcode == 'broadcast' and 'broadcast(%constant' in rest:
            opcode = 'constant'         # a constant, materialised
        m = OP_NAME.search(rest)
        out.append((opcode, m.group(1) if m else ''))
    return out


def check(mesh, *, real, overlap_chunks):
    """Plan a 16^3 transform on ``mesh``, run one pair, and check the
    names of both compiled programs. Returns the leaves seen."""
    import repro.fft as fft
    p = (fft.rplan if real else fft.plan)(SHAPE, mesh,
                                          overlap_chunks=overlap_chunks)
    x = jax.device_put(jnp.zeros(SHAPE, jnp.float32 if real
                                 else jnp.complex64), p.in_sharding)
    sx = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    y = p.forward(x)
    sy = jax.ShapeDtypeStruct(y.shape, y.dtype, sharding=y.sharding)
    p.inverse(y).block_until_ready()
    unnamed = UNNAMED | (RESHARD if real and mesh.size > 1 else set())
    leaves = set()
    for (direction, *_), fn in p._exec_cache.items():
        steps = {}
        for opcode, name in compiled_ops(fn, sx if direction == 'fwd'
                                         else sy):
            if not name:
                assert opcode in unnamed, (direction, opcode)
                continue
            own = name.split(';')[0]    # merged ops join their names
            if opcode in ('parameter', 'constant') or 'fft.boundary' in own:
                continue
            m = LEAF.search(own)
            assert m, (direction, opcode, name)
            steps.setdefault(int(m.group(1)), set()).add(m.group(2))
            leaves.add(m.group(2))
        # three axis passes, one a superstep, in schedule order
        assert sorted(steps) == [0, 1, 2], (direction, steps)
        assert all('pass' in v for v in steps.values()), (direction, steps)
        if mesh.size > 1:
            # two exchanges a direction, each inside a superstep (a
            # pipelined (swap, c2r) tail is one superstep: the c2r's)
            assert sum('swap' in v for v in steps.values()) == 2, (
                direction, steps)
    assert ('combine' in leaves) == real, leaves
    return leaves


def main():
    from repro.fft import pencil
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ('x', 'y'))
    n = 0
    for fused in ('1', '0'):
        os.environ[pencil.FUSE_ENV] = fused
        for real in (False, True):
            for oc in (1, 4):
                leaves = check(mesh, real=real, overlap_chunks=oc)
                print(f'PASS fused={fused} real={real} overlap_chunks={oc} '
                      f'leaves={sorted(leaves)}', flush=True)
                n += 1
    print(f'ALL {n} SCOPE CHECKS PASSED')


if __name__ == '__main__':
    sys.exit(main())
