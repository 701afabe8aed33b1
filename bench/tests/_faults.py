"""Faults planted under the timed path, for the tests that see
``correct`` come out false. Each patches the program (``repro``) while
it is open, and is undone when it closes; nothing here is imported by a
benchmark run."""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


def _map_out(out, f):
    return tuple(f(a) for a in out) if isinstance(out, tuple) else f(out)


@contextlib.contextmanager
def broken_raw(mp, kind: str):
    """Break every plan executable built while open:

    * ``unchanged``: the transform returns its input (complex plans);
    * ``half``: the second half of the output along its first axis is
      left out (zeros);
    * ``altered``: one output bin is negated where it is produced.
    """
    from repro.fft import api
    orig = api.FFT._raw

    def raw(self, direction, batched):
        fn = orig(self, direction, batched)

        def broken(*args):
            if kind == 'unchanged':
                return args if len(args) > 1 else args[0]
            out = fn(*args)
            if kind == 'half':
                return _map_out(out, lambda a: a.at[a.shape[0] // 2:].set(0))
            if kind == 'altered':
                return _map_out(out, lambda a: a.at[(1,) * a.ndim].multiply(-1))
            raise ValueError(kind)
        return broken

    mp.setattr(api.FFT, '_raw', raw)
    yield


@contextlib.contextmanager
def no_exchange(mp):
    """Every ownership swap keeps each device's own blocks: the local
    split and concat of the all-to-all, with no exchange between chips."""
    from repro.comm import strategies as st

    def swap_axes(self, x, mesh_axis, *, shard_pos, mem_pos):
        p = st.group_size(mesh_axis)
        return jnp.concatenate(jnp.split(x, p, axis=mem_pos), axis=shard_pos)

    for cls in (st.AllToAllStrategy, st.PpermuteStrategy,
                st.PodTreeStrategy):
        mp.setattr(cls, 'swap_axes', swap_axes)
    yield
