"""Compute/communication overlap, composable with any comm strategy.

The wsFFT schedule alternates local compute (a pencil FFT, an expert
matmul, an attention block) with an ownership swap. Running them
back-to-back leaves the wires idle during compute and the ALUs idle
during the swap; splitting the local batch into chunks and issuing
``compute(chunk_i+1)`` while ``swap(chunk_i)`` is in flight lets XLA's
latency-hiding scheduler overlap the two (the beyond-paper pipelining
previously hardcoded inside ``fft/pencil.py``).

This module owns the generic machinery so the *same* pipelining
composes with every registered strategy and every caller: the pencil
supersteps, the large-1D four-step, MoE expert dispatch and Ulysses
sequence-parallel attention.

Two granularities live here:

* :func:`pipelined` / :func:`overlapped_fft_swap` run *inside*
  ``shard_map`` on per-device local blocks — they chunk ONE call's
  work so chunk i+1's compute overlaps chunk i's collective.
* :func:`pipelined_stream` / :class:`StreamPipeline` run at the host
  level, *outside* jit — they keep a bounded window of whole dispatched
  calls in flight (the serve engine's cross-request double buffer), so
  request group g+1's pencil FFTs are already dispatched while group
  g's redistribution drains. The class form persists the window across
  calls: the serve engine's background drainer pushes ripe request
  groups into ONE pipeline on every wakeup, so the double buffer spans
  drainer passes instead of refilling from empty each time.
"""
from __future__ import annotations

from collections import deque
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import scopes


def pick_chunk_axis(local_shape: Sequence[int], exclude: Sequence[int],
                    n_chunks: int) -> Optional[int]:
    """First local axis that can carry the pipeline: not involved in the
    compute/swap pair (``exclude``) and divisible into ``n_chunks``.
    Returns None when no axis qualifies (caller falls back to the
    unpipelined path)."""
    if n_chunks <= 1:
        return None
    for pos, size in enumerate(local_shape):
        if pos not in exclude and size % n_chunks == 0 and size >= n_chunks:
            return pos
    return None


def pipelined(n_chunks: int, axis: int, fn: Callable, *arrays: jnp.ndarray):
    """Run ``fn`` over ``n_chunks`` slices of ``arrays`` along ``axis``
    and concatenate the per-chunk results along the same axis.

    ``fn(*chunks)`` is the per-chunk stage composition — typically
    compute followed by a strategy swap (or swap, compute, swap); chunk
    i+1's compute overlaps chunk i's collective. ``fn`` may return one
    array or a tuple; shapes may change on any axis other than the
    chunk axis's *position* (the swap moves sizes between axes, the
    chunk axis position itself must be preserved).

    With ``n_chunks <= 1`` this is exactly ``fn(*arrays)``.
    """
    if n_chunks <= 1:
        return fn(*arrays)
    with scopes.scope(scopes.RELAYOUT):
        parts = list(zip(*(jnp.split(a, n_chunks, axis=axis)
                           for a in arrays)))
    outs = [fn(*chunk) for chunk in parts]
    with scopes.scope(scopes.RELAYOUT):
        if isinstance(outs[0], tuple):
            return tuple(jnp.concatenate([o[k] for o in outs], axis=axis)
                         for k in range(len(outs[0])))
        return jnp.concatenate(outs, axis=axis)


class StreamPipeline:
    """A bounded window of dispatched-but-unforced jax calls that
    *persists across pushes* — the host-level double buffer of a
    continuous server.

    jax dispatch is asynchronous: pushing call i+1 right after call i
    returns puts both executables in the device queue, and XLA's
    latency-hiding scheduler overlaps request i+1's local compute with
    request i's collectives. An *unbounded* queue, though, stages every
    request's operand at once; :meth:`push` forces the oldest in-flight
    result before dispatching a new one, capping live operands at
    ``depth`` (with donated inputs: ``depth`` buffers total, not 2x).

    Unlike :func:`pipelined_stream` — which drains to empty when its
    input stream ends — the window here survives between calls: the
    serve engine's background drainer pushes each wakeup's ripe request
    groups into one long-lived pipeline, so under sustained load group
    g+1 (possibly from the *next* drainer pass) is already dispatched
    while group g's redistribution drains.

    Each pushed thunk may carry its own ``on_result`` callback, invoked
    right after its result is FORCED (``block_until_ready`` succeeded),
    in push order — so when a later call fails at execution time,
    callers observe exactly the prefix that completed, never an
    unforced (possibly poisoned) value. A force that raises pops the
    failed call; the caller decides whether to :meth:`drain` the
    survivors or :meth:`abort` the window.
    """

    def __init__(self, depth: int = 2):
        if depth < 1:
            raise ValueError(f"StreamPipeline needs depth >= 1, got {depth}")
        self.depth = depth
        self._inflight: deque = deque()

    def __len__(self) -> int:
        return len(self._inflight)

    def _force_oldest(self):
        result, on_result, on_error = self._inflight.popleft()
        try:
            result = jax.block_until_ready(result)
        except BaseException as exc:
            if on_error is not None:
                on_error(exc)
            raise
        if on_result is not None:
            on_result(result)
        return result

    def push(self, thunk: Callable, on_result: Optional[Callable] = None,
             on_error: Optional[Callable] = None):
        """Dispatch ``thunk()`` (forcing the oldest in-flight results
        first so at most ``depth`` are ever staged at once; depth=1
        serializes). ``on_error(exc)`` identifies the CULPRIT when this
        call's dispatch or forced result raises — pipeline failures
        tear down every in-flight call, and without attribution the
        serve engine could not retry innocent bystanders for free."""
        while len(self._inflight) >= self.depth:
            self._force_oldest()
        try:
            result = thunk()
        except BaseException as exc:
            if on_error is not None:
                on_error(exc)
            raise
        self._inflight.append((result, on_result, on_error))

    def drain(self) -> None:
        """Force every in-flight result, oldest first."""
        while self._inflight:
            self._force_oldest()

    def abort(self) -> int:
        """Drop every in-flight call without forcing it (their
        ``on_result`` callbacks never run — the serve engine re-queues
        the matching requests from snapshots). Returns the number
        dropped."""
        n = len(self._inflight)
        self._inflight.clear()
        return n


def pipelined_stream(fn: Callable, stream: Iterable, *,
                     depth: int = 2,
                     on_result: Optional[Callable] = None) -> List:
    """Map ``fn`` over a stream of requests with at most ``depth``
    dispatched-but-unforced results in flight (double-buffering at the
    default depth of 2) — a one-shot :class:`StreamPipeline`. Returns
    the results in stream order; ``on_result`` fires per forced result,
    in stream order, exactly as the class documents."""
    pipe = StreamPipeline(depth)
    out: List = []

    def collect(r):
        if on_result is not None:
            on_result(r)
        out.append(r)

    for item in stream:
        pipe.push(lambda item=item: fn(item), collect)
    pipe.drain()
    return out


def overlapped_fft_swap(re: jnp.ndarray, im: jnp.ndarray, *,
                        fft_fn: Callable, swap_fn: Callable,
                        chunk_axis: int, n_chunks: int,
                        wire_dtype: str = 'native'
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The pencil superstep pair — ``fft`` then ``swap`` — pipelined
    over ``n_chunks`` slices of ``chunk_axis``. ``fft_fn(re, im)`` and
    ``swap_fn(x)`` operate on local chunks. A compact ``wire_dtype``
    casts each chunk to the wire format around its swap independently
    (the chunk's compute stays full precision, and chunk i+1's cast
    cannot stall behind chunk i's collective)."""
    from repro.comm import strategies as _strat

    def stage(cr, ci):
        cr, ci = fft_fn(cr, ci)
        out = []
        for c in (cr, ci):
            with scopes.scope(scopes.SWAP):
                w, restore = _strat.wire_cast(c, wire_dtype)
                out.append(_strat.wire_restore(swap_fn(w), restore))
        return tuple(out)
    return pipelined(n_chunks, chunk_axis, stage, re, im)
