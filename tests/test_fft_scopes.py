"""The program's names for its work (repro.core.scopes): every op of a
compiled plan sits in ``fft.boundary`` or in one superstep's leaf scope,
the scopes leave the compiled program unchanged, and each plan call is
one host span on the profiler's trace. The 2x2 mesh runs in a
subprocess so this process keeps one device (see _fft_scopes_worker.py).
"""
import contextlib
import glob
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import _fft_scopes_worker as worker
import repro.fft as fft
from repro.core import scopes
from repro.fft import pencil
from repro.launch.mesh import make_mesh

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("x", "y"))


def test_superstep_ids():
    lay = ("x", "y", None)
    fwd, final = pencil.forward_schedule(lay)
    assert [s[0] for s in fwd] == ["fft", "swap", "fft", "swap", "fft"]
    assert pencil.superstep_ids(fwd) == (0, 0, 1, 1, 2)
    inv, _ = pencil.inverse_schedule(lay)
    assert pencil.superstep_ids(inv) == (0, 0, 1, 1, 2)
    # restore_layout rotations follow no pass: supersteps of their own
    extra = (("swap", "x", 0), ("swap", "y", 1))
    assert pencil.superstep_ids(extra + inv) == (0, 1, 2, 2, 3, 3, 4)
    assert pencil.superstep_ids(fwd + extra) == (0, 0, 1, 1, 2, 2, 3)


@pytest.mark.parametrize("fused", ["1", "0"])
@pytest.mark.parametrize("overlap_chunks", [1, 4])
@pytest.mark.parametrize("real", [False, True])
def test_every_op_is_named(mesh, monkeypatch, real, overlap_chunks, fused):
    monkeypatch.setenv(pencil.FUSE_ENV, fused)
    leaves = worker.check(mesh, real=real, overlap_chunks=overlap_chunks)
    assert {"pass", "relayout"} <= leaves


@pytest.mark.slow
def test_every_op_is_named_on_four_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_fft_scopes_worker.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-4000:]}\nSTDERR:\n{r.stderr[-4000:]}"
    assert "ALL 8 SCOPE CHECKS PASSED" in r.stdout


META = re.compile(r",? metadata=\{[^}]*\}")
#: the compiled text's tables of source locations, which metadata points at
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def program_text(real, direction):
    """Compiled text of a fresh 16^3 plan's executable, metadata left
    out."""
    p = (fft.rplan if real else fft.plan)(worker.SHAPE,
                                          make_mesh((1, 1), ("x", "y")))
    x = jnp.zeros(worker.SHAPE, jnp.float32 if real else jnp.complex64)
    sx = jax.ShapeDtypeStruct(x.shape, x.dtype)
    y = p.forward(x)
    sy = jax.ShapeDtypeStruct(y.shape, y.dtype)
    p.inverse(y)
    fn = p._exec_cache[(direction, (), (sx if direction == "fwd"
                                        else sy).dtype.name, False)]
    text = fn.lower(sx if direction == "fwd" else sy).compile().as_text()
    blocks = [b for b in text.split("\n\n")
              if b.split("\n", 1)[0] not in TABLES]
    return META.sub("", "\n\n".join(blocks))


@pytest.mark.parametrize("direction", ["fwd", "inv"])
@pytest.mark.parametrize("real", [False, True])
def test_scopes_leave_the_program_unchanged(monkeypatch, real, direction):
    named = program_text(real, direction)
    assert "op_name" not in named and "fft.step" not in named
    monkeypatch.setattr(scopes, "scope", lambda name: contextlib.nullcontext())
    assert program_text(real, direction) == named


def host_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("fft.")]


@pytest.mark.parametrize("real", [False, True])
def test_one_host_span_per_plan_call(mesh, tmp_path, real):
    p = (fft.rplan if real else fft.plan)(worker.SHAPE, mesh)
    x = jnp.ones(worker.SHAPE, jnp.float32 if real else jnp.complex64)
    x = p.inverse(p.forward(x))                  # warm: both compile here
    pairs = 3
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(pairs):
            x = p.inverse(p.forward(x))
        x.block_until_ready()
    names = host_spans(str(tmp_path))
    assert names.count("fft.apply.fwd") == pairs
    assert names.count("fft.apply.inv") == pairs
    assert "fft.compile" not in names


def test_first_call_is_a_compile_span(mesh, tmp_path):
    p = fft.plan(worker.SHAPE, mesh)
    with jax.profiler.trace(str(tmp_path)):
        p.forward(jnp.ones(worker.SHAPE, jnp.complex64)).block_until_ready()
    assert host_spans(str(tmp_path)) == ["fft.apply.fwd", "fft.compile"]
