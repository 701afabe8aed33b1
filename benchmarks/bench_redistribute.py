"""Microbenchmark of the repro.comm redistribution strategies.

Sweeps mesh shapes x axis groups x message sizes x wire dtypes on the
fake-device mesh (16 host devices), timing one ownership swap per
registered strategy — plus searched pod trees per mesh — and printing
it next to the wse_model prediction. Emits ``BENCH_redistribute.json``
at the repo root so the perf trajectory accumulates data across PRs.

Grid dimensions the measured table keys on:

* ``dtype`` — the wire format of the timed component array: 'c64'
  (f32 component of a complex64 planar pair), 'c128' (f64), and the
  compact wire formats 'f16'/'bf16' (an f32 component cast to 16 bits
  around the collective via ``strategies.swap_axes_wire`` — what a
  ``wire_dtype='fp16'|'bf16'`` plan puts on the wire).
* ``strategy`` — the registered names plus ``'pod_tree:<spec>'``
  trees; recording tree rows is what lets ``comm='auto'`` consider
  them (:func:`repro.comm.cost._tree_candidates`).

With ``--refresh`` the new grid points are MERGED into the existing
file — rows with the same (mesh, group, strategy, dtype, local_elems)
key are replaced, everything else (older sweeps, other hosts' points)
is kept — instead of overwriting the whole table. New wire-dtype and
tree rows are new keys, so a refresh never orphans existing rows.

``--smoke`` runs a seconds-long CI subset — one mesh/group/size, one
fp16-wire and one searched-tree config — and does not write the JSON.

In full mode the run asserts that fp16 wire beats native wall time
for at least one (mesh, group, strategy) at the 32^3-on-16-devices
per-device size (2048 elems) — the PR's headline perf claim.

Run:  PYTHONPATH=src python benchmarks/bench_redistribute.py \
          [--refresh | --smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax                                  # noqa: E402

jax.config.update("jax_enable_x64", True)   # the c128 grid needs real f64

import jax.numpy as jnp                     # noqa: E402
import numpy as np                          # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import comm                      # noqa: E402
from benchmarks.common import time_jax, emit  # noqa: E402
from repro.launch.mesh import make_mesh     # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_redistribute.json")

MESHES = [((4, 4), ("x", "y")), ((2, 8), ("x", "y"))]
GROUPS = ["x", "y", ("x", "y")]
#: local (mem_dim, row) sizes — mem_dim must divide by the group size.
#: (32, 64) is the 32^3-on-16-devices point: 2048 per-device elems.
SIZES = [(16, 64), (32, 64), (64, 256), (256, 1024)]
#: native wire grid: the f32 / f64 component array of a planar pair
DTYPES = [('c64', jnp.float32), ('c128', jnp.float64)]
#: compact wire grid, timed on f32 operands cast around the collective;
#: tags match cost.WIRE_MEASURED_DTYPE so fp16-wire plans hit the rows
WIRES = [('f16', 'fp16'), ('bf16', 'bf16')]
#: searched pod trees recorded per mesh — what comm='auto' may pick
TREES = {
    (4, 4): ('pod_tree:x.2*x.2*y.2*y.2', 'pod_tree:x.4*y.2*y.2'),
    (2, 8): ('pod_tree:x.2*y.2*y.2*y.2',),
}
#: per-device component elems of a 32^3 transform on 16 devices — the
#: size the fp16-beats-native acceptance gate reads
GATE_ELEMS = 32 * 64


def bench_swap(mesh, group, strategy, mem_dim, rows, jdtype,
               wire='native'):
    st = comm.get(strategy)

    def f(a):
        return comm.strategies.swap_axes_wire(
            st, a, group, shard_pos=0, mem_pos=1, wire_dtype=wire)

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(group, None),
                               out_specs=P(None, group), check_vma=False))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (rows * comm.strategies.static_group_size(group, dict(mesh.shape)),
         mem_dim)), jdtype)
    return time_jax(fn, x)


def _row_key(r):
    return (r.get('mesh'), r.get('group'), r.get('strategy'),
            r.get('dtype'), r.get('local_elems'))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--refresh', action='store_true',
                    help='merge new grid points into the existing JSON '
                         '(replace same-key rows, keep the rest) instead '
                         'of overwriting it')
    ap.add_argument('--smoke', action='store_true',
                    help='CI subset: one mesh/group/size with one '
                         'fp16-wire and one pod-tree config; no JSON')
    args = ap.parse_args(argv)
    meshes, groups, sizes = MESHES, GROUPS, SIZES
    wires = WIRES
    if args.smoke:
        meshes, groups, sizes = MESHES[:1], [("x", "y")], [(32, 64)]
        wires = WIRES[:1]
    print("# bench_redistribute: one ownership swap per strategy")
    print("mesh,group,strategy,p,local_elems,dtype,us,model_cycles")
    results = []
    for mesh_dims, names in meshes:
        mesh = make_mesh(mesh_dims, names)
        mesh_shape = dict(mesh.shape)
        trees = TREES.get(mesh_dims, ())
        strategies = comm.names() + (trees[:1] if args.smoke else trees)
        if args.smoke:
            strategies = ('all_to_all',) + trees[:1]
        for group in groups:
            p = comm.strategies.static_group_size(group, mesh_shape)
            for mem_dim, rows in sizes:
                if mem_dim % p:
                    continue
                elems = mem_dim * rows       # per-device component elems

                def record(strategy, dtype, us, model):
                    gname = (group if isinstance(group, str)
                             else '*'.join(group))
                    tag = (f"redistribute/{mesh_dims[0]}x{mesh_dims[1]}/"
                           f"{gname}/{strategy}/{dtype}/e{elems}")
                    emit(tag, us, f"model_cycles={model:.0f}")
                    results.append(dict(
                        mesh=f"{mesh_dims[0]}x{mesh_dims[1]}",
                        group=gname, strategy=strategy, p=p,
                        local_elems=elems, dtype=dtype,
                        us=us, model_cycles=model))

                for strategy in strategies:
                    for dtype, jdtype in DTYPES:
                        if args.smoke and dtype != 'c64':
                            continue
                        # byte-equivalent f32 count for the model column
                        f32_eq = elems * (2 if dtype == 'c128' else 1)
                        us = bench_swap(mesh, group, strategy, mem_dim,
                                        rows, jdtype)
                        model = comm.get(strategy).cost(
                            group, mesh_shape, f32_eq / 2.0, 'fp32').cycles
                        record(strategy, dtype, us, model)
                    for tag, wire in wires:
                        # an f32 component cast to 16 bits on the wire:
                        # half the bytes of the c64 row, plus the casts
                        us = bench_swap(mesh, group, strategy, mem_dim,
                                        rows, jnp.float32, wire=wire)
                        model = comm.get(strategy).cost(
                            group, mesh_shape, elems / 2.0, 'fp16').cycles
                        record(strategy, tag, us, model)
    if not args.smoke:
        nat = {(r['mesh'], r['group'], r['strategy']): r['us']
               for r in results
               if r['dtype'] == 'c64' and r['local_elems'] == GATE_ELEMS}
        f16 = {(r['mesh'], r['group'], r['strategy']): r['us']
               for r in results
               if r['dtype'] == 'f16' and r['local_elems'] == GATE_ELEMS}
        wins = sorted(k for k in f16 if k in nat and f16[k] < nat[k])
        assert wins, (
            f"fp16 wire beat native wall time on NO (mesh, group, "
            f"strategy) at the 32^3/16-device size ({GATE_ELEMS} elems)")
        print(f"# fp16 wire beats native at e{GATE_ELEMS} on "
              f"{len(wins)}/{len(f16)} configs, e.g. {wins[0]}")
    if args.smoke:
        print("# --smoke: JSON not written")
        return
    if args.refresh and os.path.exists(OUT):
        try:
            with open(OUT) as f:
                old = json.load(f).get('results', [])
        except (OSError, ValueError):
            old = []
        fresh = {_row_key(r) for r in results}
        kept = [r for r in old if _row_key(r) not in fresh]
        results = kept + results
        print(f"# --refresh: kept {len(kept)} existing rows")
    with open(OUT, "w") as f:
        json.dump(dict(benchmark="redistribute", backend=jax.default_backend(),
                       results=results), f, indent=1)
    print(f"wrote {os.path.normpath(OUT)} ({len(results)} rows)")


if __name__ == "__main__":
    main()
