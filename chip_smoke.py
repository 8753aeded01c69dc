"""Smoke run of the round engines on TPU, through their normal entry points.

    python chip_smoke.py [--seed N]              # one chip
    python chip_smoke.py --chips 4 [--seed N]    # the mesh engines on four

One chip runs three phases:

* ``priority`` — a binary fanout tree through ``PriorityRoundRunner`` (the
  fused ``HeapEngine``), checked against its closed-form per-depth counts;
* ``sssp``     — ``apps.sssp`` on the one-shard relaxed ``MeshHeapEngine``
  with split payloads, on a 256x256 weighted road grid (2^16 vertices: the
  largest power of four whose run stays near a minute, since heap waves
  are serial), checked against ``dijkstra_reference``;
* ``bfs``      — ``apps.bfs`` on the fused FIFO ``RingEngine``: a 2048x2048
  road grid (2^22 vertices) at batch 1024 over a 2^24-slot ring, checked
  against ``bfs_reference``.

``--chips 4`` runs only the mesh path, on a mesh over all four devices:
the same SSSP at four shards (checked against Dijkstra and the one-shard
result) and the sharded FIFO ring (``MeshRoundRunner(sharded=True)``) on a
fanout tree, checked against its closed-form counts.

The script refuses to start (exit 2) unless JAX's first device is a TPU,
``REPRO_PALLAS_INTERPRET`` is unset and Pallas kernels resolve to compiled
mode.  It runs in this one process and starts no other.  Each phase prints
one JSON line: sizes, peak device bytes (process-wide, up to the
phase's end), compile seconds (XLA backend
compiles, persistent-cache reads included), run seconds (the rest of the
phase's wall time: tracing, execution, host syncs), rounds, host syncs, the
face of each queue operation (``xla`` = plain XLA ops; every ``pallas_call``
traced in the megaround is listed as ``pallas``, a compiled Mosaic kernel,
or ``interpret``, which fails the phase) and the verdict.  The last line is
``{"ok": true, "device": {...}}`` only when every phase matched its
reference; otherwise the script exits 1.  Inputs are generated from
``--seed``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

BFS_SIDE, BFS_BATCH = 2048, 1024            # 2^22 vertices, 2^24 ring slots
SSSP_SIDE, SSSP_MAX_W = 256, 8              # 2^16 vertices
PRIO_ROOTS, PRIO_DEPTH, PRIO_BATCH = 32, 9, 128
FIFO_ROOTS, FIFO_DEPTH, FIFO_BATCH = 64, 16, 1024


def _fail_start(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


class CompileClock:
    """Sums XLA backend compile time (persistent-cache reads included) as
    reported through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration


def _peak_bytes(jax, devices):
    return [dev.memory_stats()["peak_bytes_in_use"] for dev in devices]


def _pallas_calls(jaxpr):
    """``(kernel name, interpret)`` of every ``pallas_call`` in a jaxpr,
    nested jaxprs (loops, branches, shard_map, pjit) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            kernel = eqn.params["jaxpr"].debug_info.func_name
            yield kernel, bool(eqn.params["interpret"])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls(sub)
                elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    yield from _pallas_calls(sub.jaxpr)


def _watch_megaround(jax, engine):
    """Wrap the engine's jitted megaround: the first call's arguments are
    kept as shapes so that, after the run, the same program can be traced
    again (no compile) and its Pallas calls read off."""
    jitted, seen = engine._megaround, []

    def call(*args):
        if not seen:
            seen.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None), args))
        return jitted(*args)

    engine._megaround = call
    return lambda: sorted(set(_pallas_calls(
        jitted.trace(*seen[0]).jaxpr.jaxpr)))


def _phase(name, jax, devices, clock, body):
    """Run one phase, time it and print its JSON line; returns its verdict.
    A phase that raises is reported and counted as failed."""
    c0, t0 = clock.seconds, time.perf_counter()
    try:
        row = body()
    except Exception:               # report every phase, then fail the run
        traceback.print_exc()
        row = {"correct": False, "error": traceback.format_exc(limit=1)}
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    row = {"phase": name, **row, "peak_bytes": _peak_bytes(jax, devices),
           "wall_s": wall, "compile_s": compile_s,
           "run_s": wall - compile_s}
    print(json.dumps(row), flush=True)
    return bool(row["correct"])


def _faces(xla_ops, calls, pallas=()) -> dict:
    """Each queue operation's face: ``xla`` for the plain-XLA plane waves
    named in ``xla_ops``, and for every ``pallas_call`` traced in the
    megaround ``pallas`` (compiled Mosaic) or ``interpret``.  The faces
    check holds when the traced kernels are exactly ``pallas``, all
    compiled."""
    faces = {op: "xla" for op in xla_ops}
    faces.update({name: "interpret" if interp else "pallas"
                  for name, interp in calls})
    return {"faces": faces,
            "faces_ok": sorted(calls) == sorted((k, False) for k in pallas)}


def phase_bfs(jax, np, seed: int):
    from repro.apps import bfs
    g = bfs.road_like(BFS_SIDE * BFS_SIDE)
    runner, init_fn = bfs.bfs_rounds_runner(g, batch=BFS_BATCH)
    kernels = _watch_megaround(jax, runner._engine)
    dist, _ = runner.run([0], acc=init_fn(0), max_rounds=1_000_000)
    dist = np.asarray(dist)
    same = bool(np.array_equal(dist, bfs.bfs_reference(g, 0)))
    faces = _faces(("dequeue", "enqueue"), kernels(),
                   pallas=("_wavefaa_kernel",))
    return {"vertices": g.n, "edges": g.m, "batch": BFS_BATCH,
            "ring_slots": 2 << runner.capacity_log2,
            "rounds": runner.stats["rounds"],
            "host_syncs": runner.stats["host_syncs"],
            "processed": runner.stats["processed"], **faces,
            "levels": int(dist.max()), "equals_reference": same,
            "correct": same and faces["faces_ok"]}


def _sssp_graph(seed: int):
    from repro.apps import bfs, sssp
    g = bfs.road_like(SSSP_SIDE * SSSP_SIDE)
    return g, sssp.with_weights(g, max_w=SSSP_MAX_W, seed=seed)


def _sssp_run(jax, np, g, w, mesh):
    from repro.apps import sssp
    runner, init_fn = sssp.sssp_mesh_rounds_runner(
        g, w, mesh=mesh, relaxed=True, split_payload=True)
    kernels = _watch_megaround(jax, runner._engine)
    dist, _ = runner.run([0], [0], acc=init_fn(0), max_rounds=10_000_000,
                         initial_aux=[0])
    faces = _faces(("pop/insert", "publish"), kernels())
    return np.asarray(dist), runner, faces


def phase_sssp(jax, np, seed: int, shards: int, ref=None):
    from repro.apps import sssp
    from repro.jaxcompat import make_mesh
    g, w = _sssp_graph(seed)
    dist, runner, faces = _sssp_run(jax, np, g, w,
                                    make_mesh((shards,), ("data",)))
    want = sssp.dijkstra_reference(g, w, 0)
    row = {"vertices": g.n, "edges": g.m, "shards": shards,
           "batch": runner.batch, "heap_capacity": runner.capacity,
           "rounds": runner.stats["rounds"],
           "host_syncs": runner.stats["host_syncs"],
           "processed": runner.stats["processed"], **faces,
           "equals_dijkstra": bool(np.array_equal(dist, want))}
    ok = row["equals_dijkstra"] and faces["faces_ok"]
    if ref is not None:
        # the one-shard result on one device of the same host
        one, _, _ = _sssp_run(jax, np, g, w, make_mesh((1,), ("data",)))
        row["equals_1shard"] = bool(np.array_equal(dist, one))
        ok = ok and row["equals_1shard"]
    row["correct"] = ok
    return row


def _fanout_step(jnp, depth: int):
    """Each item carries its remaining depth; it counts itself at that
    depth and spawns two children one level down until depth 0."""
    def step(acc, vals, valid):
        acc = acc.at[jnp.clip(vals, 0, depth)].add(valid.astype(jnp.int32))
        cv = jnp.broadcast_to((vals - 1)[:, None], (vals.shape[0], 2))
        return acc, cv.astype(jnp.int32), (valid & (vals > 0))[:, None]
    return step


def _fanout_counts(np, roots: int, depth: int):
    return np.array([roots << (depth - d) for d in range(depth + 1)],
                    np.int64)


def phase_priority(jax, np, seed: int):
    import jax.numpy as jnp
    from repro.runtime import PriorityRoundRunner
    fifo = _fanout_step(jnp, PRIO_DEPTH)

    def step(acc, keys, vals, valid):
        acc, cv, cm = fifo(acc, vals, valid)
        ck = keys[:, None] + 1 + cv            # deeper items pop later
        return acc, ck.astype(jnp.int32), cv, cm

    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 20, PRIO_ROOTS).astype(np.int32)
    peak = PRIO_ROOTS << PRIO_DEPTH
    runner = PriorityRoundRunner(step, capacity_log2=int(np.log2(2 * peak)),
                                 batch=PRIO_BATCH)
    kernels = _watch_megaround(jax, runner._engine)
    acc, _ = runner.run(keys, np.full(PRIO_ROOTS, PRIO_DEPTH, np.int32),
                        acc=jnp.zeros(PRIO_DEPTH + 1, jnp.int32),
                        max_rounds=1_000_000)
    want = _fanout_counts(np, PRIO_ROOTS, PRIO_DEPTH)
    faces = _faces(("pop/insert",), kernels())
    same = bool(np.array_equal(np.asarray(acc), want)
                and runner.stats["processed"] == want.sum())
    return {"roots": PRIO_ROOTS, "depth": PRIO_DEPTH, "batch": PRIO_BATCH,
            "heap_capacity": runner.capacity,
            "rounds": runner.stats["rounds"],
            "host_syncs": runner.stats["host_syncs"],
            "processed": runner.stats["processed"], **faces,
            "equals_closed_form": same, "correct": same and faces["faces_ok"]}


def phase_mesh_fifo(jax, np, seed: int, shards: int):
    import jax.numpy as jnp
    from repro.jaxcompat import make_mesh
    from repro.runtime import MeshRoundRunner
    del seed                                   # the tree is deterministic
    peak = FIFO_ROOTS << FIFO_DEPTH
    cap_log2 = max(int(np.log2(2 * peak)),
                   int(np.ceil(np.log2(4 * FIFO_BATCH * shards))))
    runner = MeshRoundRunner(_fanout_step(jnp, FIFO_DEPTH),
                             mesh=make_mesh((shards,), ("data",)),
                             capacity_log2=cap_log2, batch=FIFO_BATCH,
                             sharded=True, combine=lambda a: a.sum(0))
    kernels = _watch_megaround(jax, runner._engine)
    acc, _ = runner.run(np.full(FIFO_ROOTS, FIFO_DEPTH, np.int32),
                        acc=jnp.zeros(FIFO_DEPTH + 1, jnp.int32),
                        max_rounds=1_000_000)
    want = _fanout_counts(np, FIFO_ROOTS, FIFO_DEPTH)
    faces = _faces(("dequeue", "enqueue", "publish"), kernels())
    same = bool(np.array_equal(np.asarray(acc), want)
                and runner.stats["processed"] == want.sum())
    return {"roots": FIFO_ROOTS, "depth": FIFO_DEPTH, "shards": shards,
            "batch_per_shard": FIFO_BATCH, "ring_capacity": 1 << cap_log2,
            "carry_bytes_per_shard": runner.loop_carry_bytes(),
            "rounds": runner.stats["rounds"],
            "host_syncs": runner.stats["host_syncs"],
            "processed": runner.stats["processed"], **faces,
            "equals_closed_form": same, "correct": same and faces["faces_ok"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if "REPRO_PALLAS_INTERPRET" in os.environ:
        _fail_start("REPRO_PALLAS_INTERPRET is set: the smoke run only "
                    "runs compiled Pallas kernels")
    import jax
    import numpy as np
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail_start(f"no TPU: JAX's first device is {devices[0].platform}")
    if len(devices) < args.chips:
        _fail_start(f"--chips {args.chips} needs {args.chips} devices, "
                    f"JAX sees {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    from repro.kernels.pallas_env import resolve_interpret
    if resolve_interpret(None):
        _fail_start("Pallas kernels resolve to interpret mode")
    print(json.dumps({"compile_cache": use_compile_cache(ROOT)}), flush=True)
    clock = CompileClock(jax)
    used = devices[:args.chips]
    if args.chips == 1:
        # smallest state first: the peak is process-wide and only rises
        phases = [
            ("priority", lambda: phase_priority(jax, np, args.seed)),
            ("sssp", lambda: phase_sssp(jax, np, args.seed, 1)),
            ("bfs", lambda: phase_bfs(jax, np, args.seed)),
        ]
    else:
        phases = [
            ("sssp_mesh", lambda: phase_sssp(jax, np, args.seed,
                                             args.chips, ref=True)),
            ("fifo_mesh_sharded", lambda: phase_mesh_fifo(
                jax, np, args.seed, args.chips)),
        ]
    verdicts = [_phase(name, jax, used, clock, body) for name, body in phases]
    if not all(verdicts):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
