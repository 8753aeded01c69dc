"""Mesh round-engine benchmark: legacy host-driven per-round shard_map
dispatch vs the fused device-resident megaround loop (DESIGN.md § 2.3,
BENCH_4).

Workloads (both on ≥2 shards of a forced-host-device CPU mesh):

* ``fanout`` — the geometric spawn tree of bench_rounds, now spread over
  the mesh: every round each shard claims its rebalanced share of the
  global frontier, steps it, and publishes children with one psum.  Pure
  coordination cost — the mesh engine IS the workload.
* ``bfs``    — ``apps.bfs.bfs_mesh_rounds`` on a road-like grid (long
  diameter → many rounds: the per-round host-sync regime) and a kron-like
  power-law graph.

Multi-device CPU meshes need ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` set *before* jax initializes, so the sweep runs in a
subprocess (``--inner``) and the parent relays its CSV — same pattern as
tests/test_distqueue.py.  Timings are best-of-``TRIALS`` per mode (the
shared-runner scheduler noise on oversubscribed CPU devices is large);
compilation is excluded by a warmup run.

``--smoke`` is the CI acceptance gate: fused/legacy bit-parity (acc +
planes + head/tail + stats) on both workloads and host_syncs 1 vs
per-round — correctness only, no speedup assertion (CI timing noise).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from .cpu_child import spawn_inner

HEADER = ("bench,workload,batch,shards,mode,rounds,items,elapsed_s,"
          "rounds_per_s,items_per_s,host_syncs,drained,"
          "carry_bytes_per_shard")
TRIALS = 3


def _spawn_inner(args, out) -> int:
    """Run this module's inner half on the requested number of
    virtual CPU devices; relay its stdout into ``out``."""
    return spawn_inner("benchmarks.bench_mesh", args, out,
                       devices=int(args[args.index('--shards') + 1]))


# ---------------------------------------------------------------------------
# inner (subprocess) side — jax only imported here
# ---------------------------------------------------------------------------


def _fanout_step(fanout: int, depth: int):
    import jax.numpy as jnp

    def step(acc, vals, valid):
        acc = acc.at[jnp.clip(vals, 0, depth)].add(valid.astype(jnp.int32))
        cv = jnp.broadcast_to((vals - 1)[:, None],
                              (vals.shape[0], fanout)).astype(jnp.int32)
        cm = (valid & (vals > 0))[:, None]
        return acc, cv, cm
    return step


def _expected_fanout_acc(fanout: int, depth: int, roots: int):
    import numpy as np
    counts = np.zeros(depth + 1, np.int64)
    for d in range(depth, -1, -1):
        counts[d] = roots * fanout ** (depth - d)
    return counts.astype(np.int32)


def _mesh(shards: int):
    import jax
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))
    from repro.jaxcompat import make_mesh
    assert len(jax.devices()) >= shards, (
        f"need {shards} devices, have {len(jax.devices())} "
        f"(XLA_FLAGS not set before jax init?)")
    return make_mesh((shards,), ("data",))


def _fanout_runner(mesh, batch: int, *, fused: bool, sharded: bool = False,
                   depth: int = 14, roots: int = 4, sync_every: int = 0):
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime import MeshRoundRunner

    shards = int(mesh.shape["data"])
    peak = roots * 2 ** depth
    cap_log2 = max(int(np.ceil(np.log2(2 * peak))),
                   int(np.ceil(np.log2(4 * batch * shards))))
    runner = MeshRoundRunner(_fanout_step(2, depth), mesh=mesh,
                             capacity_log2=cap_log2, batch=batch,
                             fused=fused, sharded=sharded,
                             sync_every=sync_every,
                             combine=lambda a: a.sum(0))
    seeds = np.full(roots, depth, np.int32)
    acc0 = jnp.zeros(depth + 1, jnp.int32)
    return runner, seeds, acc0


def run_fanout(mesh, batch: int, *, fused: bool, sharded: bool = False,
               depth: int = 14, roots: int = 4, trials: int = TRIALS):
    """Best-of-``trials`` timed fanout run (post-warmup).  Returns
    (row dict, acc, state)."""
    import numpy as np
    runner, seeds, acc0 = _fanout_runner(mesh, batch, fused=fused,
                                         sharded=sharded, depth=depth,
                                         roots=roots)
    acc, st = runner.run(seeds, acc=acc0, max_rounds=1_000_000)  # warmup
    best = None
    for _ in range(trials):
        t0 = time.perf_counter()
        acc, st = runner.run(seeds, acc=acc0, max_rounds=1_000_000)
        el = time.perf_counter() - t0
        best = el if best is None else min(best, el)
    mode = "sharded" if sharded else ("fused" if fused else "legacy")
    row = _row("fanout", batch, int(mesh.shape["data"]), mode,
               runner.stats, best, runner.loop_carry_bytes())
    return row, np.asarray(acc), st


def run_bfs(mesh, batch: int, *, fused: bool, graph: str = "road",
            n: int = 1024, trials: int = TRIALS):
    import numpy as np
    from repro.apps import bfs

    g = (bfs.road_like(n) if graph == "road"
         else bfs.kron_like(n, avg_deg=4, seed=1))
    runner, init_fn = bfs.bfs_mesh_rounds_runner(g, mesh=mesh, batch=batch,
                                                 fused=fused)
    runner.run([0], acc=init_fn(0), max_rounds=1_000_000)        # warmup
    best, dist = None, None
    for _ in range(trials):
        t0 = time.perf_counter()
        dist, _ = runner.run([0], acc=init_fn(0), max_rounds=1_000_000)
        el = time.perf_counter() - t0
        best = el if best is None else min(best, el)
    row = _row(f"bfs_{graph}", batch, int(mesh.shape["data"]),
               "fused" if fused else "legacy", runner.stats, best,
               runner.loop_carry_bytes())
    return row, np.asarray(dist)


def _row(workload: str, batch: int, shards: int, mode: str, stats: dict,
         elapsed: float, carry_bytes: int) -> dict:
    rounds, items = stats["rounds"], stats["processed"]
    return {
        "workload": workload, "batch": batch, "shards": shards,
        "mode": mode,
        "rounds": rounds, "items": items,
        "elapsed_s": round(elapsed, 4),
        "rounds_per_s": round(rounds / max(elapsed, 1e-9), 1),
        "items_per_s": round(items / max(elapsed, 1e-9), 1),
        "host_syncs": stats["host_syncs"], "drained": stats["drained"],
        "carry_bytes_per_shard": carry_bytes,
    }


def _emit(out, row: dict) -> None:
    print(f"mesh,{row['workload']},{row['batch']},{row['shards']},"
          f"{row['mode']},{row['rounds']},{row['items']},{row['elapsed_s']},"
          f"{row['rounds_per_s']},{row['items_per_s']},{row['host_syncs']},"
          f"{row['drained']},{row['carry_bytes_per_shard']}", file=out)


def run_fanout_interleaved(mesh, batch: int, *, depth: int = 14,
                           roots: int = 4, trials: int = TRIALS):
    """Timed fanout sweep over all three modes with trials interleaved
    (min-of-interleaved-trials: shared-runner scheduler drift hits every
    mode equally instead of biasing whichever ran last)."""
    modes = ("legacy", "fused", "sharded")
    rigs, best = {}, {}
    for mode in modes:
        rigs[mode] = _fanout_runner(mesh, batch, fused=mode != "legacy",
                                    sharded=mode == "sharded",
                                    depth=depth, roots=roots)
        runner, seeds, acc0 = rigs[mode]
        runner.run(seeds, acc=acc0, max_rounds=1_000_000)        # warmup
    for _ in range(trials):
        for mode in modes:
            runner, seeds, acc0 = rigs[mode]
            t0 = time.perf_counter()
            runner.run(seeds, acc=acc0, max_rounds=1_000_000)
            el = time.perf_counter() - t0
            best[mode] = min(best.get(mode, el), el)
    return {mode: _row("fanout", batch, int(mesh.shape["data"]), mode,
                       rigs[mode][0].stats, best[mode],
                       rigs[mode][0].loop_carry_bytes())
            for mode in modes}


def inner_main(out, shards: int, batches, bfs_n: int,
               graphs=("road", "kron")) -> None:
    mesh = _mesh(shards)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)
    for batch in batches:
        by_mode = run_fanout_interleaved(mesh, batch)
        for row in by_mode.values():
            _emit(out, row)
        speedup = (by_mode["fused"]["rounds_per_s"]
                   / max(by_mode["legacy"]["rounds_per_s"], 1e-9))
        ratio = (by_mode["sharded"]["rounds_per_s"]
                 / max(by_mode["fused"]["rounds_per_s"], 1e-9))
        print(f"# mesh fanout batch={batch} shards={shards}: fused "
              f"{speedup:.1f}x rounds/s, host_syncs "
              f"{by_mode['legacy']['host_syncs']} -> "
              f"{by_mode['fused']['host_syncs']}; sharded rings "
              f"{by_mode['sharded']['carry_bytes_per_shard']} B/shard "
              f"carry vs {by_mode['fused']['carry_bytes_per_shard']} B "
              f"replicated at {ratio:.2f}x fused rounds/s", file=out)
    for graph in graphs:
        for batch in batches:
            for fused in (False, True):
                row, _ = run_bfs(mesh, batch, fused=fused, graph=graph,
                                 n=bfs_n)
                _emit(out, row)


def inner_smoke(out, shards: int) -> bool:
    """Parity gate, run inside the forced-device subprocess."""
    import numpy as np
    from repro.apps import bfs

    mesh = _mesh(shards)
    ok = True
    print("# mesh smoke: fused-vs-legacy parity on "
          f"{shards} shards", file=out)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)

    res = {}
    for fused in (False, True):
        row, acc, st = run_fanout(mesh, 32, fused=fused, depth=6, roots=2,
                                  trials=1)
        _emit(out, row)
        res[fused] = (row, acc, st)
    row_l, acc_l, st_l = res[False]
    row_f, acc_f, st_f = res[True]
    if not (np.array_equal(acc_l, acc_f)
            and np.array_equal(acc_l, _expected_fanout_acc(2, 6, 2))):
        print("# FAIL: mesh fanout acc mismatch", file=out)
        ok = False
    planes_eq = all(np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(st_l[:4], st_f[:4]))
    heads_eq = (int(np.asarray(st_l.head)) == int(np.asarray(st_f.head))
                and int(np.asarray(st_l.tail)) == int(np.asarray(st_f.tail)))
    if not (planes_eq and heads_eq):
        print("# FAIL: mesh fanout ring state mismatch", file=out)
        ok = False
    if not (row_f["host_syncs"] == 1
            and row_l["host_syncs"] == row_l["rounds"]):
        print("# FAIL: mesh fused path did not reduce host syncs", file=out)
        ok = False

    # sharded rings: same results, per-shard carry O(ring/shards)
    row_s, acc_s, _ = run_fanout(mesh, 32, fused=True, sharded=True,
                                 depth=6, roots=2, trials=1)
    _emit(out, row_s)
    if not np.array_equal(acc_s, _expected_fanout_acc(2, 6, 2)):
        print("# FAIL: sharded mesh fanout acc mismatch", file=out)
        ok = False
    if row_s["host_syncs"] != 1:
        print("# FAIL: sharded mesh path did not reduce host syncs",
              file=out)
        ok = False
    if shards > 1 and not (row_s["carry_bytes_per_shard"]
                           < row_f["carry_bytes_per_shard"]):
        print("# FAIL: sharded rings do not shrink per-shard loop carry",
              file=out)
        ok = False

    g = bfs.road_like(256)
    ref = bfs.bfs_reference(g, 0)
    for fused in (False, True):
        row, dist = run_bfs(mesh, 32, fused=fused, n=256, trials=1)
        _emit(out, row)
        if not np.array_equal(dist, ref):
            print(f"# FAIL: mesh bfs fused={fused} distances wrong",
                  file=out)
            ok = False
    print(f"# acceptance: {'PASS' if ok else 'FAIL'}", file=out)
    return ok


# ---------------------------------------------------------------------------
# outer (CSV-relaying) side
# ---------------------------------------------------------------------------


def main(out=sys.stdout, shards: int = 2, batches=(64, 256),
         bfs_n: int = 1024) -> None:
    print("# mesh round engine: legacy per-round shard_map dispatch vs "
          "fused device-resident megarounds", file=out)
    rc = _spawn_inner(["--shards", str(shards),
                       "--batches", ",".join(map(str, batches)),
                       "--bfs-n", str(bfs_n)], out)
    if rc != 0:
        # fail loudly: a silent-empty mesh section must not masquerade as
        # a completed benchmark in the emitted trajectory
        raise RuntimeError(f"mesh benchmark subprocess exited {rc}")


def smoke(out=sys.stdout, shards: int = 2) -> bool:
    rc = _spawn_inner(["--shards", str(shards), "--smoke"], out)
    return rc == 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="run the sweep in-process (expects XLA_FLAGS set)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI parity gate (fast; asserts correctness only)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweep (CI-sized)")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--batches", default="64,256")
    ap.add_argument("--bfs-n", type=int, default=1024)
    a = ap.parse_args()
    batches = tuple(int(b) for b in a.batches.split(","))
    if a.quick:
        batches, a.bfs_n = (64,), 512
    if a.inner:
        if a.smoke:
            sys.exit(0 if inner_smoke(sys.stdout, a.shards) else 1)
        inner_main(sys.stdout, a.shards, batches, a.bfs_n)
        sys.exit(0)
    if a.smoke:
        sys.exit(0 if smoke(shards=a.shards) else 1)
    main(shards=a.shards, batches=batches, bfs_n=a.bfs_n)
