"""Priority-mesh SSSP benchmark: legacy host-driven per-round dispatch vs
the fused device-resident priority megaround, and strict (replicated-heap
exact order) vs k-relaxed (per-shard heaps, hint-ordered rebalance) pop
ordering (DESIGN.md § 6, BENCH_5).

Workloads (≥2 shards of a forced-host-device CPU mesh):

* ``sssp_road`` — delta-stepping on a weighted road-like grid (long
  diameter → many short rounds: the per-round host-sync regime the fused
  engine removes).
* ``sssp_delaunay`` — weighted constant-degree graph (wider frontiers at
  bounded fanout, so rounds stay dispatch-bound and the strict mode's
  full-width replicated waves are visibly costlier than the relaxed
  mode's local ``batch``-wide waves).

Power-law (kron) graphs remain selectable (``--graphs road,kron``) but
are excluded from the default sweep: their max degree makes the publish
wave ``batch × max_fanout`` wide, so rounds are seconds of heap-scan
compute that both engines pay equally — the § 4.3 / § 2.3 wide-fanout
tradeoff carried to the heap, noise-dominated rather than
dispatch-dominated.  The default sweep stays in the dispatch-bound
regime for the same reason: at ``batch ≥ 256`` the strict mode's
``shards·batch``-wide heap waves stretch rounds to tens of ms, the
per-round dispatch the fused engine removes drops under the host's
timing noise (~±5% here), and the comparison measures the machine, not
the engines.  ``--batches 64,256`` reproduces the wide-batch tier.

Multi-device CPU meshes need ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` set *before* jax initializes, so the sweep runs in a
subprocess (``--inner``) and the parent relays its CSV — the
bench_mesh.py pattern.  Timings are the median of ``TRIALS`` interleaved
legacy/fused runs after a compilation warmup (``run_pair``).

``--smoke`` is the CI acceptance gate: fused/legacy bit-parity (labels +
stats) for both orderings, exact distances vs the Dijkstra oracle, and
the recorded 2-shard pop history held to the declared
``mesh_relaxation_bound`` envelope by the ``plinearizability`` checker —
correctness only, no speedup assertion (CI timing noise).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from .cpu_child import spawn_inner

HEADER = ("bench,workload,batch,shards,order,mode,delta,rounds,items,"
          "elapsed_s,rounds_per_s,items_per_s,host_syncs,drained")
TRIALS = 15   # paired best-of-15: the shared-runner noise on oversubscribed
              # CPU devices is several percent, so trials interleave the
              # two modes (run_pair) and the default sweep sizes the graphs
              # for the dispatch-bound regime the fused engine targets


def _spawn_inner(args, out) -> int:
    """Run this module's inner half on the requested number of
    virtual CPU devices; relay its stdout into ``out``."""
    return spawn_inner("benchmarks.bench_sssp", args, out,
                       devices=int(args[args.index('--shards') + 1]))


# ---------------------------------------------------------------------------
# inner (subprocess) side — jax only imported here
# ---------------------------------------------------------------------------


def _mesh(shards: int):
    import jax
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))
    from repro.jaxcompat import make_mesh
    assert len(jax.devices()) >= shards, (
        f"need {shards} devices, have {len(jax.devices())} "
        f"(XLA_FLAGS not set before jax init?)")
    return make_mesh((shards,), ("data",))


def _graph(kind: str, n: int):
    from repro.apps import bfs, sssp
    if kind == "road":
        g = bfs.road_like(n)
    elif kind == "delaunay":
        g = bfs.delaunay_like(n, deg=6, seed=1)
    elif kind == "kron":
        g = bfs.kron_like(n, avg_deg=4, seed=1)
    else:
        raise ValueError(f"unknown graph kind {kind!r} (road|delaunay|kron)")
    return g, sssp.with_weights(g, max_w=8, seed=1)


def run_sssp(mesh, batch: int, *, relaxed: bool, fused: bool,
             graph: str = "road", n: int = 1024, delta: int = 4,
             trials: int = TRIALS):
    """Best-of-``trials`` timed SSSP run (post-warmup).  Returns
    (row dict, dist, stats)."""
    import numpy as np
    from repro.apps import sssp

    g, w = _graph(graph, n)
    runner, init_fn = sssp.sssp_mesh_rounds_runner(
        g, w, mesh=mesh, batch=batch, delta=delta, relaxed=relaxed,
        fused=fused)
    runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)   # warmup
    best, dist = None, None
    for _ in range(trials):
        t0 = time.perf_counter()
        dist, _ = runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)
        el = time.perf_counter() - t0
        best = el if best is None else min(best, el)
    row = _row(f"sssp_{graph}", batch, int(mesh.shape["data"]), relaxed,
               fused, delta, runner.stats, best)
    return row, np.asarray(dist), dict(runner.stats)


def run_pair(mesh, batch: int, *, relaxed: bool, graph: str = "road",
             n: int = 1024, delta: int = 4, trials: int = TRIALS):
    """Paired legacy/fused measurement: both runners are warmed, then the
    trials *interleave* the two modes, so a background-load burst on an
    oversubscribed CPU host lands on both sides instead of skewing one
    mode's whole window.  Rows report the *median* trial — the typical
    per-round dispatch cost is the quantity under comparison, and best-of
    would instead reward the legacy path's luckiest dispatch timing while
    a robust median keeps outlier bursts out of both sides.  Returns
    {"legacy": row, "fused": row}."""
    import statistics

    from repro.apps import sssp

    g, w = _graph(graph, n)
    runners = {}
    for fused in (False, True):
        runner, init_fn = sssp.sssp_mesh_rounds_runner(
            g, w, mesh=mesh, batch=batch, delta=delta, relaxed=relaxed,
            fused=fused)
        runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)  # warmup
        runners["fused" if fused else "legacy"] = (runner, init_fn)
    times = {"legacy": [], "fused": []}
    stats = {}
    for _ in range(trials):
        for mode, (runner, init_fn) in runners.items():
            t0 = time.perf_counter()
            runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)
            times[mode].append(time.perf_counter() - t0)
            stats[mode] = dict(runner.stats)
    shards = int(mesh.shape["data"])
    return {mode: _row(f"sssp_{graph}", batch, shards, relaxed,
                       mode == "fused", delta, stats[mode],
                       statistics.median(times[mode]))
            for mode in ("legacy", "fused")}


def _row(workload: str, batch: int, shards: int, relaxed: bool, fused: bool,
         delta: int, stats: dict, elapsed: float) -> dict:
    rounds, items = stats["rounds"], stats["processed"]
    return {
        "workload": workload, "batch": batch, "shards": shards,
        "order": "relaxed" if relaxed else "strict",
        "mode": "fused" if fused else "legacy", "delta": delta,
        "rounds": rounds, "items": items,
        "elapsed_s": round(elapsed, 4),
        "rounds_per_s": round(rounds / max(elapsed, 1e-9), 1),
        "items_per_s": round(items / max(elapsed, 1e-9), 1),
        "host_syncs": stats["host_syncs"], "drained": stats["drained"],
    }


def _emit(out, row: dict) -> None:
    print(f"sssp,{row['workload']},{row['batch']},{row['shards']},"
          f"{row['order']},{row['mode']},{row['delta']},{row['rounds']},"
          f"{row['items']},{row['elapsed_s']},{row['rounds_per_s']},"
          f"{row['items_per_s']},{row['host_syncs']},{row['drained']}",
          file=out)


def inner_main(out, shards: int, batches, n: int,
               graphs=("road", "delaunay")) -> None:
    mesh = _mesh(shards)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)
    for graph in graphs:
        for batch in batches:
            for relaxed in (False, True):
                by_mode = run_pair(mesh, batch, relaxed=relaxed,
                                   graph=graph, n=n)
                _emit(out, by_mode["legacy"])
                _emit(out, by_mode["fused"])
                speedup = (by_mode["fused"]["rounds_per_s"]
                           / max(by_mode["legacy"]["rounds_per_s"], 1e-9))
                print(f"# sssp {graph} batch={batch} shards={shards} "
                      f"order={by_mode['fused']['order']}: fused "
                      f"{speedup:.1f}x rounds/s, host_syncs "
                      f"{by_mode['legacy']['host_syncs']} -> "
                      f"{by_mode['fused']['host_syncs']}", file=out)


def inner_smoke(out, shards: int) -> bool:
    """Correctness gate, run inside the forced-device subprocess."""
    import jax.numpy as jnp
    import numpy as np
    from repro.apps import sssp
    from repro.runtime import PriorityMeshRoundRunner
    from repro.sched import (check_p_linearizable, mesh_relaxation_bound,
                             mesh_trace_history)

    mesh = _mesh(shards)
    ok = True
    print(f"# sssp smoke: fused-vs-legacy parity + Dijkstra exactness + "
          f"relaxation envelope on {shards} shards", file=out)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)

    g, w = _graph("road", 256)
    ref = sssp.dijkstra_reference(g, w, 0)
    for relaxed in (False, True):
        res = {}
        for fused in (False, True):
            row, dist, stats = run_sssp(mesh, 32, relaxed=relaxed,
                                        fused=fused, n=256, trials=1)
            _emit(out, row)
            res[fused] = (row, dist, stats)
        row_l, dist_l, st_l = res[False]
        row_f, dist_f, st_f = res[True]
        order = row_f["order"]
        if not np.array_equal(dist_l, dist_f):
            print(f"# FAIL: sssp {order} fused/legacy labels differ",
                  file=out)
            ok = False
        if not np.array_equal(dist_f, ref):
            print(f"# FAIL: sssp {order} distances != Dijkstra", file=out)
            ok = False
        for k in ("rounds", "processed", "spawned", "max_occupancy",
                  "drained"):
            if st_l[k] != st_f[k]:
                print(f"# FAIL: sssp {order} stat {k} mismatch", file=out)
                ok = False
        if not (row_f["host_syncs"] == 1
                and row_l["host_syncs"] == row_l["rounds"]):
            print(f"# FAIL: sssp {order} fused path did not reduce host "
                  f"syncs", file=out)
            ok = False

    # the k-relaxed bound check: record a spawn-tree pop history (unique
    # payload idents) and hold it to the declared mesh envelope
    def tree_step(acc, keys, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        ck = (cv * 7919) % 1000
        cm = (valid & (vals < 128))[:, None]
        return acc, ck, cv, cm

    batch = 8
    runner = PriorityMeshRoundRunner(tree_step, mesh=mesh, capacity_log2=10,
                                     batch=batch, relaxed=True, fused=False,
                                     trace=True, combine=lambda a: a.sum(0))
    seeds = [(7919 % 1000, 1)]
    acc, _ = runner.run([k for k, _ in seeds], [v for _, v in seeds],
                        acc=jnp.zeros(260, jnp.int32))
    if np.asarray(acc)[1:256].tolist() != [1] * 255:
        print("# FAIL: spawn-tree tasks not exactly-once", file=out)
        ok = False
    hist = mesh_trace_history(runner.trace, seeds)
    k_env = mesh_relaxation_bound(shards, batch,
                                  runner.stats["max_occupancy"])
    res = check_p_linearizable(hist, k_env)
    if not res.ok:
        print(f"# FAIL: pop history violates the declared relaxation "
              f"envelope k={k_env}: {res.reason}", file=out)
        ok = False
    else:
        print(f"# relaxation envelope holds: {len(hist)} events "
              f"p-linearizable at declared k={k_env} "
              f"(shards={shards}, batch={batch}, "
              f"max_occ={runner.stats['max_occupancy']})", file=out)
    print(f"# acceptance: {'PASS' if ok else 'FAIL'}", file=out)
    return ok


# ---------------------------------------------------------------------------
# outer (CSV-relaying) side
# ---------------------------------------------------------------------------


def main(out=sys.stdout, shards: int = 2, batches=(64,),
         n: int = 512, graphs=("road", "delaunay")) -> None:
    print("# priority-mesh SSSP: legacy per-round dispatch vs fused "
          "megarounds, strict vs k-relaxed pop order", file=out)
    rc = _spawn_inner(["--shards", str(shards),
                       "--batches", ",".join(map(str, batches)),
                       "--n", str(n), "--graphs", ",".join(graphs)], out)
    if rc != 0:
        # fail loudly: a silent-empty sssp section must not masquerade as
        # a completed benchmark in the emitted trajectory
        raise RuntimeError(f"sssp benchmark subprocess exited {rc}")


def smoke(out=sys.stdout, shards: int = 2) -> bool:
    rc = _spawn_inner(["--shards", str(shards), "--smoke"], out)
    return rc == 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="run the sweep in-process (expects XLA_FLAGS set)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI correctness gate (fast; no speedup assertion)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweep (CI-sized)")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--batches", default="64")
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--graphs", default="road,delaunay",
                    help="comma list of road|delaunay|kron")
    a = ap.parse_args()
    batches = tuple(int(b) for b in a.batches.split(","))
    graphs = tuple(g for g in a.graphs.split(",") if g)
    if a.quick:
        batches, a.n = (64,), 512
    if a.inner:
        if a.smoke:
            sys.exit(0 if inner_smoke(sys.stdout, a.shards) else 1)
        inner_main(sys.stdout, a.shards, batches, a.n, graphs)
        sys.exit(0)
    if a.smoke:
        sys.exit(0 if smoke(shards=a.shards) else 1)
    main(shards=a.shards, batches=batches, n=a.n, graphs=graphs)
