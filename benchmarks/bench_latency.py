"""Offered-load latency sweep: per-class sojourn percentiles from the
device span planes on a 2-shard priority mesh (DESIGN.md § 7.6, BENCH_7).

The span layer's payoff benchmark: where bench_obs prices the *overhead*
of span tracing, this section reads the *signal* — queue sojourn time
(enqueue → dequeue, in rounds) as offered load rises.  ``batch`` is the
load knob: each relaxed shard claims up to ``batch`` items per round, so
``offered_load = items / (rounds · batch · shards)`` is the fraction of
claim capacity the workload actually filled; the p50/p95/p99 columns are
the wait distribution the serving layer cares about and ``starved``
counts classes whose max-wait high-water blew past the starvation factor
(``obs.analyze.starvation_flags``).

Workloads (2-shard relaxed priority mesh, forced host devices):

* ``sssp_road`` — delta-stepping SSSP on the weighted road-like grid;
  span rows default to one per shard (is either shard's queue aging
  worse?).
* ``prio_tree`` — synthetic spawn tree with scrambled keys
  ``(payload · 7919) mod 256`` and ``class_of = key // 64`` (4 priority
  classes): the relaxed pop order serves low keys first, so high-key
  classes *should* wait longer — the per-class p99 gradient makes the
  fairness/ordering tradeoff visible.

Multi-device CPU meshes need ``XLA_FLAGS=--xla_force_host_platform_
device_count=N`` before jax initializes, so the sweep runs in a
subprocess (``--inner``) and the parent relays its CSV — the
bench_sssp.py pattern.  ``--smoke`` is the CI gate: span mass equals
processed items, percentiles are ordered, and the per-class rows merge
consistently across shards.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from .cpu_child import spawn_inner

HEADER = ("bench,workload,batch,shards,classes,rounds,items,elapsed_s,"
          "offered_load,p50_wait,p95_wait,p99_wait,max_wait,worst_class,"
          "starved,dropped_flows")


def _spawn_inner(args, out) -> int:
    """Run this module's inner half on the requested number of
    virtual CPU devices; relay its stdout into ``out``."""
    return spawn_inner("benchmarks.bench_latency", args, out,
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


def run_sssp_spans(mesh, batch: int, *, n: int = 512, delta: int = 4):
    """One instrumented relaxed-mesh SSSP run; span rows = shards.
    Returns (row, spans, stats)."""
    from repro.apps import bfs, sssp
    from repro.obs import Spans

    shards = int(mesh.shape["data"])
    g = bfs.road_like(n)
    w = sssp.with_weights(g, max_w=8, seed=1)
    sp = Spans(classes=shards, engine="sssp_mesh")
    runner, init_fn = sssp.sssp_mesh_rounds_runner(
        g, w, mesh=mesh, batch=batch, delta=delta, relaxed=True,
        fused=True, spans=sp)
    runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)   # warmup
    sp.reset()
    t0 = time.perf_counter()
    runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)
    el = time.perf_counter() - t0
    return (_row("sssp_road", batch, shards, sp, runner.stats, el),
            sp, dict(runner.stats))


def run_prio_tree_spans(mesh, batch: int, *, limit: int = 256,
                        roots: int = 4):
    """One instrumented relaxed priority-mesh run over a synthetic spawn
    tree with 4 key-derived priority classes.  Returns (row, spans,
    stats)."""
    import jax.numpy as jnp
    from repro.obs import Spans
    from repro.runtime import PriorityMeshRoundRunner

    shards = int(mesh.shape["data"])

    def tree_step(acc, keys, vals, valid):
        del keys
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        ck = (cv * 7919) % 256
        cm = (valid & (vals < limit))[:, None]
        return acc, ck, cv, cm

    sp = Spans(classes=4, engine="prio_mesh",
               class_of=lambda k: k // 64)
    runner = PriorityMeshRoundRunner(
        tree_step, mesh=mesh, capacity_log2=12, batch=batch, relaxed=True,
        fused=True, combine=lambda a: a.sum(0), spans=sp)
    seeds = [((v * 7919) % 256, v) for v in range(1, roots + 1)]
    acc0 = lambda: jnp.zeros(2 * limit + 8, jnp.int32)  # noqa: E731
    runner.run([k for k, _ in seeds], [v for _, v in seeds], acc=acc0())
    sp.reset()
    t0 = time.perf_counter()
    runner.run([k for k, _ in seeds], [v for _, v in seeds], acc=acc0())
    el = time.perf_counter() - t0
    return (_row("prio_tree", batch, shards, sp, runner.stats, el),
            sp, dict(runner.stats))


def _row(workload: str, batch: int, shards: int, sp, stats: dict,
         elapsed: float) -> dict:
    from repro.obs import max_wait_highwater, starvation_flags
    rounds, items = stats["rounds"], stats["processed"]
    summ = sp.summary()
    hw = max_wait_highwater(summ)
    flags = starvation_flags(summ)
    return {
        "workload": workload, "batch": batch, "shards": shards,
        "classes": summ["classes"], "rounds": rounds, "items": items,
        "elapsed_s": round(elapsed, 4),
        "offered_load": round(items / max(rounds * batch * shards, 1), 4),
        "p50_wait": summ["p50"], "p95_wait": summ["p95"],
        "p99_wait": summ["p99"], "max_wait": hw["high_water"],
        "worst_class": hw["worst_class"],
        "starved": len(flags["starved_classes"]),
        "dropped_flows": sp.dropped_flows,
    }


def _emit(out, row: dict) -> None:
    cells = [row[k] for k in HEADER.split(",")[1:]]
    print("latency," + ",".join("" if c is None else str(c)
                                for c in cells), file=out)


def inner_main(out, shards: int, batches, n: int) -> None:
    mesh = _mesh(shards)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)
    for batch in batches:
        row_s, _, _ = run_sssp_spans(mesh, batch, n=n)
        _emit(out, row_s)
        row_p, _, _ = run_prio_tree_spans(mesh, batch)
        _emit(out, row_p)
        print(f"# batch={batch}: sssp p99 wait {row_s['p99_wait']} rounds "
              f"@ load {row_s['offered_load']}, prio_tree p99 "
              f"{row_p['p99_wait']} @ load {row_p['offered_load']} "
              f"(worst class {row_p['worst_class']})", file=out)


def inner_smoke(out, shards: int) -> bool:
    """CI gate: span mass == processed items, ordered percentiles, and a
    populated per-class histogram on both workloads."""
    from repro.obs import bucket_edges, bucket_of
    mesh = _mesh(shards)
    ok = True
    print(f"# latency smoke: span-mass parity + ordered percentiles on "
          f"{shards} shards", file=out)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)
    for name, fn in (("sssp_road", lambda: run_sssp_spans(mesh, 32, n=256)),
                     ("prio_tree", lambda: run_prio_tree_spans(
                         mesh, 32, limit=128))):
        row, sp, stats = fn()
        _emit(out, row)
        if sp.total != stats["processed"]:
            print(f"# FAIL: {name} span mass {sp.total} != processed "
                  f"{stats['processed']}", file=out)
            ok = False
        ps = [row["p50_wait"], row["p95_wait"], row["p99_wait"]]
        known = [p for p in ps if p is not None]
        if not known or known != sorted(known):
            print(f"# FAIL: {name} percentiles missing or unordered: {ps}",
                  file=out)
            ok = False
        # p99 is a bucket *upper edge* while max_wait is exact, so compare
        # at bucket granularity: p99's edge cannot exceed the edge of the
        # bucket holding the true maximum
        nb = sp.buckets
        if (row["p99_wait"] is not None
                and row["p99_wait"]
                > int(bucket_edges(nb)[bucket_of(row["max_wait"], nb)])):
            print(f"# FAIL: {name} p99 {row['p99_wait']} beyond max_wait "
                  f"{row['max_wait']}'s bucket", file=out)
            ok = False
    print(f"# acceptance: {'PASS' if ok else 'FAIL'}", file=out)
    return ok


# ---------------------------------------------------------------------------
# outer (CSV-relaying) side
# ---------------------------------------------------------------------------


def main(out=sys.stdout, shards: int = 2, batches=(16, 64, 256),
         n: int = 512) -> None:
    print("# offered-load latency sweep: device span histograms on the "
          "2-shard priority mesh", file=out)
    rc = _spawn_inner(["--shards", str(shards),
                       "--batches", ",".join(map(str, batches)),
                       "--n", str(n)], out)
    if rc != 0:
        raise RuntimeError(f"latency benchmark subprocess exited {rc}")


def smoke(out=sys.stdout, shards: int = 2) -> bool:
    rc = _spawn_inner(["--shards", str(shards), "--smoke"], out)
    return rc == 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="run the sweep in-process (expects XLA_FLAGS set)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI correctness gate (no timing assertion)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweep (CI-sized)")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--batches", default="16,64,256")
    ap.add_argument("--n", type=int, default=512)
    a = ap.parse_args()
    batches = tuple(int(b) for b in a.batches.split(","))
    if a.quick:
        batches, a.n = (64,), 256
    if a.inner:
        if a.smoke:
            sys.exit(0 if inner_smoke(sys.stdout, a.shards) else 1)
        inner_main(sys.stdout, a.shards, batches, a.n)
        sys.exit(0)
    if a.smoke:
        sys.exit(0 if smoke(shards=a.shards) else 1)
    main(shards=a.shards, batches=batches, n=a.n)
