"""Telemetry overhead benchmark: fused engines with trace planes on vs
off (DESIGN.md § 7.5, BENCH_6 "obs" section).

The trace plane rides the megaround loop as extra carry — a handful of
masked ``at[slot].set`` scatters per round, zero extra collectives, zero
extra host syncs.  This benchmark prices that: each workload runs the
*same* fused runner twice (``telemetry=None`` vs a live ``Telemetry``),
trials interleaved and the per-side minimum reported, and the ``on`` row carries
``overhead_pct`` = the rounds/s cost of recording.  The acceptance gate
(ISSUE 6) is < 5% on ``fanout`` @ batch 64 — the round-dispatch-bound
regime where per-round overhead is most visible.

Workloads:

* ``fanout``    — geometric spawn tree on the chip ``FusedRounds`` engine
  (bench_rounds's workload; shortest rounds, worst case for per-round
  recording cost).
* ``bfs_road``  — road-grid BFS on ``FusedRounds`` (real claim traffic).
* ``sssp_road`` — delta-stepping SSSP on the relaxed priority mesh at one
  shard (the widened 4-word psum meta path, in-process — multi-shard
  overhead is covered by the ``--trace`` emitter's 2-shard run).

Also home to the ``run.py --trace`` emitter (:func:`trace_main`): a
forced-2-device subprocess runs one mesh SSSP with telemetry on, drains
the planes, measures rank error against the declared
``mesh_relaxation_bound`` envelope (exact history from a legacy traced
run + the fused plane's inversion proxy), and writes the JSONL + Chrome
trace files ``tools/trace_check.py`` validates.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from .cpu_child import REPO, cpu_child_env

HEADER = ("bench,workload,batch,telemetry,rounds,items,elapsed_s,"
          "rounds_per_s,items_per_s,overhead_pct,records,dropped")
TRIALS = 30     # interleaved on/off; the estimator is the MIN over trials,
                # not the median: shared-host interference is one-sided (it
                # only ever adds time), so the fastest interleaved trial is
                # the highest-fidelity estimate of intrinsic per-run cost —
                # medians on this class of box scatter by ±10pp run-to-run,
                # and 15 draws left the span-overhead pct with ±5pp scatter
                # (the <5% gate needs ~1-2pp resolution, hence 30)
CAPACITY = 1024   # the Telemetry default; covers every workload's round
                  # count here with headroom (in-loop carry cost scales
                  # with plane capacity — benchmark what users get)


def _row(workload: str, batch: int, tel_on, stats: dict,
         elapsed: float, *, overhead_pct=None, records=0,
         dropped=0) -> dict:
    rounds, items = stats["rounds"], stats["processed"]
    return {
        "workload": workload, "batch": batch,
        "telemetry": (tel_on if isinstance(tel_on, str)
                      else ("on" if tel_on else "off")),
        "rounds": rounds, "items": items,
        "elapsed_s": round(elapsed, 4),
        "rounds_per_s": round(rounds / max(elapsed, 1e-9), 1),
        "items_per_s": round(items / max(elapsed, 1e-9), 1),
        # baseline rows carry no overhead measurement: emit JSON null, not
        # "" — trace_check/bench_compare reject empty-string numerics
        "overhead_pct": (None if overhead_pct is None
                         else round(overhead_pct, 2)),
        "records": records, "dropped": dropped,
    }


def _emit(out, row: dict) -> None:
    ov = "" if row["overhead_pct"] is None else row["overhead_pct"]
    print(f"obs,{row['workload']},{row['batch']},{row['telemetry']},"
          f"{row['rounds']},{row['items']},{row['elapsed_s']},"
          f"{row['rounds_per_s']},{row['items_per_s']},"
          f"{ov},{row['records']},{row['dropped']}",
          file=out)


def _measure_pair(make_runner, run_once, batch: int, workload: str,
                  trials: int = TRIALS):
    """Min-of-interleaved-trials for telemetry off vs on (see TRIALS note).
    Both runners are built from the same factory and warmed before timing;
    the ``on`` telemetry is reset per trial so drain cost (the real
    per-sync price) is inside the timed region but record accumulation
    across trials is not."""
    from repro.obs import Telemetry

    tel = Telemetry(CAPACITY, engine=workload)
    runners = {False: make_runner(None), True: make_runner(tel)}
    for r in runners.values():
        run_once(r)                               # warmup/compile
    times = {False: [], True: []}
    stats = {}
    for _ in range(trials):
        for tel_on, runner in runners.items():
            if tel_on:
                tel.reset()
            t0 = time.perf_counter()
            run_once(runner)
            times[tel_on].append(time.perf_counter() - t0)
            stats[tel_on] = dict(runner.stats)
    assert stats[True] == stats[False], (
        f"{workload}: telemetry changed engine stats")
    med = {k: min(v) for k, v in times.items()}
    rps = {k: stats[k]["rounds"] / max(med[k], 1e-9) for k in med}
    overhead = (rps[False] - rps[True]) / max(rps[False], 1e-9) * 100
    assert len(tel.records) + tel.dropped == stats[True]["rounds"], (
        f"{workload}: plane lost rounds")
    return (_row(workload, batch, False, stats[False], med[False]),
            _row(workload, batch, True, stats[True], med[True],
                 overhead_pct=overhead, records=len(tel.records),
                 dropped=tel.dropped))


def _measure_span_pair(make_runner, run_once, batch: int, workload: str,
                       trials: int = TRIALS):
    """Span-layer twin of :func:`_measure_pair`: spans off vs on with the
    same min-of-interleaved-trials estimator.  The ``on`` row's
    ``records`` is the histogram mass (one count per claimed task) and
    ``dropped`` counts flow-ring overwrites (sampling, never an error)."""
    from repro.obs.spans import Spans

    sp = Spans(classes=1, engine=workload)
    runners = {False: make_runner(None), True: make_runner(sp)}
    for r in runners.values():
        run_once(r)                               # warmup/compile
    times = {False: [], True: []}
    stats = {}
    for _ in range(trials):
        for sp_on, runner in runners.items():
            if sp_on:
                sp.reset()
            t0 = time.perf_counter()
            run_once(runner)
            times[sp_on].append(time.perf_counter() - t0)
            stats[sp_on] = dict(runner.stats)
    assert stats[True] == stats[False], (
        f"{workload}: spans changed engine stats")
    best = {k: min(v) for k, v in times.items()}
    rps = {k: stats[k]["rounds"] / max(best[k], 1e-9) for k in best}
    overhead = (rps[False] - rps[True]) / max(rps[False], 1e-9) * 100
    assert sp.total == stats[True]["processed"], (
        f"{workload}: span histogram lost tasks "
        f"({sp.total} != {stats[True]['processed']})")
    return (_row(workload, batch, "span-off", stats[False], best[False]),
            _row(workload, batch, "span-on", stats[True], best[True],
                 overhead_pct=overhead, records=sp.total,
                 dropped=sp.dropped_flows))


def run_fanout_span_pair(batch: int, *, depth: int = 10, roots: int = 4,
                         trials: int = TRIALS):
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime import RoundRunner
    from .bench_rounds import _fanout_step

    peak = roots * 2 ** depth
    capacity_log2 = max(int(np.ceil(np.log2(2 * peak))),
                        int(np.ceil(np.log2(2 * batch))))
    seeds = np.full(roots, depth, np.int32)
    acc0 = jnp.zeros(depth + 1, jnp.int32)

    def make(sp):
        return RoundRunner(_fanout_step(2, depth),
                           capacity_log2=capacity_log2, batch=batch,
                           spans=sp)

    return _measure_span_pair(
        make, lambda r: r.run(seeds, acc=acc0, max_rounds=1_000_000),
        batch, "fanout_spans", trials)


def run_fanout_pair(batch: int, *, depth: int = 10, roots: int = 4,
                    trials: int = TRIALS):
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime import RoundRunner
    from .bench_rounds import _fanout_step

    peak = roots * 2 ** depth
    capacity_log2 = max(int(np.ceil(np.log2(2 * peak))),
                        int(np.ceil(np.log2(2 * batch))))
    seeds = np.full(roots, depth, np.int32)
    acc0 = jnp.zeros(depth + 1, jnp.int32)

    def make(tel):
        return RoundRunner(_fanout_step(2, depth),
                           capacity_log2=capacity_log2, batch=batch,
                           telemetry=tel)

    return _measure_pair(
        make, lambda r: r.run(seeds, acc=acc0, max_rounds=1_000_000),
        batch, "fanout", trials)


def run_bfs_pair(batch: int, *, n: int = 4096, trials: int = TRIALS):
    from repro.apps import bfs

    g = bfs.road_like(n)
    init = {}

    def make(tel):
        runner, init_fn = bfs.bfs_rounds_runner(g, batch=batch,
                                                telemetry=tel)
        init["fn"] = init_fn
        return runner

    return _measure_pair(
        make, lambda r: r.run([0], acc=init["fn"](0), max_rounds=1_000_000),
        batch, "bfs_road", trials)


def run_sssp_pair(batch: int, *, n: int = 1024, delta: int = 4,
                  trials: int = TRIALS):
    from repro.apps import bfs, sssp
    from repro.jaxcompat import make_mesh

    g = bfs.road_like(n)
    w = sssp.with_weights(g, max_w=8, seed=1)
    mesh = make_mesh((1,), ("data",))
    init = {}

    def make(tel):
        runner, init_fn = sssp.sssp_mesh_rounds_runner(
            g, w, mesh=mesh, batch=batch, delta=delta, telemetry=tel)
        init["fn"] = init_fn
        return runner

    return _measure_pair(
        make,
        lambda r: r.run([0], [0], acc=init["fn"](0), max_rounds=1_000_000),
        batch, "sssp_road", trials)


def main(out=sys.stdout, batches=(64, 256), fanout_depth: int = 10,
         bfs_n: int = 4096, sssp_n: int = 1024) -> list:
    """The "obs" sweep: telemetry on-vs-off across the three workloads."""
    print("# telemetry overhead: fused engines with trace planes on vs off",
          file=out)
    print(HEADER, file=out)
    rows = []
    for batch in batches:
        off, on = run_fanout_pair(batch, depth=fanout_depth)
        _emit(out, off)
        _emit(out, on)
        rows += [off, on]
        print(f"# fanout batch={batch}: telemetry costs "
              f"{on['overhead_pct']}% rounds/s "
              f"({on['records']} records, {on['dropped']} dropped)",
              file=out)
    for batch in batches:
        soff, son = run_fanout_span_pair(batch, depth=fanout_depth)
        _emit(out, soff)
        _emit(out, son)
        rows += [soff, son]
        print(f"# fanout batch={batch}: spans cost "
              f"{son['overhead_pct']}% rounds/s "
              f"({son['records']} sojourns, {son['dropped']} flow drops)",
              file=out)
    for batch in batches:
        for pair in (run_bfs_pair(batch, n=bfs_n),
                     run_sssp_pair(batch, n=sssp_n)):
            off, on = pair
            _emit(out, off)
            _emit(out, on)
            rows += [off, on]
    return rows


def smoke(out=sys.stdout) -> bool:
    """CI gate: stats identical with telemetry on/off, plane accounts for
    every round, and the trace files validate."""
    import tempfile

    from repro.obs import write_chrome_trace, write_jsonl
    from repro.obs.trace import Telemetry

    print("# obs smoke: telemetry + span parity + export validation",
          file=out)
    print(HEADER, file=out)
    off, on = run_fanout_pair(32, depth=6, trials=3)
    _emit(out, off)
    _emit(out, on)
    ok = on["rounds"] == off["rounds"] and on["records"] == on["rounds"]
    soff, son = run_fanout_span_pair(32, depth=6, trials=3)
    _emit(out, soff)
    _emit(out, son)
    ok = ok and son["rounds"] == soff["rounds"]
    ok = ok and son["records"] == son["items"]   # one sojourn per task
    # re-run one instrumented pass and validate its export end to end
    from repro.obs.spans import Spans
    from repro.runtime import RoundRunner
    import jax.numpy as jnp
    import numpy as np
    from .bench_rounds import _fanout_step
    tel = Telemetry(CAPACITY, engine="fanout")
    sp = Spans(classes=1, engine="fanout")
    r = RoundRunner(_fanout_step(2, 6), capacity_log2=8, batch=32,
                    telemetry=tel, spans=sp)
    r.run(np.full(2, 6, np.int32), acc=jnp.zeros(7, jnp.int32))
    with tempfile.TemporaryDirectory() as d:
        jl = os.path.join(d, "t.jsonl")
        ch = os.path.join(d, "t.json")
        write_jsonl(jl, tel.records, tel.sync_points,
                    metrics=tel.registry.snapshot(), engine="fanout",
                    spans=sp)
        write_chrome_trace(ch, tel.records, tel.sync_points,
                           engine="fanout", flows=sp.flows)
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_check.py"),
             jl, "--chrome", ch], capture_output=True, text=True,
            env=cpu_child_env())
        if res.returncode != 0:
            print(f"# FAIL: trace_check rejected the export: "
                  f"{res.stderr[-1000:]}", file=out)
            ok = False
    print(f"# acceptance: {'PASS' if ok else 'FAIL'}", file=out)
    return ok


# ---------------------------------------------------------------------------
# run.py --trace emitter (forced-device subprocess, bench_mesh pattern)
# ---------------------------------------------------------------------------


def trace_main(out=sys.stdout, *, trace_dir: str = ".", shards: int = 2,
               batch: int = 64, n: int = 512) -> bool:
    """Emit the PR-6 acceptance artifact: one mesh SSSP run's telemetry as
    ``trace_sssp.jsonl`` + ``trace_sssp.json`` (Chrome) under
    ``trace_dir``, validated by ``tools/trace_check.py``."""
    trace_dir = os.path.abspath(trace_dir)
    os.makedirs(trace_dir, exist_ok=True)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_obs", "--inner-trace",
         "--trace-dir", trace_dir, "--shards", str(shards),
         "--batches", str(batch), "--n", str(n)],
        capture_output=True, text=True, cwd=REPO,
        env=cpu_child_env(shards), timeout=1800)
    print(proc.stdout, end="", file=out)
    if proc.returncode != 0:
        print(f"# FAIL: trace subprocess exited {proc.returncode}: "
              f"{proc.stderr[-2000:]}", file=out)
        return False
    jl = os.path.join(trace_dir, "trace_sssp.jsonl")
    ch = os.path.join(trace_dir, "trace_sssp.json")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_check.py"),
         jl, "--chrome", ch], capture_output=True, text=True,
        env=cpu_child_env())
    print(f"# {res.stdout.strip()}", file=out)
    if res.returncode != 0:
        print(f"# FAIL: emitted trace is schema-invalid: "
              f"{res.stderr[-2000:]}", file=out)
        return False
    return True


def inner_trace(out, trace_dir: str, shards: int, batch: int,
                n: int) -> None:
    """Subprocess side of :func:`trace_main` (expects XLA_FLAGS set)."""
    import numpy as np

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "src"))
    import jax
    assert len(jax.devices()) >= shards, (
        f"need {shards} devices, have {len(jax.devices())}")
    from repro.apps import bfs, sssp
    from repro.jaxcompat import make_mesh
    from repro.obs import (Telemetry, rank_error_vs_envelope, write_jsonl,
                           write_chrome_trace)
    from repro.sched import mesh_relaxation_bound

    mesh = make_mesh((shards,), ("data",))
    g = bfs.road_like(n)
    w = sssp.with_weights(g, max_w=8, seed=1)

    # fused run with the trace plane: per-round occupancy / imbalance /
    # key extrema drained at quiescence
    tel = Telemetry(CAPACITY, engine="sssp_mesh")
    runner, init_fn = sssp.sssp_mesh_rounds_runner(
        g, w, mesh=mesh, batch=batch, telemetry=tel)
    dist, _ = runner.run([0], [0], acc=init_fn(0), max_rounds=1_000_000)
    ref = sssp.dijkstra_reference(g, w, 0)
    exact = bool(np.array_equal(np.asarray(dist), ref))

    # legacy traced run: the exact per-pop history for measured rank error
    lruner, linit = sssp.sssp_mesh_rounds_runner(
        g, w, mesh=mesh, batch=batch, fused=False, trace=True)
    lruner.run([0], [0], acc=linit(0), max_rounds=1_000_000)
    history, inserts = [], []
    for rec in lruner.trace:
        pk, _, ok = rec["pops"]
        history.append([int(k) for k, o in
                        zip(pk.reshape(-1), ok.reshape(-1)) if o])
        gk, _, ga = rec["pushes"]
        inserts.append([int(k) for k, a in
                        zip(gk.reshape(-1), ga.reshape(-1)) if a])
    env = mesh_relaxation_bound(shards, batch,
                                lruner.stats["max_occupancy"])
    rank = rank_error_vs_envelope(env, history=history, inserts=inserts,
                                  records=tel.records)

    meta = {"workload": "sssp_road", "shards": shards, "batch": batch,
            "n": g.n, "exact_distances": exact, "rank_error": rank,
            "stats": dict(runner.stats)}
    jl = os.path.join(trace_dir, "trace_sssp.jsonl")
    ch = os.path.join(trace_dir, "trace_sssp.json")
    nl = write_jsonl(jl, tel.records, tel.sync_points,
                     metrics=tel.registry.snapshot(), engine="sssp_mesh",
                     extra_meta=meta)
    ne = write_chrome_trace(ch, tel.records, tel.sync_points,
                            engine="sssp_mesh")
    print(f"# trace: {nl} jsonl lines -> {jl}", file=out)
    print(f"# trace: {ne} chrome events -> {ch}", file=out)
    print(f"# rank error: measured {rank['measured_rank_error']} vs "
          f"declared envelope {rank['envelope']} "
          f"(within={rank['within_envelope']}, "
          f"inversions={rank['key_inversions']}); "
          f"exact_distances={exact}", file=out)
    if not exact or not rank["within_envelope"]:
        raise SystemExit("trace run violated correctness/envelope")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--batches", default="64,256")
    ap.add_argument("--trace", action="store_true",
                    help="emit the validated SSSP trace artifact")
    ap.add_argument("--inner-trace", action="store_true")
    ap.add_argument("--trace-dir", default=".")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--n", type=int, default=512)
    a = ap.parse_args()
    batches = tuple(int(b) for b in a.batches.split(","))
    if a.inner_trace:
        inner_trace(sys.stdout, a.trace_dir, a.shards, batches[0], a.n)
        sys.exit(0)
    if a.trace:
        sys.exit(0 if trace_main(trace_dir=a.trace_dir, shards=a.shards,
                                 batch=batches[0], n=a.n) else 1)
    if a.smoke:
        sys.exit(0 if smoke() else 1)
    if a.quick:
        main(batches=(64,), fanout_depth=8, bfs_n=1024, sssp_n=512)
    else:
        main(batches=batches)
