"""Open-loop serving harness: goodput and tail latency vs offered load
(DESIGN.md § 5.5, BENCH_10).

Replays ``repro.serving.traffic``'s bursty power-law arrival traces
through the full ``ServingEngine`` twice per offered-load point — once
with the host-pool EDF admission path and once with device-resident
admission (``ServingMeshEngine`` megarounds) — and reports, per tenant:

* **goodput** — completions within ``slo_ticks`` of submit, per arrival
  tick (the paper-style saturation curve: past the knee, offered load
  rises while goodput flattens);
* **p50/p99 latency** — submit→finish sojourn in engine ticks (the tail
  the EDF aging guarantee protects);
* **ticks_per_s** — wall-clock tick rate, min-of-interleaved-trials (the
  bench-noise discipline: trials interleave across modes so drift hits
  both equally, and the minimum elapsed time is the gate).

The tick clock is logical, so admitted sets, goodput, and latency are
deterministic given a trace — the runs are replayed per trial only to
time them, and the harness asserts the replays agree bit-for-bit.

Multi-device CPU meshes need ``XLA_FLAGS`` set before jax initializes,
so everything runs in a forced-2-device subprocess (``--inner``), the
bench_latency pattern.  ``--smoke`` is the CI gate: host and 1-shard
device admission agree exactly; 2-shard device admission conserves
requests and its relaxed pop order stays inside
``sched.mesh_relaxation_bound``; and the serving telemetry trace
round-trips ``tools/trace_check.py`` cleanly.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import tempfile
import time

from .cpu_child import REPO, cpu_child_env, spawn_inner

HEADER = ("bench,mode,shards,rate,offered_load,tenants,tenant,submitted,"
          "admitted,completed,goodput,slo_ticks,p50_lat,p99_lat,ticks,"
          "elapsed_s,ticks_per_s")


def _spawn_inner(args, out) -> int:
    return spawn_inner("benchmarks.bench_serving", args, out, devices=2)


# ---------------------------------------------------------------------------
# inner (subprocess) side — jax only imported here
# ---------------------------------------------------------------------------

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        from repro.configs import get_config
        from repro.models import init_params
        cfg = get_config("h2o-danube-1.8b").reduced()
        _MODEL = (cfg, init_params(cfg))
    return _MODEL


def run_serving(mode: str, tc, *, shards: int = 1, max_extra: int = 400):
    """Replay one traffic trace through the engine; returns the metrics
    dict the rows are cut from.  ``mode`` is ``host`` (EDF pool) or
    ``device`` (mesh admission at ``shards``)."""
    import numpy as np

    from repro.serving import (EngineConfig, Request, ServingEngine,
                               generate_trace)
    cfg, params = _model()
    ecfg = EngineConfig(
        max_slots=4, page_size=8, num_pages=16, max_seq=64,
        request_ring_capacity=512,
        admission="device" if mode == "device" else "edf",
        tenants=tc.tenants, device_capacity_log2=9, device_batch=8,
        device_table_log2=9, device_shards=shards)
    eng = ServingEngine(cfg, params, ecfg)
    trace = generate_trace(tc)
    reqs, by_tick = [], {}
    for rid, a in enumerate(trace):
        req = Request(rid=rid,
                      prompt=(np.arange(a.prompt_len) % 17 + 1
                              ).astype(np.int32),
                      max_new_tokens=a.max_new_tokens, priority=a.priority,
                      tenant=a.tenant)
        reqs.append(req)
        by_tick.setdefault(a.tick, []).append(req)
    t0 = time.perf_counter()
    for _ in range(tc.ticks + max_extra):
        for req in by_tick.get(eng.tick, []):
            assert eng.submit(req), "request pool sized for the trace"
        eng.step()
        if (eng.tick > tc.ticks and not any(eng.slots) and not eng.stalled
                and eng._queue_empty()):
            break
    elapsed = time.perf_counter() - t0
    per_tenant = {}
    for t in range(tc.tenants):
        sub = [r for r in reqs if r.tenant == t]
        lats = sorted(r.finish_tick - r.submit_tick for r in sub if r.done)
        good = sum(1 for d in lats if d <= tc.slo_ticks)
        per_tenant[t] = {
            "submitted": len(sub), "completed": len(lats),
            "goodput": round(good / max(1, tc.ticks), 4),
            "p50_lat": lats[len(lats) // 2] if lats else None,
            "p99_lat": lats[min(len(lats) - 1,
                                (99 * len(lats)) // 100)] if lats else None,
        }
    return {
        "mode": mode, "shards": shards, "trace_len": len(trace),
        "admitted": eng.metrics["admitted"],
        "completed": eng.metrics["completed"],
        "admission_log": list(eng.admission_log),
        "decode_steps": eng.metrics["decode_steps"],
        "goodput": round(sum(p["goodput"] for p in per_tenant.values()), 4),
        "ticks": eng.tick, "elapsed_s": elapsed, "per_tenant": per_tenant,
    }


def _emit_rows(out, res, tc, rate: float) -> None:
    base = {
        "mode": res["mode"], "shards": res["shards"], "rate": rate,
        "offered_load": round(res["trace_len"] / tc.ticks, 4),
        "tenants": tc.tenants, "slo_ticks": tc.slo_ticks,
        "ticks": res["ticks"], "elapsed_s": round(res["elapsed_s"], 4),
        "ticks_per_s": round(res["ticks"] / max(res["elapsed_s"], 1e-9), 1),
    }
    rows = [dict(base, tenant=t, **p) for t, p in res["per_tenant"].items()]
    rows.append(dict(base, tenant=-1, submitted=res["trace_len"],
                     admitted=res["admitted"], completed=res["completed"],
                     goodput=res["goodput"], p50_lat=None, p99_lat=None))
    for row in rows:
        cells = [row.get(k) for k in HEADER.split(",")[1:]]
        print("serving," + ",".join("" if c is None else str(c)
                                    for c in cells), file=out)


def _same_replay(a, b) -> bool:
    """The determinism gate: two replays of one (mode, trace) must agree
    on everything but wall time."""
    keys = ("admitted", "completed", "admission_log", "decode_steps",
            "ticks", "per_tenant")
    return all(a[k] == b[k] for k in keys)


def inner_main(out, rates, *, ticks: int, tenants: int, trials: int) -> bool:
    """The sweep: modes x offered loads x tenants, trials interleaved
    across modes, elapsed = min over trials."""
    from repro.serving import TrafficConfig
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)
    best = {}
    for trial in range(trials):
        for rate in rates:
            tc = TrafficConfig(ticks=ticks, rate=rate, tenants=tenants,
                               seed=10, prompt_len=(2, 6),
                               max_new_tokens=(1, 4), slo_ticks=ticks)
            for mode in ("host", "device"):
                res = run_serving(mode, tc)
                key = (mode, rate)
                if key not in best:
                    best[key] = (res, tc)
                else:
                    prev = best[key][0]
                    assert _same_replay(prev, res), \
                        f"nondeterministic replay for {key}"
                    if res["elapsed_s"] < prev["elapsed_s"]:
                        best[key] = (res, tc)
                print(f"# trial {trial} {mode} rate={rate}: goodput "
                      f"{res['goodput']}, {res['elapsed_s']:.2f}s", file=out)
    for (mode, rate), (res, tc) in sorted(best.items(),
                                          key=lambda kv: (kv[0][1],
                                                          kv[0][0])):
        _emit_rows(out, res, tc, rate)
    top = max(r for _, r in best)
    dev, host = best[("device", top)][0], best[("host", top)][0]
    ok = dev["goodput"] >= host["goodput"]
    print(f"# acceptance: device goodput {dev['goodput']} "
          f"{'>=' if ok else '<'} host goodput {host['goodput']} at "
          f"rate {top}: {'PASS' if ok else 'FAIL'}", file=out)
    return ok


def inner_smoke(out) -> bool:
    """CI gate: exactness at one shard, conservation + relaxation
    envelope at two, and a schema-clean serving telemetry trace."""
    import numpy as np

    from repro.jaxcompat import make_mesh
    from repro.obs import Telemetry, write_jsonl
    from repro.sched import mesh_relaxation_bound
    from repro.serving import ServingMeshEngine, TrafficConfig
    ok = True
    print("# serving smoke: host/device exactness, 2-shard envelope, "
          "trace schema", file=out)
    print(f"bench,{HEADER.split(',', 1)[1]}", file=out)

    # 1. exactness: host pool and 1-shard device admission agree on the
    # admitted requests AND their order (same EDF keys, same prefixes)
    tc = TrafficConfig(ticks=24, rate=0.5, tenants=2, seed=3,
                       prompt_len=(2, 5), max_new_tokens=(1, 3),
                       slo_ticks=24)
    host = run_serving("host", tc)
    dev = run_serving("device", tc)
    for res in (host, dev):
        _emit_rows(out, res, tc, tc.rate)
    if dev["admission_log"] != host["admission_log"]:
        print("# FAIL: 1-shard device admission order diverged from the "
              "host pool", file=out)
        ok = False
    if not (dev["completed"] == host["completed"] == dev["trace_len"]):
        print(f"# FAIL: completions {dev['completed']}/{host['completed']} "
              f"!= submitted {dev['trace_len']}", file=out)
        ok = False
    if dev["goodput"] < host["goodput"]:
        print(f"# FAIL: device goodput {dev['goodput']} < host "
              f"{host['goodput']}", file=out)
        ok = False

    # 2. two-shard envelope: pops of a single stall-free admission tick
    # must order within the declared mesh relaxation bound, and every
    # request is admitted exactly once (conservation)
    eng = ServingMeshEngine(mesh=make_mesh((2,), ("data",)),
                            capacity_log2=6, batch=8, table_log2=6,
                            pop_log=256, telemetry=Telemetry(capacity=512))
    rng = np.random.default_rng(0)
    keys = np.sort(rng.choice(10_000, size=32, replace=False))
    rng.shuffle(keys)
    admitted = eng.tick(keys.tolist(), list(range(32)), slots=32, pages=64,
                        need=[1] * 32)
    if sorted(admitted) != list(range(32)) or eng.occupancy() != 0:
        print(f"# FAIL: 2-shard conservation broken: {sorted(admitted)}",
              file=out)
        ok = False
    k = mesh_relaxation_bound(2, 8, eng.stats["max_occupancy"])
    popped = [kk for _, _, kk, _ in eng.pop_history()]
    depth = max(sum(1 for later in popped[i + 1:] if later < ki)
                for i, ki in enumerate(popped))
    print(f"# 2-shard pop inversion depth {depth} vs envelope k={k}",
          file=out)
    if depth > k:
        print(f"# FAIL: relaxed pop order escaped the envelope "
              f"({depth} > {k})", file=out)
        ok = False

    # 3. the serving trace artifact round-trips the schema validator
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "trace_serving.jsonl")
        write_jsonl(path, eng.telemetry.records, eng.telemetry.sync_points,
                    metrics=dict(eng.stats), engine="serving")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_check.py"),
             path], capture_output=True, text=True, cwd=REPO,
            env=cpu_child_env(), timeout=300)
        print(f"# trace_check: {proc.stdout.strip()}", file=out)
        if proc.returncode != 0:
            print(f"# FAIL: serving trace failed schema validation: "
                  f"{proc.stderr[-1000:]}", file=out)
            ok = False
    print(f"# acceptance: {'PASS' if ok else 'FAIL'}", file=out)
    return ok


# ---------------------------------------------------------------------------
# outer (CSV-relaying) side
# ---------------------------------------------------------------------------


def main(out=sys.stdout, rates=(0.5, 1.5, 3.0), ticks: int = 120,
         tenants: int = 2, trials: int = 3) -> None:
    print("# open-loop serving: goodput + tail latency vs offered load, "
          "host-pool vs device admission", file=out)
    rc = _spawn_inner(["--rates", ",".join(map(str, rates)),
                       "--ticks", str(ticks), "--tenants", str(tenants),
                       "--trials", str(trials)], out)
    if rc != 0:
        raise RuntimeError(f"serving benchmark subprocess exited {rc}")


def smoke(out=sys.stdout) -> bool:
    return _spawn_inner(["--smoke"], out) == 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="run in-process (expects XLA_FLAGS set)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI correctness gate (no timing assertion)")
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweep (CI-sized)")
    ap.add_argument("--rates", default="0.5,1.5,3.0")
    ap.add_argument("--ticks", type=int, default=120)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--trials", type=int, default=3)
    a = ap.parse_args()
    rates = tuple(float(r) for r in a.rates.split(","))
    if a.quick:
        rates, a.ticks, a.trials = (0.5, 2.5), 80, 2
    if a.inner:
        if a.smoke:
            sys.exit(0 if inner_smoke(sys.stdout) else 1)
        sys.exit(0 if inner_main(sys.stdout, rates, ticks=a.ticks,
                                 tenants=a.tenants, trials=a.trials) else 1)
    if a.smoke:
        sys.exit(0 if smoke() else 1)
    main(rates=rates, ticks=a.ticks, tenants=a.tenants, trials=a.trials)
