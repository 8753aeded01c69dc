"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

``run_cell`` is everything after the look for a chip (``run.py`` makes
that), so a test can drive a whole run on the CPU at a small size, and
plant a fault in the timed path through ``plant``.

Searches run back to back; a search is one call of the program's entry
point and ends when its result is on the host.  The window starts at the
first search's launch and ends at the end of the first whole pass over
the cell's traffic (``query.pass_length`` searches) that ends at or after
``seconds``, so every run does whole passes.  With ``trace`` the run
traces the first search alone, and the per-layer metrics are read from
that trace: the whole search, or, where the cell gives
``trace_seconds``, its first ``trace_seconds``, after which the profiler
is stopped (a search that makes more device events than the profiler
keeps is traced in a stated segment so).
"""

from __future__ import annotations

import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional


from . import manifest as mf
from . import trace_reduce as tr
from .peaks import peaks_for

SEARCH_SPAN = "bench.search"
LAUNCH_SPAN = "bench.launch"    # an empty span just before each search


class Search(NamedTuple):
    index: int
    start: float            # host seconds from the window's start
    end: float
    out: object             # the query's result, None if it raised
    stats: dict             # the engine's counters for this search
    error: Optional[str]
    traced: bool


class Run(NamedTuple):
    """What an end-to-end metric reader gets."""
    cell: mf.Cell
    searches: List[Search]
    checks: Dict[int, "Check"]
    window_s: float
    setup_s: float
    peak: int                    # peak bytes in use on the fullest chip


class Check(NamedTuple):
    mismatches: Dict[str, int]   # number compared -> value (limit 0)
    edges: int                   # input edges the search traversed
    reached: int                 # vertices or nodes it reached


class TraceContext(NamedTuple):
    """What a per-layer metric reader gets."""
    cell: mf.Cell
    trace: tr.Trace
    lo: float                    # traced window on the trace's clock (ns)
    hi: float
    devices: List[str]           # the cell's device planes
    fullest: str                 # the device plane busiest in the window
    busy: Dict[str, float]       # device plane -> busy ns in the window
    leaves: Dict[str, list]      # device plane -> operations that ran
    searches: List[Search]       # the traced searches
    checks: Dict[int, Check]     # search index -> its comparison
    peaks: object
    info: dict                   # the query's static facts (lane counts)


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache reads included)
    reported through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax) -> None:
        self.count = 0
        self.seconds = 0.0
        self._monitoring = jax.monitoring
        self._listener = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._listener)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._listener)


def control(query) -> None:
    """The control: a queue that breaks exactly-once delivery.  The step
    never sees the last item of each full half wave (lanes B/2 - 1 and
    B - 1); every step takes its wave's ``valid`` mask last."""
    import jax.numpy as jnp
    inner = query.engine.step_fn

    def step(*args):
        valid = args[-1]
        half = valid.shape[0] // 2
        lane = jnp.arange(valid.shape[0])
        return inner(*args[:-1], valid & (lane % half != half - 1))

    query.engine.step_fn = step


def _seconds_from(t0: float) -> float:
    return time.perf_counter() - t0


class _Stopper:
    """Stops the profiler ``seconds`` after ``start``, from a thread of its
    own, while the search runs on."""

    def __init__(self, jax, seconds: float) -> None:
        self.error = None

        def stop():
            try:
                jax.profiler.stop_trace()
            except Exception as e:          # re-raised by join
                self.error = e

        self.timer = threading.Timer(seconds, stop)

    def start(self) -> None:
        self.timer.start()

    def join(self) -> None:
        self.timer.join()
        if self.error is not None:
            raise self.error


def _window(query, seconds: float, trace_dir: Optional[str], jax, log):
    """Run searches back to back; returns (searches, window seconds)."""
    searches: List[Search] = []
    tracing = trace_dir is not None
    stopper = None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        segment = query.spec.get("trace_seconds")
        if segment:
            stopper = _Stopper(jax, segment)
    t0 = time.perf_counter()
    i = 0
    while True:
        with jax.profiler.TraceAnnotation(LAUNCH_SPAN):
            pass
        if stopper is not None and i == 0:
            stopper.start()
        start = _seconds_from(t0)
        out, err = None, None
        with jax.profiler.TraceAnnotation(SEARCH_SPAN):
            try:
                out = query.search(i)
            except RuntimeError as e:       # overflow or truncation
                err = str(e)
        end = _seconds_from(t0)
        searches.append(Search(i, start, end, out, query.stats(), err,
                               tracing))
        i += 1
        if tracing:
            if stopper is not None:
                stopper.join()
            else:
                jax.profiler.stop_trace()
            print(f"trace written in {_seconds_from(t0) - end} s", file=log)
            break
        if end >= seconds and i % query.pass_length == 0:
            break
    return searches, searches[-1].end


def _trace_context(cell, trace_dir, searches, checks, device, info,
                   chips, log):
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    # the CPU backend (tests) runs its operations on host threads
    trace = tr.extract(ProfileData.from_file(str(files[-1])),
                       host_ops=device.platform == "cpu")
    launches = [s for s in trace.spans if s.name == LAUNCH_SPAN]
    ends = [s.end for s in trace.spans if s.name == SEARCH_SPAN]
    traced = [s for s in searches if s.traced]
    if not launches:
        raise RuntimeError("the trace holds no search launch")
    lo = launches[0].start
    segment = cell.spec.get("trace_seconds")
    if segment:
        # the profiler stopped at or after the segment's end; a search
        # that ended before it ends the window
        hi = min([lo + segment * 1e9] + ends)
    elif ends:
        hi = ends[-1]
    else:
        raise RuntimeError("the trace holds no whole search")
    devices = sorted(trace.ops, key=lambda d: int(d.rsplit(":", 1)[1]))
    devices = devices[:chips]
    cut = tr.cut(trace, devices)
    if cut:
        raise RuntimeError(
            f"the profiler dropped events on {cut} ({trace.events}): the "
            f"traced window is longer than its trace; give the cell a "
            f"shorter trace_seconds")
    leaves = {d: tr.leaves(trace.ops[d]) for d in devices}
    busy = {d: tr.busy(leaves[d], lo, hi) for d in devices}
    fullest = max(devices, key=lambda d: busy[d])
    peaks = None if device.platform == "cpu" else peaks_for(device.device_kind)
    return TraceContext(cell, trace, lo, hi, devices, fullest, busy, leaves,
                        traced, checks, peaks, info)


def _trace_log(ctx: TraceContext, log) -> None:
    """How the trace covers the window: counts, and busy time by tenths."""
    for d in ctx.devices:
        ops, lv = ctx.trace.ops[d], ctx.leaves[d]
        step = (ctx.hi - ctx.lo) / 10
        tenths = [tr.busy(lv, ctx.lo + k * step, ctx.lo + (k + 1) * step)
                  / step for k in range(10)]
        print(f"trace {d}: {len(ops)} ops, {len(lv)} leaves, "
              f"{len(ctx.trace.modules.get(d, ()))} program runs, "
              f"first op at {(ops[0].start - ctx.lo) * 1e-9 if ops else None}"
              f" s, last end at {(ops[-1].end - ctx.lo) * 1e-9 if ops else None}"
              f" s; busy by tenths {[round(x, 3) for x in tenths]}", file=log)


def _breakdown(ctx: TraceContext) -> dict:
    dev = ctx.fullest
    by_op = tr.time_by(ctx.leaves[dev], tr.by_name, ctx.lo, ctx.hi)
    idle = tr.gap_time_by_label(tr.gaps(
        ctx.leaves[dev], ctx.lo, ctx.hi, ctx.trace.spans,
        ctx.trace.modules.get(dev, ())))
    return {"device_ops": [[k, v * 1e-9] for k, v in tr.top(by_op)],
            "idle_gaps": [[k, v * 1e-9] for k, v in tr.top(idle)]}


def _end_to_end(run: Run) -> dict:
    """Each end-to-end metric of the cell, by its reader."""
    return {m["name"]: {"value": mf.metric_module(m["name"]).read(run),
                        "unit": m["unit"]} for m in run.cell.end_to_end}


def _compare(query, searches: List[Search], log):
    """Every search against the reference: ``(checks by search index,
    numbers compared)``.  A search that raised or mismatched fails."""
    checks: Dict[int, Check] = {}
    failed = 0
    for s in searches:
        if s.error is not None:
            failed += 1
            print(f"search {s.index} raised: {s.error}", file=log)
            continue
        checks[s.index] = query.check(s.index, s.out)
        failed += any(checks[s.index].mismatches.values())
    numbers = {"failed_searches": failed}
    for c in checks.values():
        for k, v in c.mismatches.items():
            numbers[k] = numbers.get(k, 0) + v
    for s in searches:
        c = checks.get(s.index)
        print(f"search {s.index}: {s.end - s.start} s, {s.stats}, "
              f"edges {c.edges if c else None}", file=log)
    return checks, numbers


def _per_layer(cell, ctx: TraceContext) -> dict:
    metrics = {}
    for m in cell.per_layer:
        v = mf.metric_module(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics


def run_cell(cell: mf.Cell, seed: int, seconds: float, trace: bool,
             devices, t_start: float, *,
             plant: Optional[Callable] = None, log=sys.stderr) -> dict:
    """Set up, warm up, measure, compare; returns the result object.
    ``t_start`` is the host clock (``perf_counter``) at the process's
    start, so set-up counts the imports too.  ``plant(query)`` may break
    the timed path before the warm-up (the control and the fault tests)."""
    import jax

    compiles = CompileCounter(jax)
    try:
        used = list(devices)[:cell.spec["chips"]]
        query = mf.query_module(cell.spec["query"]).Query(cell, seed, used)
        if plant is not None:
            plant(query)
        query.warm()
        setup_s = _seconds_from(t_start)
        before = compiles.count
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            searches, window_s = _window(query, seconds,
                                         tdir if trace else None, jax, log)
            in_window = compiles.count - before
            peak = (max(d.memory_stats()["peak_bytes_in_use"] for d in used)
                    if used[0].platform != "cpu" else 0)
            query.release()
            checks, numbers = _compare(query, searches, log)
            device = {"platform": used[0].platform,
                      "kind": used[0].device_kind, "count": len(devices),
                      "memory_peak_bytes": peak}
            result = {"correct": numbers["failed_searches"] == 0,
                      "attempted": len(searches),
                      "failed": numbers["failed_searches"]}
            if trace:
                t_read = time.perf_counter()
                ctx = _trace_context(cell, tdir, searches, checks, used[0],
                                     query.info, cell.spec["chips"], log)
                print(f"trace read and reduced in "
                      f"{_seconds_from(t_read)} s", file=log)
                _trace_log(ctx, log)
                busy = [ctx.busy[d] for d in ctx.devices]
                device["busy_s"] = sum(busy) / len(busy) * 1e-9
                device["window_s"] = (ctx.hi - ctx.lo) * 1e-9
                result["metrics"] = _per_layer(cell, ctx)
                result["device"] = device
                result["breakdown"] = _breakdown(ctx)
            else:
                result["metrics"] = _end_to_end(Run(
                    cell, searches, checks, window_s, setup_s, peak))
                result["device"] = device
        print(f"window: {len(searches)} searches in {window_s} s, "
              f"{in_window} compiles inside it, setup {setup_s} s "
              f"({compiles.count} compiles, {compiles.seconds} s)",
              file=log)
    finally:
        compiles.close()
    result["checks"] = {k: {"value": v, "limit": 0}
                        for k, v in numbers.items()}
    return result
