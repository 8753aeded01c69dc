"""Round phases on the trace (``bench/phases.py``) and the metrics read
from them: the op -> path map and the scoped gaps on text and intervals
worked out by hand, then whole traced runs of each cell at small sizes."""

import sys
import tempfile
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import manifest as mf  # noqa: E402
from bench import phases  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.harness import TraceContext  # noqa: E402

E = tr.Event

HLO = """\
HloModule jit__megaround_impl, is_scheduled=true, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%wrapped_add_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/repro.step/add" stack_frame_id=3}
}

%body (arg: (s32[8])) -> (s32[8]) {
  %arg = (s32[8]{0}) parameter(0)
  %get-tuple-element.5 = s32[8]{0} get-tuple-element(%arg), index=0
  %copy.7 = s32[8]{0} copy(%get-tuple-element.5)
  %fusion.97 = s32[8]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/while/body/repro.publish/jit(g)/repro.ring.enq/scatter" stack_frame_id=9}
  %wrapped_add = s32[8]{0} fusion(%fusion.97), kind=kLoop, calls=%wrapped_add_computation
  ROOT %tuple.2 = (s32[8]{0}) tuple(%wrapped_add)
}

%cond (arg.1: (s32[8])) -> pred[] {
  %arg.1 = (s32[8]{0}) parameter(0)
  ROOT %constant.1 = pred[] constant(false)
}

ENTRY %main.9 (Arg_0.1: s32[8]) -> s32[8] {
  %Arg_0.1 = s32[8]{0} parameter(0), metadata={op_name="x"}
  %copy.1 = s32[8]{0} copy(%Arg_0.1)
  %tuple.1 = (s32[8]{0}) tuple(%copy.1)
  %while.343 = (s32[8]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(f)/while/repro.heap.pop/while" stack_frame_id=1}
  %repro.wavefaa.3 = (s32[8]{0}, s32[1]{0}) custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/repro.wavefaa/pallas_call"}
  ROOT %get-tuple-element.9 = s32[8]{0} get-tuple-element(%while.343), index=0
}
"""


def test_op_paths_read_compiled_text():
    module, paths = phases.op_paths(HLO)
    assert module == "jit__megaround_impl"
    assert paths["fusion.97"] == \
        "jit(f)/while/body/repro.publish/jit(g)/repro.ring.enq/scatter"
    assert paths["add.1"] == "jit(f)/while/body/repro.step/add"
    # a wrapping fusion takes its computation's root's path; a copy in a
    # loop body takes the loop's; an entry copy has none
    assert paths["wrapped_add"] == paths["add.1"]
    assert paths["copy.7"] == paths["while.343"] == \
        "jit(f)/while/repro.heap.pop/while"
    assert "copy.1" not in paths
    assert paths["repro.wavefaa.3"].endswith("repro.wavefaa/pallas_call")
    assert phases.op_paths("no module here") == ("", {})


@pytest.mark.parametrize("path,want", [
    ("jit(f)/while/body/repro.publish/jit(g)/repro.ring.enq/scatter",
     "repro.ring.enq"),
    ("jit(f)/while/body/repro.step/add", "repro.step"),
    ("jit(f)/while/body/sub", None),
    ("x", None), ("", None), (None, None)])
def test_phase_is_the_innermost_repro_scope(path, want):
    assert phases.phase(path) == want


def test_runs_are_program_runs_or_outermost_ops():
    dev = "/device:TPU:0"
    ops = [E(0, 100, "while.1", "while.1", "ops"),
           E(10, 20, "a", "a", "ops"), E(120, 130, "b", "b", "ops"),
           E(5, 15, "c", "c", "other")]
    mods = [E(0, 110, "jit_f", "", "XLA Modules")]
    assert phases.runs(tr.Trace({dev: ops}, [], {dev: mods}), dev) == mods
    assert [e.name for e in phases.runs(tr.Trace({dev: ops}, []), dev)] == \
        ["while.1", "c", "b"]


def test_scoped_gaps_keep_the_ops_on_both_sides():
    ops = [E(0, 10, "a"), E(12, 20, "b"), E(15, 30, "c"), E(40, 45, "d"),
           E(60, 70, "e"), E(75, 80, "f")]
    runs = [E(0, 50, "jit_f"), E(58, 90, "jit_f")]
    got = phases.scoped_gaps(ops, 0, 100, runs)
    assert [(s, e, b.name, a.name) for s, e, b, a in got] == [
        (10, 12, "a", "b"), (30, 40, "c", "d"), (70, 75, "e", "f")]
    # 45-60 has its midpoint outside both runs; 80-100 ends the window
    assert [(s, e) for s, e, _, _ in
            phases.scoped_gaps(ops, 11, 72, runs)] == [(11, 12), (30, 40)]


# A hand-worked device trace of two program runs: the megaround
# (0-100) and a seeding program (110-120); window 0-200.
#   ring.deq 0-10, gap 10-15 (ring/step), step 15-35, gap 35-40
#   (step/heap), heap.pop 40-60, gap 60-70 (heap/heap), heap.insert
#   70-90, idle 90-100 inside the run with no op after it in the run,
#   the seeding op 110-120 (outside the megaround), idle 120-200.
DEV = "/device:TPU:0"
TEXT = """\
HloModule jit__megaround_impl, entry_computation_layout={()->()}
  %fusion.1 = s32[8]{0} fusion(), metadata={op_name="m/while/body/repro.ring.deq/gather"}
  %fusion.2 = s32[8]{0} fusion(), metadata={op_name="m/while/body/repro.step/add"}
  %fusion.3 = s32[8]{0} fusion(), metadata={op_name="m/while/body/repro.heap.pop/while"}
  %fusion.4 = s32[8]{0} fusion(), metadata={op_name="m/while/body/repro.heap.insert/while"}
"""
OPS = [E(0, 10, "fusion.1 s32[8] fusion", "fusion.1", "XLA Ops"),
       E(15, 35, "fusion.2 s32[8] fusion", "fusion.2", "XLA Ops"),
       E(40, 60, "fusion.3 s32[8] fusion", "fusion.3", "XLA Ops"),
       E(70, 90, "fusion.4 s32[8] fusion", "fusion.4", "XLA Ops"),
       E(110, 120, "fusion.1 s32[8] fusion", "fusion.1", "XLA Ops")]
MODS = [E(0, 100, "jit__megaround_impl", "", "XLA Modules"),
        E(110, 120, "jit_seed", "", "XLA Modules")]


def _ctx():
    cell = mf.cell("dimacs_road.sssp")
    trace = tr.Trace({DEV: OPS}, [], {DEV: MODS}, {DEV: len(OPS)})
    leaves = tr.leaves(OPS)
    return TraceContext(cell, trace, 0.0, 200.0, [DEV], DEV,
                        {DEV: tr.busy(leaves, 0.0, 200.0)}, {DEV: leaves},
                        [], {}, None, {})


@pytest.mark.parametrize("metric,want", [
    ("ring_wave_share", 100.0 * 10 / 80),     # busy 10+20+20+20+10 = 80
    ("heap_wave_share", 100.0 * 40 / 80),
    ("step_share", 100.0 * 20 / 80),
    ("heap_gap_share", 100.0 * 10 / 200),     # only 60-70
    ("host_gap_share", 100.0 * 90 / 200)])    # 100-110 and 120-200
def test_metrics_read_the_hand_worked_trace(monkeypatch, metric, want):
    monkeypatch.setattr(phases, "megaround_text", lambda cell: TEXT)
    assert mf.metric_module(metric).read(_ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["ring_wave_share", "heap_wave_share",
                                    "step_share", "heap_gap_share"])
def test_a_program_that_names_no_phase_reads_nothing(monkeypatch, metric):
    monkeypatch.setattr(phases, "megaround_text", lambda cell: None)
    assert mf.metric_module(metric).read(_ctx()) is None


def test_phases_outside_the_megaround_count_as_none(monkeypatch):
    monkeypatch.setattr(phases, "megaround_text", lambda cell: TEXT)
    ctx = _ctx()
    ph = phases.of(ctx)
    assert ph.module == "jit__megaround_impl"
    assert ph.phase == ["repro.ring.deq", "repro.step", "repro.heap.pop",
                        "repro.heap.insert", None]
    assert phases.busy_by_phase(ctx, ph) == {
        "repro.ring.deq": 10, "repro.step": 20, "repro.heap.pop": 20,
        "repro.heap.insert": 20, None: 10}


def test_repro_spans_stay_out_of_the_window_and_name_host_gaps():
    """The harness's ``extract`` keeps ``bench.`` spans alone, so the
    window (``bench.launch`` to ``bench.search``) cannot move; the
    program's ``repro.`` spans are kept by asking for both prefixes, and
    then name the host gaps under them."""
    profile = _FakeProfile([
        _FakePlane("/device:TPU:0", [_FakeLine("XLA Ops", [
            _FakeEvent("%a = s32[4]{0} add()", 40, 20)])]),
        _FakePlane("/host:CPU", [_FakeLine("python", [
            _FakeEvent("bench.launch", 0, 0),
            _FakeEvent("bench.search", 1, 99),
            _FakeEvent("repro.seed", 2, 28),
            _FakeEvent("repro.dispatch", 32, 3),
            _FakeEvent("repro.sync", 36, 58)])])])
    bench_only = tr.extract(profile)
    assert [s.name for s in bench_only.spans] == ["bench.launch",
                                                  "bench.search"]
    both = tr.extract(profile, span_prefix=("bench.", "repro."))
    assert [s.name for s in both.spans] == [
        "bench.launch", "bench.search", "repro.seed", "repro.dispatch",
        "repro.sync"]
    ops = both.ops["/device:TPU:0"]
    assert tr.gaps(ops, 0, 100, bench_only.spans) == [
        (0, 40, "bench.search"), (60, 100, "bench.search")]
    assert tr.gaps(ops, 0, 100, both.spans) == [
        (0, 40, "repro.seed"), (60, 100, "repro.sync")]


NEW_METRICS = {"ring_wave_share", "heap_wave_share", "step_share",
               "heap_gap_share", "host_gap_share"}


@pytest.mark.parametrize("name,spec", [
    ("dimacs_road.bfs", {}), ("dimacs_road.sssp", {"trace_seconds": 0.05})])
def test_traced_run_reads_every_phase_metric(name, spec):
    pytest.importorskip("jax")
    from benchcells import run
    r = run(name, trace=True, **spec)
    assert r["correct"]
    listed = {m["name"] for m in mf.cell(name).per_layer} & NEW_METRICS
    assert listed and listed <= set(r["metrics"])
    for m in listed:
        v = r["metrics"][m]["value"]
        assert isinstance(v, float) and 0.0 <= v <= 100.0, (m, v)


def test_program_host_spans_lie_inside_the_search():
    """A CPU trace of one small search: the program's ``repro.seed``,
    ``repro.dispatch`` and ``repro.sync`` spans, in that order, inside
    the harness's ``bench.search``."""
    jax = pytest.importorskip("jax")
    import io
    from jax.profiler import ProfileData
    from benchcells import SEED, small_cell
    from bench import harness
    cell = small_cell("dimacs_road.bfs")
    query = mf.query_module("bfs").Query(cell, SEED, jax.devices()[:1])
    query.warm()
    with tempfile.TemporaryDirectory() as d:
        harness._window(query, 0.0, d, jax, io.StringIO())
        path = next(Path(d).rglob("*.xplane.pb"))
        trace = tr.extract(ProfileData.from_file(str(path)), host_ops=True,
                           span_prefix=("bench.", "repro."))
    search, = [s for s in trace.spans if s.name == "bench.search"]
    steps = [s for s in trace.spans if s.name.startswith("repro.")]
    assert [s.name for s in steps] == ["repro.seed", "repro.dispatch",
                                       "repro.sync"]
    assert all(search.start <= s.start and s.end <= search.end
               for s in steps)


class _FakeEvent:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, \
            duration_ns
        self.stats = []


class _FakeLine:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _FakePlane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _FakeProfile:
    def __init__(self, planes):
        self.planes = planes
