"""The trace reduction on intervals worked out by hand: a small trace
recorded on the CPU (``record_cpu_trace.py``) and synthetic events."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace_reduce as tr  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"
E = tr.Event


@pytest.fixture(scope="module")
def cpu_trace():
    from jax.profiler import ProfileData
    return tr.extract(ProfileData.from_file(str(TRACE)), host_ops=True)


def test_extract_reads_ops_and_spans(cpu_trace):
    assert list(cpu_trace.ops) == ["/host:CPU:0"]
    ops = cpu_trace.ops["/host:CPU:0"]
    assert [(e.start, e.end, e.name) for e in ops] == [
        (312722.0, 428512.0, "wrapped_sine"),
        (429462.0, 572464.0, "dot_general.1"),
        (574259.0, 579986.0, "broadcast_add_fusion"),
        (1877054.0, 2004937.0, "wrapped_sine"),
        (2005759.0, 2170019.0, "dot_general.1"),
        (2171250.0, 2176176.0, "broadcast_add_fusion")]
    assert [(s.start, s.end, s.name) for s in cpu_trace.spans] == [
        (26182.0, 642199.0, "bench.search"),
        (646154.0, 1736738.0, "bench.wait"),
        (1744313.0, 2345766.0, "bench.search"),
        (2349158.0, 3420290.0, "bench.wait")]


def test_recorded_trace_by_hand(cpu_trace):
    ops = cpu_trace.ops["/host:CPU:0"]
    lo, hi = 26182.0, 2345766.0                 # first to last search span
    # 115790 + 143002 + 5727 + 127883 + 164260 + 4926
    assert tr.busy(ops, lo, hi) == 561588.0
    assert tr.leaves(ops) == ops                # no op holds another
    assert tr.time_by(ops, tr.by_name, lo, hi) == {
        "wrapped_sine": 115790.0 + 127883.0,
        "dot_general.1": 143002.0 + 164260.0,
        "broadcast_add_fusion": 5727.0 + 4926.0}
    gaps = tr.gaps(ops, lo, hi, cpu_trace.spans)
    assert gaps[0] == (579986.0, 1877054.0, "bench.wait")
    assert gaps[1] == (26182.0, 312722.0, "bench.search")
    assert len(gaps) == 7
    assert tr.gap_time_by_label(gaps) == {
        "bench.wait": 1297068.0,
        "bench.search": 286540.0 + 950.0 + 1795.0 + 822.0 + 1231.0
        + 169590.0}
    assert tr.busy(ops, lo, hi) + sum(e - s for s, e, _ in gaps) == hi - lo


def test_union_merges_overlaps_and_clips():
    ivs = [(5, 9), (0, 2), (1, 3), (8, 12), (20, 21)]
    assert tr.union(ivs) == [(0, 3), (5, 12), (20, 21)]
    assert tr.union(ivs, 2, 20) == [(2, 3), (5, 12)]
    assert tr.busy(ivs) == 3 + 7 + 1
    assert tr.busy(ivs, 6, 100) == 6 + 1


def test_leaves_drop_the_ops_that_hold_others():
    evs = [E(0, 100, "while", line="ops"), E(10, 30, "fusion.1", line="ops"),
           E(40, 45, "fusion.2", line="ops"),
           E(50, 60, "fusion.1", "repro.wavefaa.3", "ops"),
           E(0, 70, "other", line="second line")]
    lv = tr.leaves(evs)
    assert [e.name for e in lv] == ["other", "fusion.1", "fusion.2",
                                    "fusion.1"]
    # the loop spans 0-100; only its body ran, and another line 0-70
    assert tr.busy(lv) == 70
    assert tr.busy(lv, 20, 100) == 50          # 20-70, the rest inside it
    assert tr.time_by(lv, tr.by_name) == {"fusion.1": 30, "fusion.2": 5,
                                          "other": 70}
    assert tr.time_by(lv, tr.in_scope("repro.wavefaa")) == {
        "repro.wavefaa": 10}
    assert tr.time_by(lv, tr.by_name, 20, 55)["fusion.1"] == 10 + 5
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [("b", 3.0),
                                                         ("c", 2.0)]


def test_gaps_take_the_innermost_span():
    spans = [E(0, 100, "bench.search"), E(40, 60, "bench.fetch")]
    ops = [E(10, 45, "a"), E(55, 90, "b")]
    # longest first; gaps of equal length stay in time order
    assert tr.gaps(ops, 0, 100, spans) == [
        (0, 10, "bench.search"), (45, 55, "bench.fetch"),
        (90, 100, "bench.search")]
    assert tr.gaps(ops, 0, 120, spans)[0] == (90, 120, tr.NO_SPAN)
    assert tr.gaps([], 0, 10, []) == [(0, 10, tr.NO_SPAN)]


def test_gaps_inside_a_program_run_are_the_device_s_own():
    spans = [E(0, 100, "bench.search")]
    runs = [E(8, 50, "jit__megaround_impl")]
    ops = [E(10, 20, "a"), E(30, 40, "b"), E(60, 70, "c")]
    assert sorted(tr.gaps(ops, 0, 100, spans, runs)) == [
        (0, 10, "bench.search"), (20, 30, "inside jit__megaround_impl"),
        (40, 60, "bench.search"), (70, 100, "bench.search")]


def test_op_label_shortens_tpu_hlo_names():
    assert tr.op_label(
        "%fusion.97 = s32[16777216]{0:T(1024)} fusion(s32[16777216]{0:T(1024)}"
        " %fusion.91, s32[4096]{0:T(1024)S(1)} %bitcast.139), kind=kCustom, "
        "calls=%fused_computation.14") == "fusion.97 s32[16777216] fusion kCustom"
    assert tr.op_label(
        "%while.343 = (s32[]{:T(128)}, s32[65536]{0:T(1024)}) while((s32[], "
        "s32[65536]) %tuple.543), condition=%c, body=%b") == \
        "while.343 tuple while"
    assert tr.op_label(
        "%repro.wavefaa.3 = (s32[32,128]{1,0:T(8,128)S(1)}, s32[1]{0:T(128)})"
        " custom-call(s32[1]{0:T(128)} %bitcast.114), custom_call_target="
        '"tpu_custom_call"') == "repro.wavefaa.3 tuple custom-call"
    assert tr.op_label("wrapped_sine") == "wrapped_sine"


def test_cut_finds_the_devices_whose_events_reached_the_cap():
    cap = tr.DEVICE_EVENT_CAP
    trace = tr.Trace({}, [], {}, {"/device:TPU:0": cap,
                                  "/device:TPU:1": cap // 2,
                                  "/device:TPU:2": int(0.995 * cap)})
    devices = ["/device:TPU:0", "/device:TPU:1", "/device:TPU:2",
               "/device:TPU:3"]
    assert tr.cut(trace, devices) == ["/device:TPU:0", "/device:TPU:2"]
    assert tr.cut(trace, ["/device:TPU:1"]) == []


def test_extract_counts_every_event_of_a_device_plane():
    ev = _FakeEvent
    profile = _FakeProfile([_FakePlane("/device:TPU:0", [
        _FakeLine("XLA Ops", [ev("%a = s32[4]{0} add(), kind=kLoop", 0, 5),
                              ev("%b = s32[4]{0} copy()", 6, 2)]),
        _FakeLine("XLA Modules", [ev("jit_f(123)", 0, 9)]),
        _FakeLine("Steps", [ev("0", 0, 9), ev("1", 9, 1), ev("2", 10, 1)])]),
        _FakePlane("/host:CPU", [_FakeLine("python", [
            ev("bench.launch", 0, 0), ev("other", 1, 1)])])])
    t = tr.extract(profile)
    assert t.events == {"/device:TPU:0": 6}
    assert [e.name for e in t.ops["/device:TPU:0"]] == [
        "a s32[4] add kLoop", "b s32[4] copy"]
    assert [e.name for e in t.modules["/device:TPU:0"]] == ["jit_f"]
    assert [(s.name, s.start, s.end) for s in t.spans] == [
        ("bench.launch", 0.0, 0.0)]


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
