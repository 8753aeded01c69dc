"""Sound runs of every cell come out correct, and the control (a queue
that loses the last item of each full half wave) comes out not correct,
at sizes a CPU test run holds."""

import pytest

from benchcells import cells, run

pytest.importorskip("jax")


@pytest.mark.parametrize("name", cells())
def test_sound_run_is_correct(name):
    r = run(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in r["checks"].values())
    assert list(r)[-1] == "checks"
    rates = [v["value"] for k, v in r["metrics"].items() if k.endswith("teps")]
    assert len(rates) == 1 and rates[0] > 0
    assert r["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("name", cells())
def test_control_is_not_correct(name):
    from bench.harness import control
    r = run(name, plant=control)
    assert not r["correct"]
    assert r["failed"] >= 1
    assert r["checks"]["failed_searches"]["value"] == r["failed"]


def test_traced_run_reads_its_metrics():
    r = run("dimacs_road.bfs", trace=True)
    assert r["correct"]
    dev = r["device"]
    assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
    assert {"round_us", "device_idle_share"} <= set(r["metrics"])
    assert "teps" not in r["metrics"]
    assert 1 <= len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def test_sound_run_does_whole_passes():
    r = run("dimacs_road.bfs", seconds=0.0)
    assert r["attempted"] == 3                 # the small cell's 3 roots


def test_traced_segment_stops_the_profiler_and_reads_its_metrics():
    r = run("dimacs_road.sssp", trace=True, trace_seconds=0.05)
    assert r["correct"]
    dev = r["device"]
    assert 0 < dev["window_s"] <= 0.05 + 1e-9
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert {"round_us", "device_idle_share", "pops_per_vertex"} <= \
        set(r["metrics"])
