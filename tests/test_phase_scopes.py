"""The round phases reach the compiled megaround, and the host steps of a
run reach the profiler's trace (``runtime/enginecore.py``, "Phase names
on the profiler's clock").

A ``jax.named_scope`` lives only in the compiled instructions' ``op_name``
metadata, which ``EngineCore.megaround_hlo`` exposes; the host steps are
``jax.profiler.TraceAnnotation`` spans.  Small sizes on the CPU; the
compile for a described v5e is in ``test_tpu_compile.py``."""

import re
import tempfile
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import AxisType, Mesh  # noqa: E402

from repro.apps import bfs, sssp  # noqa: E402
from repro.obs import Telemetry  # noqa: E402


def _bfs(telemetry=None):
    g = bfs.road_like(12 * 12)
    runner, init = bfs.bfs_rounds_runner(g, batch=8, telemetry=telemetry)
    return runner._engine, lambda: runner.run([0], acc=init(0))


def _sssp():
    g = bfs.road_like(8 * 8)
    w = sssp.with_weights(g, max_w=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",),
                axis_types=(AxisType.Auto,))
    runner, init = sssp.sssp_mesh_rounds_runner(
        g, w, mesh=mesh, batch=8, relaxed=True, split_payload=True)
    return runner._engine, lambda: runner.run([0], [0], acc=init(0),
                                              initial_aux=[0])


def _scopes(hlo: str):
    return {seg for path in re.findall(r'op_name="([^"]*)"', hlo)
            for seg in path.split("/") if seg.startswith("repro.")}


@pytest.mark.parametrize("make,want", [
    (_bfs, {"repro.ring.deq", "repro.ring.enq", "repro.step",
            "repro.wavefaa"}),
    (_sssp, {"repro.heap.pop", "repro.heap.insert", "repro.step",
             "repro.publish"})], ids=["bfs_ring", "sssp_relaxed_1_shard"])
def test_every_phase_scope_reaches_the_compiled_megaround(make, want):
    engine, run = make()
    with pytest.raises(RuntimeError, match="no megaround"):
        engine.megaround_hlo()
    run()
    hlo = engine.megaround_hlo()
    assert hlo.startswith("HloModule jit__megaround_impl")
    assert _scopes(hlo) == want


@pytest.mark.parametrize("telemetry", [False, True],
                         ids=["telemetry_off", "telemetry_on"])
def test_host_steps_are_spans_on_the_profiler_clock(telemetry):
    from jax.profiler import ProfileData
    _, run = _bfs(Telemetry(64, engine="rounds") if telemetry else None)
    run()                                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            run()
        finally:
            jax.profiler.stop_trace()
        profile = ProfileData.from_file(
            str(next(Path(d).rglob("*.xplane.pb"))))
    names = [ev.name for plane in profile.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.") and "=" not in ev.name]
    want = ["repro.seed", "repro.dispatch", "repro.sync"]
    if telemetry:
        want.append("repro.drain")
    assert names == want
