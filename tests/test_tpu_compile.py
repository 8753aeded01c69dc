"""Compile the chip path for a described TPU v5e, without a chip.

The TPU compiler is installed with jax and compiles for a topology that is
described rather than attached, so it refuses here what the chip would
refuse: a Pallas kernel that Mosaic cannot lower, more VMEM than a kernel
may use, a program that does not fit device memory.  Interpret mode (what
the rest of the suite runs) hides all of these, so every Pallas call below
passes ``interpret=False`` explicitly.

Covered, at the sizes ``chip_smoke.py`` runs:

* ``wavefaa`` — the one Pallas kernel left on the round engines' path;
* one ``RingEngine`` megaround over a 2^24-slot ring (the BFS phase's
  ring): one Mosaic call (``wavefaa``), every plane wave in XLA;
* one ``HeapEngine`` megaround (the priority phase): no Mosaic call;
* the four-chip mesh engines on a 2x2 mesh: the sharded FIFO ring and
  relaxed split-payload SSSP at four shards.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and each xdist worker imports
every test file.
"""

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (AxisType, Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

MOSAIC = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))


def _on(sharding):
    return lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=sharding)


def _fanout_step(depth):
    def step(acc, vals, valid):
        acc = acc.at[jnp.clip(vals, 0, depth)].add(valid.astype(jnp.int32))
        cv = jnp.broadcast_to((vals - 1)[:, None], (vals.shape[0], 2))
        return acc, cv.astype(jnp.int32), (valid & (vals > 0))[:, None]
    return step


@pytest.mark.parametrize("lanes", [1024, 4096])
def test_wavefaa_compiles(one_chip, lanes):
    from repro.kernels.wavefaa import wavefaa
    s = _on(one_chip)
    f = jax.jit(lambda a, c: wavefaa(a, c, interpret=False))
    text = f.lower(s((lanes,)), s((1,))).compile().as_text()
    assert MOSAIC in text


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OPERATION = re.compile(r"^([\w-]+)\(([^)]*)\)(.*)$")
_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.-]+)")


def _split_shape(text):
    """``(shape, rest)`` of an instruction's right-hand side; a tuple
    shape is its whole parenthesised group."""
    if not text.startswith("("):
        return text.split(" ", 1)
    depth = 0
    for i, ch in enumerate(text):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return text[:i + 1], text[i + 2:]


def _computations(text):
    """Optimized HLO text as ``{computation: [(name, shape, opcode,
    operands, attributes)]}``, each array shape without its layout."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line == "}":
            cur = None
        elif cur is not None and (m := _INSTRUCTION.match(line)):
            shape, rhs = _split_shape(m.group(2))
            op = _OPERATION.match(rhs)
            if op:
                cur.append((m.group(1), re.sub(r"\{[^}]*\}", "", shape),
                            op.group(1),
                            [a.strip().lstrip("%")
                             for a in op.group(2).split(",")],
                            op.group(3)))
    return comps


def _while_body(text, plane):
    """Every instruction of the while loop that carries ``plane``-typed
    state, its body and the computations the body calls, transitively."""
    comps = _computations(text)
    bodies = [re.search(r"body=%([\w.-]+)", line).group(1)
              for line in text.splitlines()
              if " while(" in line and plane in line]
    assert len(bodies) == 1, bodies
    seen, todo = set(), bodies
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for *_, rest in comps[c]:
            todo += _CALLED.findall(rest)
    return [(c, ins) for c in seen for ins in comps[c]]


def test_ring_engine_round_compiles_at_2_24_slots(one_chip):
    """The BFS phase's chip engine: batch 1024, capacity 2^23 (2^24 ring
    slots, 4 planes = 256 MiB).  The ring waves run as XLA ops (the Pallas
    ring kernels would hold the whole ring in VMEM), so the only Mosaic
    call is ``wavefaa``.  They are window waves: inside the loop no
    scatter or gather of a ring wave touches a whole plane, each plane is
    updated in place by block writes, and no plane is copied."""
    from repro.runtime.fusedrounds import RingEngine, RingState
    s = _on(one_chip)
    eng = RingEngine(_fanout_step(16), capacity_log2=23, batch=1024,
                     interpret=False)
    ns = 2 << eng.capacity_log2
    assert ns == 1 << 24
    q = RingState(s((ns,)), s((ns,)), s((ns,)), s((ns,)), s(()), s(()))
    comp = eng._megaround.lower(q, s((17,)), s(()), s(()), s(()),
                                s(())).compile()
    text = comp.as_text()
    assert text.count(MOSAIC) == 1
    assert comp.memory_analysis().argument_size_in_bytes >= 4 * ns * 4
    plane = f"s32[{ns}]"
    body = _while_body(text, plane)
    shapes = {(c, name): shape for c, (name, shape, *_) in body}
    wave_ops = [name for c, (name, _, op, args, rest) in body
                if op in ("scatter", "gather")
                and re.search(r"repro\.ring\.(enq|deq)", rest)
                and shapes.get((c, args[0])) == plane]
    assert wave_ops == []
    copies = [name for _, (name, shape, op, *_) in body
              if op.startswith("copy") and plane in shape]
    assert copies == []
    block_writes = [name for _, (name, shape, op, *_) in body
                    if op == "dynamic-update-slice" and shape == plane]
    assert len(block_writes) >= 2 * (3 + 4)   # deq 3 planes, enq 4


def test_heap_engine_round_compiles(one_chip):
    """The priority phase's chip engine: every heap wave in XLA."""
    from repro.runtime.fusedrounds import HeapEngine, HeapState
    s = _on(one_chip)
    fifo = _fanout_step(9)

    def step(acc, keys, vals, valid):
        acc, cv, cm = fifo(acc, vals, valid)
        return acc, (keys[:, None] + 1 + cv).astype(jnp.int32), cv, cm

    eng = HeapEngine(step, capacity_log2=15, batch=128)
    cap = eng.capacity
    comp = eng._megaround.lower(HeapState(s((cap,)), s((cap,)), s(())),
                                s((10,)), s(()), s(()), s(()),
                                s(())).compile()
    assert MOSAIC not in comp.as_text()


def _mesh_avals(eng, mesh, args):
    """Shapes of a mesh engine's carry, each with the sharding of its
    megaround in_spec (``None`` slots stay empty)."""
    specs = jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p),
                                   tuple(eng._carry_specs),
                                   is_leaf=lambda p: isinstance(p, P))
    return tuple(None if a is None else jax.tree_util.tree_map(
        lambda sh, x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        sp, a) for sp, a in zip(specs, args))


def test_sharded_mesh_ring_compiles_on_four_chips(mesh4):
    """``MeshRoundRunner(sharded=True)`` over the 2x2 mesh at the smoke's
    fanout size: 1/4 of a 2^23-entry ring per chip, one psum per round."""
    from repro.core.distqueue import DistShardedQueueState
    from repro.runtime import MeshRoundRunner
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    r = MeshRoundRunner(_fanout_step(16), mesh=mesh4, capacity_log2=23,
                        batch=1024, sharded=True,
                        combine=lambda a: a.sum(0))
    eng = r._engine
    n2 = 2 * eng.local_capacity
    q = DistShardedQueueState(s((4, n2)), s((4, n2)), s((4, n2)),
                              s((4, n2)), tails=s((4,)), heads=s((4,)))
    avals = _mesh_avals(eng, mesh4, (q, s((4, 17)), s(()), s(()), s(()),
                                     s(()), None, None, None))
    comp = eng._megaround.lower(*avals).compile()
    text = comp.as_text()
    assert MOSAIC not in text and "all-reduce" in text
    assert comp.memory_analysis().argument_size_in_bytes < 4 * 2 * n2 * 4


def test_sssp_mesh_compiles_on_four_chips(mesh4):
    """Relaxed split-payload SSSP at four shards on the smoke's 2^16-vertex
    weighted road grid: every heap wave in XLA."""
    from repro.apps import bfs, sssp
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    g = bfs.road_like(256 * 256)
    w = sssp.with_weights(g, max_w=8)
    runner, _ = sssp.sssp_mesh_rounds_runner(g, w, mesh=mesh4, relaxed=True,
                                             split_payload=True)
    eng = runner._engine
    cap = eng.capacity
    q = (s((4, cap)), s((4, cap)), s((4,)), s((4,)))
    avals = _mesh_avals(eng, mesh4, (q, s((4, g.n)), s(()), s(()), s(()),
                                     s(()), None, None, s((4, cap))))
    text = eng._megaround.lower(*avals).compile().as_text()
    assert MOSAIC not in text and "all-reduce" in text


def _v5e_phase_text(kind, topo):
    """Optimized v5e text of a small megaround: BFS on the chip ring
    (``RingEngine``) or relaxed split-payload SSSP on a one-chip mesh
    (``MeshHeapEngine``, one shard)."""
    from repro.apps import bfs, sssp
    g = bfs.road_like(64 * 64)
    if kind == "bfs_ring":
        from repro.runtime.fusedrounds import RingState
        s = _on(SingleDeviceSharding(topo.devices[0]))
        runner, init = bfs.bfs_rounds_runner(g, batch=64, interpret=False)
        eng = runner._engine
        ns = 2 << eng.capacity_log2
        acc = jax.tree_util.tree_map(lambda x: s(x.shape), init(0))
        q = RingState(s((ns,)), s((ns,)), s((ns,)), s((ns,)), s(()), s(()))
        return eng._megaround.lower(q, acc, s(()), s(()), s(()),
                                    s(())).compile().as_text()
    mesh1 = Mesh(np.array(topo.devices[:1]), ("data",),
                 axis_types=(AxisType.Auto,))
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    runner, _ = sssp.sssp_mesh_rounds_runner(
        g, sssp.with_weights(g, max_w=8), mesh=mesh1, batch=64,
        relaxed=True, split_payload=True)
    eng = runner._engine
    cap = eng.capacity
    q = (s((1, cap)), s((1, cap)), s((1,)), s((1,)))
    avals = _mesh_avals(eng, mesh1, (q, s((1, g.n)), s(()), s(()), s(()),
                                     s(()), None, None, s((1, cap))))
    return eng._megaround.lower(*avals).compile().as_text()


@pytest.mark.parametrize("kind,scopes", [
    ("bfs_ring", {"repro.ring.deq", "repro.ring.enq", "repro.step",
                  "repro.wavefaa"}),
    ("sssp_relaxed_1_shard", {"repro.heap.pop", "repro.heap.insert",
                              "repro.step", "repro.publish"})])
def test_phase_scopes_reach_the_v5e_megaround(topo, kind, scopes):
    """Every phase scope is in the compiled instructions' ``op_name``;
    the Pallas call keeps the HLO name ``repro.wavefaa.N`` that the
    benchmark's ``wavefaa`` readers match; and on one chip XLA drops the
    one-device publish psum, so the SSSP cell's ``repro.publish`` holds
    only the packing and ranking around it."""
    import re
    text = _v5e_phase_text(kind, topo)
    found = {seg for path in re.findall(r'op_name="([^"]*)"', text)
             for seg in path.split("/") if seg.startswith("repro.")}
    assert found == scopes
    calls = re.findall(r"%(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                       text)
    if kind == "bfs_ring":
        assert len(calls) == 1 and calls[0].startswith("repro.wavefaa.")
    else:
        assert calls == [] and "all-reduce" not in text
