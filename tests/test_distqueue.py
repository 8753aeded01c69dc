"""Distributed mesh-level queue: exactly-once + FIFO under shard_map,
with the replication checker ON (the psum-gathered rounds keep the ring
planes replicated-typed, so no ``check_vma=False`` escape hatch), for both
application engines (vectorized ``planes`` sub-waves and the legacy serial
``scan``), at wrap boundaries (tickets crossing the int32 sign and the
full 2^32 cycle boundary), with over-capacity rounds (sub-wave splitting)
and all-inactive shards.

The 8-device run needs XLA_FLAGS set before jax initializes, so it
executes in a subprocess (the main test process must keep 1 device for
the other tests); it also asserts per-shard ring states stay bit-identical
after every round."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.distqueue import (dist_claim_round, dist_dequeue_round,
                                  dist_enqueue_round, dist_queue_init)
from repro.jaxcompat import make_mesh

ENGINES = ("planes", "scan")
# ticket counters near the int32 sign boundary and the full 2^32 wrap
WRAP_STARTS = (None, 2 ** 30, 2 ** 31 - 64, 2 ** 32 - 64)


def _round_fn(engine, b, check_vma=True):
    mesh = make_mesh((1,), ("data",))

    def inner(state, values, emask, want):
        state, granted = dist_enqueue_round(state, values, emask, "data",
                                            engine=engine)
        state, vals, ok = dist_dequeue_round(state, want, "data",
                                             engine=engine)
        return state, granted, vals, ok

    io = (P(), P("data"), P("data"), P("data"))
    return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=io,
                                 out_specs=io, check_vma=check_vma))


def test_single_device_semantics():
    f = _round_fn("planes", 4)
    state = dist_queue_init(16)
    vals = jnp.asarray([5, 6, 7, 8], jnp.int32)
    ones = jnp.ones(4, jnp.int32)
    state, granted, dv, ok = f(state, vals, ones, ones)
    assert bool(granted.all())
    np.testing.assert_array_equal(np.asarray(dv), np.asarray(vals))  # FIFO
    assert bool(ok.all())


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("start", WRAP_STARTS)
def test_fifo_oracle_at_wrap_boundaries(engine, start):
    """Host FIFO oracle parity across rounds whose tickets cross the int32
    sign boundary and the full 2^32 cycle wrap (wCQ-style wrap safety):
    every granted value comes back exactly once, in order."""
    b = 8
    f = _round_fn(engine, b)
    cap = 16
    n2 = 2 * cap
    state = dist_queue_init(cap, start=None if start is None
                            else (start // n2) * n2)
    rng = np.random.default_rng(3)
    sent, got = [], []
    for rnd in range(8):
        vals = jnp.asarray(rng.integers(1, 10_000, (b,)), jnp.int32)
        em = jnp.asarray(rng.random(b) < 0.7, jnp.int32)
        wm = jnp.asarray(rng.random(b) < 0.7, jnp.int32)
        state, granted, dv, ok = f(state, vals, em, wm)
        sent += [int(v) for v, g in zip(vals, granted) if g]
        got += [int(v) for v, o in zip(dv, ok) if o]
    for _ in range(8):
        state, granted, dv, ok = f(state, jnp.zeros(b, jnp.int32),
                                   jnp.zeros(b, jnp.int32),
                                   jnp.ones(b, jnp.int32))
        got += [int(v) for v, o in zip(dv, ok) if o]
    assert got == sent, f"FIFO/exactly-once violated at start={start}"
    assert len(sent) > 0


@pytest.mark.parametrize("start", (None, 2 ** 32 - 128))
def test_engines_bit_identical(start):
    """The vectorized sub-wave engine and the serial scan reference produce
    bit-identical ring states and grant/value/ok vectors, including across
    the wrap boundary."""
    b = 8
    fns = {e: _round_fn(e, b) for e in ENGINES}
    cap = 8
    states = {e: dist_queue_init(cap, start=None if start is None
                                 else (start // (2 * cap)) * (2 * cap))
              for e in ENGINES}
    rng = np.random.default_rng(11)
    for rnd in range(10):
        vals = jnp.asarray(rng.integers(1, 1000, (b,)), jnp.int32)
        em = jnp.asarray(rng.random(b) < 0.8, jnp.int32)
        wm = jnp.asarray(rng.random(b) < 0.6, jnp.int32)
        outs = {}
        for e in ENGINES:
            states[e], granted, dv, ok = fns[e](states[e], vals, em, wm)
            outs[e] = (granted, dv, ok)
        for a, b_ in zip(jax.tree_util.tree_leaves((states["planes"],
                                                    outs["planes"])),
                         jax.tree_util.tree_leaves((states["scan"],
                                                    outs["scan"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_preadvanced_ring_sort_regression():
    """Regression for the order-unsafe sort sentinel: with tail/head
    pre-advanced past 2^30 the legacy scan path's sentinel used to sort
    masked-out lanes *before* live tickets.  Rank-keyed sorting with an
    INT32_MAX sentinel must keep FIFO order exact on a pre-advanced ring
    with interleaved inactive lanes."""
    b = 8
    cap = 16
    n2 = 2 * cap
    start = ((2 ** 30 + 12345) // n2 + 1) * n2      # tail/head > 2^30
    for engine in ENGINES:
        f = _round_fn(engine, b)
        state = dist_queue_init(cap, start=start)
        # interleave inactive (-1-masked) lanes with live ones
        vals = jnp.asarray([10, 0, 11, 0, 12, 0, 13, 0], jnp.int32)
        em = jnp.asarray([1, 0, 1, 0, 1, 0, 1, 0], jnp.int32)
        state, granted, dv, ok = f(state, vals, em,
                                   jnp.asarray([1, 1, 1, 1, 0, 0, 0, 0],
                                               jnp.int32))
        assert [int(v) for v, g in zip(vals, granted) if g] == [10, 11, 12, 13]
        assert [int(v) for v, o in zip(dv, ok) if o] == [10, 11, 12, 13], (
            engine, np.asarray(dv), np.asarray(ok))


@pytest.mark.parametrize("engine", ENGINES)
def test_overcapacity_round_subwaves(engine):
    """A dequeue round asking for more tickets than the ring has slots
    (> 2n ops) must split into sub-waves: requests beyond the occupancy
    miss cleanly (⊥-advance) and later rounds still run FIFO."""
    b = 24                                          # > 2n = 8 slots
    f = _round_fn(engine, b)
    state = dist_queue_init(4)                      # n2 = 8 slots
    vals = jnp.arange(1, b + 1, dtype=jnp.int32)
    em = jnp.asarray([1] * 6 + [0] * (b - 6), jnp.int32)
    state, granted, dv, ok = f(state, vals, em, jnp.ones(b, jnp.int32))
    assert [int(v) for v, g in zip(vals, granted) if g] == [1, 2, 3, 4, 5, 6]
    assert [int(v) for v, o in zip(dv, ok) if o] == [1, 2, 3, 4, 5, 6]
    # the ⊥-advanced ring keeps working in later rounds
    state, granted, dv, ok = f(state, vals, em, jnp.ones(b, jnp.int32))
    assert [int(v) for v, o in zip(dv, ok) if o] == \
        [int(v) for v, g in zip(vals, granted) if g]


def test_all_inactive_round():
    """A round where nothing is requested leaves the state unchanged."""
    f = _round_fn("planes", 4)
    state = dist_queue_init(16)
    zeros = jnp.zeros(4, jnp.int32)
    state2, granted, dv, ok = f(state, zeros, zeros, zeros)
    assert not bool(granted.any()) and not bool(ok.any())
    for a, b in zip(state, state2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_claim_round_balanced_schedule():
    """dist_claim_round splits the budget evenly (remainder to the lowest
    shard indices) with no collective, preserving FIFO order."""
    mesh = make_mesh((1,), ("data",))

    def inner(state, values, emask, k):
        state, granted = dist_enqueue_round(state, values, emask, "data")
        state, vals, ok = dist_claim_round(state, k[0], 8, "data")
        return state, granted, vals, ok

    f = jax.jit(jax.shard_map(
        inner, mesh=mesh, in_specs=(P(), P("data"), P("data"), P()),
        out_specs=(P(), P("data"), P("data"), P("data"))))
    state = dist_queue_init(16)
    vals = jnp.arange(1, 9, dtype=jnp.int32)
    ones = jnp.ones(8, jnp.int32)
    state, granted, cv, ok = f(state, vals, ones,
                               jnp.asarray([5], jnp.int32))
    assert bool(granted.all())
    assert int(ok.sum()) == 5
    assert [int(v) for v, o in zip(cv, ok) if o] == [1, 2, 3, 4, 5]
    assert int(state.tail - state.head) == 3        # 3 left behind


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.distqueue import (dist_queue_init, dist_enqueue_round,
                                      dist_dequeue_round)
    from repro.jaxcompat import make_mesh

    mesh = make_mesh((8,), ("data",))
    B = 4

    def make(engine):
        def inner(state, values, emask, want):
            state, granted = dist_enqueue_round(state, values, emask,
                                                "data", engine=engine)
            state, vals, ok = dist_dequeue_round(state, want, "data",
                                                 engine=engine)
            return state, granted, vals, ok
        # replication checker ON: the psum-gathered rounds keep the planes
        # replicated-typed (no check_vma=False escape hatch)
        return jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P("data"), P("data"), P("data")),
            out_specs=(P(), P("data"), P("data"), P("data"))))

    def per_shard(state):
        # observe every shard's copy of the (replicated) planes
        def inner(state):
            return jax.tree_util.tree_map(lambda x: x[None], tuple(state))
        f = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=(P(),),
                                  out_specs=P("data")))
        return f(state)

    for engine in ("planes", "scan"):
        f = make(engine)
        # start past 2^30: the pre-advanced-ring regression regime, and
        # one shard (the last) all-inactive every round
        n2 = 2 * 64
        state = dist_queue_init(64, start=((2 ** 30) // n2 + 1) * n2)
        rng = np.random.default_rng(0)
        sent, got = [], []
        for rnd in range(6):
            vals = jnp.asarray(rng.integers(1, 1000, (8 * B,)), jnp.int32) \\
                + rnd * 10000
            em = np.asarray(rng.random(8 * B) < 0.7, np.int32)
            wm = np.asarray(rng.random(8 * B) < 0.7, np.int32)
            em[-B:] = 0                      # an all-inactive shard
            wm[-B:] = 0
            state, granted, dv, ok = f(state, vals, jnp.asarray(em),
                                       jnp.asarray(wm))
            sent += [int(v) for v, g in zip(vals, granted) if g]
            got += [int(v) for v, o in zip(dv, ok) if o]
            shards_view = per_shard(state)
            for plane in shards_view:        # bit-identical on every shard
                p = np.asarray(plane)
                assert (p == p[:1]).all(), "shard states diverged"
        for _ in range(6):
            state, granted, dv, ok = f(state, jnp.zeros(8 * B, jnp.int32),
                                       jnp.zeros(8 * B, jnp.int32),
                                       jnp.ones(8 * B, jnp.int32))
            got += [int(v) for v, o in zip(dv, ok) if o]
        assert got == sent, (
            f"FIFO/exactly-once violated ({{engine}}): "
            f"{{len(sent)}} vs {{len(got)}}")
        print("OK", engine, len(sent))
""")


def test_eight_device_fifo_exactly_once():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SUBPROC.format(src=os.path.abspath(src))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK planes" in proc.stdout and "OK scan" in proc.stdout
