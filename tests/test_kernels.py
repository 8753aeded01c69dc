"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle across
shape/dtype sweeps, plus hypothesis properties of the ticket semantics."""

import zlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (pip install .[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref


@pytest.mark.parametrize("n", [1024, 2048, 4096])
@pytest.mark.parametrize("density", [0.0, 0.37, 1.0])
def test_wavefaa_matches_ref(n, density):
    rng = np.random.default_rng(n)
    a = (rng.random(n) < density).astype(np.int32)
    c = jnp.array([17], jnp.int32)
    tk, nc = ops.wavefaa(jnp.asarray(a), c)
    tr, ncr = ref.wavefaa_ref(jnp.asarray(a), c)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(tr))
    assert int(nc[0]) == int(ncr[0])


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 20), st.integers(1, 3))
def test_wavefaa_tickets_unique_and_contiguous(start, blocks):
    n = blocks * 1024
    rng = np.random.default_rng(start)
    a = (rng.random(n) < 0.5).astype(np.int32)
    tk, nc = ops.wavefaa(jnp.asarray(a), jnp.array([start], jnp.int32))
    got = np.asarray(tk)[a > 0]
    assert len(set(got.tolist())) == len(got)             # pairwise distinct
    assert (np.sort(got) == np.arange(start, start + len(got))).all()
    assert int(nc[0]) == start + int(a.sum())


@pytest.mark.parametrize("nsl2", [5, 6, 8])
def test_ring_enqueue_dequeue_roundtrip(nsl2):
    nslots, bot = 1 << nsl2, (1 << 31) - 1
    cyc = jnp.zeros(nslots, jnp.int32)
    saf = jnp.ones(nslots, jnp.int32)
    enq = jnp.zeros(nslots, jnp.int32)
    idx = jnp.full(nslots, bot, jnp.int32)
    b = nslots // 2
    tickets = jnp.arange(nslots, nslots + b, dtype=jnp.int32)
    values = jnp.arange(100, 100 + b, dtype=jnp.int32)
    head = jnp.array([nslots], jnp.int32)
    for use_kernel in (True, False):
        k = ops.ring_enqueue(cyc, saf, enq, idx, tickets, values, head,
                             nslots_log2=nsl2, idx_bot=bot,
                             use_kernel=use_kernel)
        r = ref.ring_enqueue_ref(cyc, saf, enq, idx, tickets, values, head,
                                 nsl2, bot)
        for a_, b_ in zip(k, r):
            np.testing.assert_array_equal(np.asarray(a_), np.asarray(b_))
        assert bool(k[4].all())
        dq = ops.ring_dequeue(*k[:4], tickets, nslots_log2=nsl2, idx_bot=bot,
                              use_kernel=use_kernel)
        np.testing.assert_array_equal(np.asarray(dq[4]), np.asarray(values))
        assert bool(dq[5].all())


def test_ring_inactive_tickets_noop():
    nsl2, bot = 5, (1 << 31) - 1
    nslots = 1 << nsl2
    cyc = jnp.zeros(nslots, jnp.int32)
    saf = jnp.ones(nslots, jnp.int32)
    enq = jnp.zeros(nslots, jnp.int32)
    idx = jnp.full(nslots, bot, jnp.int32)
    tickets = jnp.full((8,), -1, jnp.int32)
    values = jnp.arange(8, dtype=jnp.int32)
    out = ops.ring_enqueue(cyc, saf, enq, idx, tickets, values,
                           jnp.array([nslots], jnp.int32),
                           nslots_log2=nsl2, idx_bot=bot)
    assert not bool(out[4].any())
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(idx))


@pytest.mark.parametrize("t,e,k,cap", [(64, 16, 2, 10), (128, 8, 1, 32),
                                       (64, 40, 8, 16)])
def test_moe_route_matches_ref(t, e, k, cap):
    rng = np.random.default_rng(t * e)
    gates = jnp.asarray(rng.normal(size=(t, e)).astype(np.float32))
    dk, ek, ck = ops.moe_route(gates, k, cap)
    dr, er, cr = ref.moe_route_ref(gates, k, cap)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er))
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cr), atol=1e-6)


def test_moe_capacity_is_respected():
    t, e, k, cap = 256, 4, 1, 8
    gates = jnp.zeros((t, e)).at[:, 0].set(10.0)   # all route to expert 0
    dk, ek, _ = ops.moe_route(gates, k, cap)
    granted = np.asarray(dk)[:, 0]
    assert (granted >= 0).sum() == cap             # bounded-ring admission
    assert (granted[granted >= 0] < cap).all()
    assert len(set(granted[granted >= 0].tolist())) == cap  # unique slots


@pytest.mark.parametrize("n,deg", [(64, 4), (256, 8)])
def test_frontier_expand_matches_ref(n, deg):
    rng = np.random.default_rng(n)
    col, rp = [], [0]
    for _ in range(n):
        col.extend(rng.choice(n, size=deg, replace=False).tolist())
        rp.append(len(col))
    row_ptr = jnp.asarray(rp, jnp.int32)
    col_idx = jnp.asarray(col, jnp.int32)
    f0 = [0, n // 2, n - 1]
    frontier = jnp.asarray(f0 + [-1] * (16 - len(f0)), jnp.int32)
    visited = jnp.zeros(n, jnp.int32).at[jnp.asarray(f0)].set(1)
    fk = ops.frontier_expand(row_ptr, col_idx, frontier, visited, max_out=n)
    fr = ref.frontier_expand_ref(row_ptr, col_idx, frontier, None, visited, n)
    np.testing.assert_array_equal(np.asarray(fk[0]), np.asarray(fr[0]))
    assert int(fk[1][0]) == int(fr[1])
    np.testing.assert_array_equal(np.asarray(fk[2]), np.asarray(fr[2]))


@pytest.mark.parametrize("cfg", [
    dict(b=1, h=4, kv=2, sq=512, sk=512, hd=64, causal=True, win=0, cap=0.0),
    dict(b=2, h=8, kv=4, sq=1024, sk=1024, hd=64, causal=True, win=128, cap=0.0),
    dict(b=1, h=4, kv=4, sq=512, sk=1024, hd=32, causal=True, win=0, cap=50.0),
    dict(b=1, h=2, kv=2, sq=512, sk=512, hd=64, causal=False, win=0, cap=0.0),
])
def test_pallas_flash_attention_matches_ref(cfg):
    from repro.kernels.flash_attn import flash_attention
    rng = np.random.default_rng(cfg["sq"])
    q = jnp.asarray(rng.normal(size=(cfg["b"], cfg["h"], cfg["sq"], cfg["hd"]))
                    * 0.3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(cfg["b"], cfg["kv"], cfg["sk"], cfg["hd"]))
                    * 0.3, jnp.float32)
    v = jnp.asarray(rng.normal(size=(cfg["b"], cfg["kv"], cfg["sk"], cfg["hd"]))
                    * 0.3, jnp.float32)
    out = flash_attention(q, k, v, causal=cfg["causal"], window=cfg["win"],
                          softcap_val=cfg["cap"], bq=256, bk=256)
    want = ref.flash_attention_ref(q, k, v, causal=cfg["causal"],
                                   window=cfg["win"], softcap_val=cfg["cap"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)


# -- window waves: block reads and writes over the contiguous ticket run ----

_BOT = (1 << 31) - 1
_WIN_NSL2 = 8                      # 256 slots


def _i32(x):
    """Python or int64 tickets as the int32 the device holds (wrapping)."""
    return (np.asarray(x, np.int64) & 0xFFFFFFFF).astype(np.uint32).view(
        np.int32)


def _random_ring(rng, base, packed):
    """Planes whose cycles sit around the tickets' own, so a wave meets
    every predicate: hit, advance, unsafe, install and refusal."""
    n = 1 << _WIN_NSL2
    c0 = (int(base) & 0xFFFFFFFF) >> _WIN_NSL2
    enqs = rng.integers(0, 2, n)
    if packed:
        enqs |= rng.integers(0, 1 << 20, n) << 1
    idxs = rng.choice([_BOT, _BOT - 1, 7, 123456], n)
    return tuple(jnp.asarray(_i32(p)) for p in (
        c0 + rng.integers(-2, 2, n), rng.integers(0, 2, n), enqs, idxs))


_STARTS = {"zero": 7 << _WIN_NSL2, "mid_block": (7 << _WIN_NSL2) + 75,
           "block_end": (7 << _WIN_NSL2) + 31,
           "ring_end": (8 << _WIN_NSL2) - 5,      # straddles slot 2n-1 -> 0
           "int32_wrap": (1 << 31) - 3}            # tickets pass 2^31
_COUNTS = {"none": lambda w: 0, "one": lambda w: 1,
           "partial": lambda w: w // 2 + 1, "full": lambda w: w}
_WINDOW_CASES = (
    [dict(start=s, count=c) for s in ("zero", "mid_block", "block_end",
                                      "ring_end") for c in _COUNTS]
    + [dict(start="mid_block", count="partial", over=True),
       dict(start="ring_end", count="full", over=True),
       dict(start="mid_block", count="partial", packed=True),
       dict(start="ring_end", count="full", packed=True),
       dict(start="int32_wrap", count="full"),
       dict(start="int32_wrap", count="partial", packed=True),
       dict(start="block_end", count="full", width=32),
       dict(start="ring_end", count="partial", width=32)])


def _case_id(case):
    return "-".join([case["start"], case["count"]]
                    + [k for k in ("over", "packed") if case.get(k)]
                    + ([f"w{case['width']}"] if "width" in case else []))


@pytest.mark.parametrize("case", _WINDOW_CASES, ids=_case_id)
def test_window_waves_match_plane_waves(case):
    """``deq_window``/``enq_window`` give what ``deq_planes``/``enq_planes``
    give for the same contiguous tickets, bit for bit: planes, values, ok
    and packed birth stamps, with each ticket's lane masked explicitly so
    tickets past 2^31 stay live."""
    from repro.kernels.ring_slots import (deq_planes, deq_window,
                                          enq_planes, enq_window,
                                          window_width)
    width = case.get("width", 24)
    packed, over = case.get("packed", False), case.get("over", False)
    base = _STARTS[case["start"]]
    count = _COUNTS[case["count"]](width)
    rng = np.random.default_rng(zlib.crc32(_case_id(case).encode()))
    planes = _random_ring(rng, base, packed)
    assert 2 * window_width(width) <= 1 << _WIN_NSL2
    lane = np.arange(width)
    tickets = jnp.asarray(_i32(base + lane))
    live = jnp.asarray(lane < count)
    head = jnp.int32(_i32(base))
    kw = dict(nslots_log2=_WIN_NSL2, idx_bot=_BOT)

    want = deq_planes(*planes, tickets, active=live, birth_packed=packed,
                      **kw)
    got = deq_window(*planes, head, jnp.int32(count), batch=width,
                     birth_packed=packed, **kw)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    # the engine's overflow flag installs nothing: -1 tickets on the
    # scatter path, a count of 0 in the window
    values = jnp.asarray(rng.integers(0, 1 << 30, width), jnp.int32)
    birth = 12345 if packed else None
    # the head before, at and inside the wave: an unsafe slot installs
    # only from its ticket at the head on
    for lag in (-3, 0, count // 2, max(count - 1, 0)):
        ehead = jnp.int32(_i32(base + lag))
        want = enq_planes(*planes, jnp.full((width,), -1, jnp.int32) if over
                          else tickets, values, ehead,
                          active=None if over else live, birth_round=birth,
                          **kw)
        got = enq_window(*planes, head, ehead, values,
                         jnp.int32(0 if over else count), birth_round=birth,
                         **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        if count == width and not over:   # the wave reaches the planes
            assert not all(np.array_equal(np.asarray(g), np.asarray(p))
                           for g, p in zip(got[:4], planes))


def _tree(limit):
    def step(acc, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        return acc, cv, (valid & (vals < limit))[:, None]
    return step


@pytest.mark.parametrize("capacity_log2,limit,compact,whole", [
    (8, 32, None, True),         # 32-lane child wave in a 512-slot ring
    (4, 8, True, True),          # compacted to the 16-item bound
    (4, 8, False, False),        # 32 sparse lanes, past the 16-item bound
])
def test_ring_engine_wave_path(capacity_log2, limit, compact, whole):
    """``RingEngine`` runs both waves through the window and matches the
    legacy per-round loop bit for bit, also where the child wave is wider
    than the capacity (not ``whole``) and the window takes only its first
    ``capacity`` children in rank order, as no later one can install."""
    from repro.kernels.compact import compact_width
    from repro.runtime import RoundRunner
    batch, wave = 16, 32
    width = compact_width(wave, 1 << capacity_log2, compact) or wave
    assert (width <= 1 << capacity_log2) == whole
    out = []
    for fused in (True, False):
        r = RoundRunner(_tree(limit), capacity_log2=capacity_log2,
                        batch=batch, fused=fused, compact=compact)
        acc, st = r.run([1], acc=jnp.zeros(2 * limit + 2, jnp.int32))
        out.append((acc, st, r.stats))
    (acc, st, stats), (acc0, st0, stats0) = out
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc0))
    for a, b in zip(st[:4], st0[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (st.head, st.tail) == (st0.head, st0.tail)
    assert stats["rounds"] == stats0["rounds"]
    assert stats["ring_window_waves"] == 2 * stats["rounds"]
