"""Dense-wave compaction invariants (DESIGN.md § 4.4):

* ``wave_compact`` (Pallas segmented scan) and ``compact_planes`` (pure-jnp
  ``associative_scan`` twin) both match a numpy cumsum oracle over random /
  all-inactive / full masks, one and two planes, single- and multi-block
  shapes, and both report the TRUE popcount even when lanes clamp;
* compacted lanes land in exactly the row-major ticket order ``wavefaa``
  ranks promise, so the dense wave and the sparse scatter address the same
  slots;
* ``compact_width`` implements the engagement rule (off / auto / forced,
  bound clamp, nlanes==0);
* birth-round stamps survive a compacted wave: span planes are
  bit-identical with compaction forced on vs off on every engine, and the
  four engines themselves stay fused/legacy bit-identical with the
  dense-wave path engaged.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.jaxcompat import make_mesh
from repro.kernels import LANES, compact_planes, compact_width, wave_compact
from repro.kernels.wavefaa import wavefaa
from repro.obs.spans import Spans
from repro.runtime import (MeshRoundRunner, PriorityMeshRoundRunner,
                           PriorityRoundRunner, RoundRunner)


def _oracle(mask, planes, width):
    """Numpy reference: exclusive-cumsum ranks in row-major order, drop
    lanes past ``width``, TRUE (unclamped) popcount."""
    m = np.asarray(mask) > 0
    rank = np.cumsum(m) - m
    dense = [np.zeros(width, np.int32) for _ in planes]
    for d, p in zip(dense, planes):
        keep = m & (rank < width)
        d[rank[keep]] = np.asarray(p)[keep]
    return dense, int(m.sum())


@pytest.mark.parametrize("n", [256, 1024, 2500])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("nplanes", [1, 2])
def test_compact_matches_cumsum_oracle(n, density, nplanes):
    rng = np.random.default_rng(n * 7 + nplanes)
    mask = (rng.random(n) < density).astype(np.int32)
    planes = [rng.integers(1, 1 << 20, n).astype(np.int32)
              for _ in range(nplanes)]
    for width in (max(n // 8, 8), n):          # clamping and full widths
        dref, cref = _oracle(mask, planes, width)
        dj, cj = compact_planes(jnp.asarray(mask),
                                tuple(jnp.asarray(p) for p in planes),
                                width=width)
        dk, ck = wave_compact(jnp.asarray(mask),
                              tuple(jnp.asarray(p) for p in planes),
                              width=width, interpret=True)
        assert int(cj) == cref and int(ck) == cref   # TRUE popcount
        for a, b, c in zip(dref, dj, dk):
            np.testing.assert_array_equal(a, np.asarray(b))
            np.testing.assert_array_equal(a, np.asarray(c))


def test_compact_multiblock_matches_twin():
    # > one grid step for the Pallas kernel (block = LANES lanes)
    n = 3 * LANES + 137
    rng = np.random.default_rng(9)
    mask = (rng.random(n) < 0.15).astype(np.int32)
    plane = rng.integers(1, 1 << 20, n).astype(np.int32)
    width = 512
    (dj,), cj = compact_planes(jnp.asarray(mask), (jnp.asarray(plane),),
                               width=width)
    (dk,), ck = wave_compact(jnp.asarray(mask), (jnp.asarray(plane),),
                             width=width, interpret=True)
    np.testing.assert_array_equal(np.asarray(dj), np.asarray(dk))
    assert int(cj) == int(ck) == int(mask.sum())


def test_compact_order_matches_wavefaa_ranks():
    # the dense wave's lane i must hold the value whose wavefaa ticket is
    # base + i — row-major ticket order is the shared contract
    n = 2048
    rng = np.random.default_rng(3)
    mask = (rng.random(n) < 0.4).astype(np.int32)
    vals = rng.integers(1, 1 << 20, n).astype(np.int32)
    base = 1000
    tickets, _ = wavefaa(jnp.asarray(mask), jnp.array([base], jnp.int32),
                         interpret=True)
    (dense,), count = compact_planes(jnp.asarray(mask), (jnp.asarray(vals),),
                                     width=n)
    sparse = np.zeros(n, np.int32)
    tk = np.asarray(tickets)
    sparse[tk[mask > 0] - base] = vals[mask > 0]
    np.testing.assert_array_equal(np.asarray(dense), sparse)
    assert int(count) == int(mask.sum())


def test_compact_width_rule():
    assert compact_width(100, 64, False) is None       # forced off
    assert compact_width(0, 64) is None                # no lanes
    assert compact_width(100, 64) == 64                # auto: engages, clamps
    assert compact_width(32, 64) is None               # auto: already narrow
    assert compact_width(32, 64, True) == 32           # forced on
    assert compact_width(3, 0, True) == 1              # floor at one lane


def _tree_step():
    def step(acc, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        cm = (valid & (vals < 32))[:, None]
        return acc, cv, cm
    return step


def _pri_step():
    def step(acc, keys, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        ck = (cv * 7919) % 1000
        cm = (valid & (vals < 32))[:, None]
        return acc, ck, cv, cm
    return step


def _runs(make, priority=False):
    out = []
    for compact in (False, True):
        r = make(compact)
        acc, st = (r.run([7919 % 1000], [1], acc=jnp.zeros(80, jnp.int32))
                   if priority
                   else r.run([1], acc=jnp.zeros(80, jnp.int32)))
        stats = {k: v for k, v in r.stats.items()
                 if k not in ("fused", "host_syncs")}
        out.append((np.asarray(acc), stats, r))
    return out


def test_chip_fifo_compact_bit_identical():
    off, on = _runs(lambda c: RoundRunner(
        _tree_step(), capacity_log2=8, batch=16, interpret=True, compact=c))
    np.testing.assert_array_equal(off[0], on[0])
    assert off[1] == on[1]


def test_chip_priority_compact_bit_identical():
    off, on = _runs(lambda c: PriorityRoundRunner(
        _pri_step(), capacity_log2=8, batch=16, compact=c),
        priority=True)
    np.testing.assert_array_equal(off[0], on[0])
    assert off[1] == on[1]


def test_mesh_fifo_compact_bit_identical():
    mesh = make_mesh((1,), ("data",))
    off, on = _runs(lambda c: MeshRoundRunner(
        _tree_step(), mesh=mesh, capacity_log2=8, batch=16, compact=c,
        combine=lambda a: a.sum(0)))
    np.testing.assert_array_equal(off[0], on[0])
    assert off[1] == on[1]


@pytest.mark.parametrize("relaxed", [True, False])
def test_mesh_priority_compact_bit_identical(relaxed):
    mesh = make_mesh((1,), ("data",))
    off, on = _runs(lambda c: PriorityMeshRoundRunner(
        _pri_step(), mesh=mesh, capacity_log2=8, batch=16, relaxed=relaxed,
        compact=c, combine=lambda a: a.sum(0)), priority=True)
    np.testing.assert_array_equal(off[0], on[0])
    assert off[1] == on[1]


def _span_snap(sp):
    return (np.asarray(sp.hist).tolist(), np.asarray(sp.max_wait).tolist(),
            int(np.asarray(sp.total).sum()))


def test_spans_survive_compacted_wave_chip():
    # birth stamps thread the compacted enqueue: identical wait histograms
    snaps = []
    for compact in (False, True):
        sp = Spans(classes=1, engine="rounds")
        r = RoundRunner(_tree_step(), capacity_log2=8, batch=16,
                        interpret=True, compact=compact, spans=sp)
        r.run([1], acc=jnp.zeros(80, jnp.int32))
        snaps.append(_span_snap(sp))
    assert snaps[0] == snaps[1]
    assert snaps[0][2] > 0


@pytest.mark.parametrize("relaxed", [True, False])
def test_spans_survive_compacted_wave_mesh_priority(relaxed):
    mesh = make_mesh((1,), ("data",))
    snaps = []
    for compact in (False, True):
        sp = Spans(classes=1, engine="pmesh")
        r = PriorityMeshRoundRunner(_pri_step(), mesh=mesh, capacity_log2=8,
                                    batch=16, relaxed=relaxed,
                                    compact=compact, spans=sp,
                                    combine=lambda a: a.sum(0))
        r.run([7919 % 1000], [1], acc=jnp.zeros(80, jnp.int32))
        snaps.append(_span_snap(sp))
    assert snaps[0] == snaps[1]
    assert snaps[0][2] > 0
