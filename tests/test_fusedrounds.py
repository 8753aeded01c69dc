"""Fused round engine invariants (DESIGN.md § 4.3):

* the fused megaround loop is bit-identical to the legacy per-round loop —
  same acc, same field planes, same head/tail / heap size, same stats
  counters — on tree, BFS, and raytrace workloads;
* the fused path syncs the host once at quiescence (``sync_every`` gives a
  periodic heartbeat), where the legacy path syncs every round;
* overflow (ring and heap) and ``max_rounds`` truncation raise
  ``RuntimeError`` from both engines — truncation cannot be mistaken for
  quiescence;
* ``wavefaa`` edge cases: all-inactive mask and the multi-block SMEM
  carry of the in-loop ticket source;
* ``REPRO_PALLAS_INTERPRET`` resolves interpret/compiled mode for every
  kernel entry point without a code change.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.runtime import PriorityRoundRunner, RoundRunner  # noqa: E402

STAT_KEYS = ("rounds", "processed", "spawned", "max_occupancy", "drained")


def _tree_step():
    def step(acc, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        cm = (valid & (vals < 32))[:, None]
        return acc, cv, cm
    return step


def _run_pair(**kw):
    accs, states, stats = [], [], []
    for fused in (True, False):
        r = RoundRunner(_tree_step(), capacity_log2=8, batch=16,
                        fused=fused, **kw)
        acc, st = r.run([1], acc=jnp.zeros(80, jnp.int32))
        accs.append(np.asarray(acc))
        states.append(st)
        stats.append(r.stats)
    return accs, states, stats


def test_fused_matches_legacy_tree():
    accs, states, stats = _run_pair()
    np.testing.assert_array_equal(accs[0], accs[1])
    for a, b in zip(states[0][:4], states[1][:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (states[0].head, states[0].tail) == (states[1].head,
                                                states[1].tail)
    for k in STAT_KEYS:
        assert stats[0][k] == stats[1][k], k
    # the headline: host sync only at quiescence vs every round
    assert stats[0]["host_syncs"] == 1
    assert stats[1]["host_syncs"] > stats[1]["rounds"]


def test_fused_sync_every_heartbeat():
    r = RoundRunner(_tree_step(), capacity_log2=8, batch=16, sync_every=2)
    acc, _ = r.run([1], acc=jnp.zeros(80, jnp.int32))
    full = RoundRunner(_tree_step(), capacity_log2=8, batch=16)
    acc2, _ = full.run([1], acc=jnp.zeros(80, jnp.int32))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc2))
    assert r.stats["host_syncs"] > 1
    assert r.sync_log[-1]["occupancy"] == 0
    assert [e["rounds"] for e in r.sync_log] == \
        sorted(e["rounds"] for e in r.sync_log)


def test_fused_bfs_bit_identical_and_exact():
    from repro.apps import bfs
    for g in (bfs.kron_like(300, avg_deg=6, seed=2), bfs.road_like(256)):
        ref = bfs.bfs_reference(g, 0)
        dist_f, stats_f = bfs.bfs_rounds(g, 0, batch=32, fused=True)
        dist_l, stats_l = bfs.bfs_rounds(g, 0, batch=32, fused=False)
        np.testing.assert_array_equal(dist_f, ref)
        np.testing.assert_array_equal(dist_l, ref)
        for k in STAT_KEYS:
            assert stats_f[k] == stats_l[k], (g.name, k)
        assert stats_f["host_syncs"] < stats_l["host_syncs"]


def test_fused_raytrace_bit_identical_to_legacy_and_queue():
    from repro.apps import raytrace
    scene = raytrace.cornell_scene()
    img_q, _ = raytrace.render_queue(scene, w=16, h=16)
    img_f, info_f = raytrace.render_rounds(scene, w=16, h=16, batch=64,
                                           fused=True)
    img_l, info_l = raytrace.render_rounds(scene, w=16, h=16, batch=64,
                                           fused=False)
    np.testing.assert_array_equal(img_f, img_l)          # bit-identical
    np.testing.assert_allclose(img_f, img_q, rtol=1e-5, atol=1e-5)
    assert info_f["rays"] == info_l["rays"] > 0
    assert info_f["host_syncs"] == 1


def _pri_step():
    def step(acc, keys, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(valid.astype(jnp.int32))
        ck = jnp.stack([keys + 1, keys + 2], -1).astype(jnp.int32)
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        cm = (valid & (vals < 32))[:, None]
        return acc, ck, cv, cm
    return step


def test_fused_priority_matches_legacy():
    accs, sizes, stats = [], [], []
    for fused in (True, False):
        r = PriorityRoundRunner(_pri_step(), capacity_log2=8, batch=16,
                                fused=fused)
        acc, st = r.run([5], [1], acc=jnp.zeros(80, jnp.int32))
        accs.append(np.asarray(acc))
        sizes.append(st.size)
        stats.append(r.stats)
        if fused:
            keys_f, vals_f = np.asarray(st.keys), np.asarray(st.vals)
        else:
            np.testing.assert_array_equal(keys_f, np.asarray(st.keys))
            np.testing.assert_array_equal(vals_f, np.asarray(st.vals))
    np.testing.assert_array_equal(accs[0], accs[1])
    assert sizes[0] == sizes[1]
    for k in STAT_KEYS:
        assert stats[0][k] == stats[1][k], k
    assert stats[0]["host_syncs"] == 1 < stats[1]["host_syncs"]


# -- error paths --------------------------------------------------------------


def _explode_step():
    def step(acc, vals, valid):
        cv = jnp.broadcast_to(vals[:, None], (vals.shape[0], 4)) + 1
        cm = jnp.broadcast_to(valid[:, None], cv.shape)
        return acc, cv.astype(jnp.int32), cm
    return step


@pytest.mark.parametrize("fused", [True, False])
def test_ring_overflow_raises(fused):
    r = RoundRunner(_explode_step(), capacity_log2=4, batch=8, fused=fused)
    with pytest.raises(RuntimeError, match="ring overflow"):
        r.run(np.arange(8), acc=jnp.int32(0), max_rounds=100)


@pytest.mark.parametrize("fused", [True, False])
def test_ring_seed_overflow_raises(fused):
    r = RoundRunner(_tree_step(), capacity_log2=4, batch=8, fused=fused)
    with pytest.raises(RuntimeError, match="ring overflow"):
        r.run(np.arange(64), acc=jnp.zeros(80, jnp.int32))


@pytest.mark.parametrize("fused", [True, False])
def test_failed_run_does_not_keep_stale_stats(fused):
    """A run that dies before its first sync must not republish the
    previous successful run's stats."""
    r = RoundRunner(_tree_step(), capacity_log2=4, batch=8, fused=fused)
    r.run([40], acc=jnp.zeros(80, jnp.int32))          # drains instantly
    assert r.stats["drained"] == 1
    with pytest.raises(RuntimeError, match="ring overflow"):
        r.run(np.arange(64), acc=jnp.zeros(80, jnp.int32))
    assert "drained" not in r.stats                    # reset, not stale


def _pri_explode_step():
    def step(acc, keys, vals, valid):
        ck = jnp.broadcast_to(keys[:, None], (keys.shape[0], 4)) + 1
        cv = jnp.broadcast_to(vals[:, None], ck.shape) + 1
        cm = jnp.broadcast_to(valid[:, None], ck.shape)
        return acc, ck.astype(jnp.int32), cv.astype(jnp.int32), cm
    return step


@pytest.mark.parametrize("fused", [True, False])
def test_heap_overflow_raises(fused):
    r = PriorityRoundRunner(_pri_explode_step(), capacity_log2=4, batch=8,
                            fused=fused)
    with pytest.raises(RuntimeError, match="heap overflow"):
        r.run(np.arange(8), np.arange(8), acc=jnp.int32(0), max_rounds=100)


def _immortal_step():
    def step(acc, vals, valid):
        return acc, vals[:, None], valid[:, None]     # every task respawns
    return step


@pytest.mark.parametrize("fused", [True, False])
def test_max_rounds_truncation_raises(fused):
    r = RoundRunner(_immortal_step(), capacity_log2=6, batch=8, fused=fused)
    with pytest.raises(RuntimeError, match="not quiescent"):
        r.run([1, 2, 3], acc=jnp.int32(0), max_rounds=5)
    assert r.stats["drained"] == 0
    assert r.stats["rounds"] == 5


def _pri_immortal_step():
    def step(acc, keys, vals, valid):
        return acc, keys[:, None], vals[:, None], valid[:, None]
    return step


@pytest.mark.parametrize("fused", [True, False])
def test_priority_max_rounds_truncation_raises(fused):
    r = PriorityRoundRunner(_pri_immortal_step(), capacity_log2=6, batch=8,
                            fused=fused)
    with pytest.raises(RuntimeError, match="not quiescent"):
        r.run([1, 2], [1, 2], acc=jnp.int32(0), max_rounds=5)
    assert r.stats["drained"] == 0


# -- wavefaa edge cases -------------------------------------------------------


def test_wavefaa_all_inactive():
    from repro.kernels import wavefaa
    tickets, newctr = wavefaa(jnp.zeros(2048, jnp.int32),
                              jnp.array([123], jnp.int32))
    assert int(newctr[0]) == 123                       # counter untouched
    assert (np.asarray(tickets) == -1).all()


def test_wavefaa_multiblock_smem_carry():
    """The SMEM accumulator must carry the running count across grid
    blocks: lane ranks in block k start at the popcount of blocks < k."""
    from repro.kernels import LANES, wavefaa
    blocks = 3
    active = np.zeros(blocks * LANES, np.int32)
    active[5] = active[LANES + 7] = active[2 * LANES + 11] = 1
    active[LANES - 1] = 1                              # block-boundary lane
    tickets, newctr = wavefaa(jnp.asarray(active), jnp.array([50], jnp.int32))
    t = np.asarray(tickets)
    got = t[active > 0]
    np.testing.assert_array_equal(np.sort(got), np.arange(50, 54))
    assert int(newctr[0]) == 54
    assert t[5] == 50 and t[LANES - 1] == 51           # in-lane order
    assert t[LANES + 7] == 52 and t[2 * LANES + 11] == 53
    assert (t[active == 0] == -1).all()


# -- REPRO_PALLAS_INTERPRET override ------------------------------------------


def test_env_interpret_override(monkeypatch):
    from repro.kernels.pallas_env import env_interpret, resolve_interpret
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert env_interpret() is None
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert env_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False           # explicit flag wins
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "compiled")
    assert env_interpret() is False
    assert resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "banana")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        env_interpret()


def test_env_interpret_reaches_kernels(monkeypatch):
    """With the env forcing interpret mode, the engine path's Pallas kernel
    (``wavefaa``, the one a round runs) resolves to it through the round
    runner and still agrees with the oracle — the flag is plumbed end to
    end, while the ring waves run as plain XLA ops on every backend."""
    from repro.kernels import LANES, ref, wavefaa
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "interpret")
    r = RoundRunner(_tree_step(), capacity_log2=8, batch=16)
    assert r._engine.interpret is True
    acc, _ = r.run([1], acc=jnp.zeros(80, jnp.int32))
    want = np.zeros(80, np.int32)
    want[1:64] = 1                                     # the tree 1..63
    np.testing.assert_array_equal(np.asarray(acc), want)
    active = jnp.asarray((np.arange(2 * LANES) % 3 == 0).astype(np.int32))
    counter = jnp.array([7], jnp.int32)
    for a, b in zip(wavefaa(active, counter), ref.wavefaa_ref(active,
                                                              counter)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- bench acceptance ---------------------------------------------------------


def test_bench_rounds_smoke_parity():
    """The CI gate: fused/legacy bit-parity on fanout + BFS workloads."""
    import io
    from benchmarks.bench_rounds import smoke
    buf = io.StringIO()
    assert smoke(buf), buf.getvalue()
