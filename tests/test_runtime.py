"""Task-runtime invariants (DESIGN.md § 4):

* every spawned task executes exactly once — no loss, no duplication —
  under random/gang/rr interleaving, with and without stealing, for all
  four queue algorithms;
* every (lane, shard) ring history is independently linearizable
  (``check_linearizable``), since shards are plain bounded FIFO rings;
* priority lanes actually pre-empt: urgent tasks finish ahead of normal
  ones under a single-consumer drain;
* the JAX round face is bit-deterministic across reruns and processes each
  seeded/spawned value exactly once;
* the mesh-scope round (``mesh_task_round``) grants and claims FIFO at a
  single-device mesh;
* the rewired apps agree with their references;
* the bench_runtime acceptance comparison holds: ≥32 workers under
  power-law costs, sharded+stealing beats the single shared queue on
  throughput and idle-steps.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

from repro.core import QUEUE_CLASSES, check_linearizable
from repro.runtime import (ExecutorConfig, RoundRunner, TaskFabric,
                           TaskRuntime, TaskSpec)

ALGOS = list(QUEUE_CLASSES)


def _tree_runtime(algo, policy, *, steal=True, workers=8, shards=2,
                  depth=4, roots=2, seed=0):
    """Binary-tree spawn workload: roots at depth d, every task spawns two
    children until depth 0 — total roots·(2^(d+1)−1) tasks."""
    def handler(rec):
        d = rec.payload
        if d <= 0:
            return []
        return [TaskSpec(d - 1, cost=1, priority=1),
                TaskSpec(d - 1, cost=1, priority=1)]

    fabric = TaskFabric(algo=algo, shards=shards, capacity_per_shard=128,
                        num_threads=workers + 1, steal=steal)
    rt = TaskRuntime(fabric, handler,
                     ExecutorConfig(workers=workers, policy=policy, seed=seed))
    for _ in range(roots):
        rt.add_task(depth, cost=1)
    metrics = rt.run()
    total = roots * (2 ** (depth + 1) - 1)
    return rt, fabric, metrics, total


@pytest.mark.parametrize("algo", ALGOS)
@pytest.mark.parametrize("policy", ["random", "gang", "rr"])
def test_exactly_once_and_linearizable(algo, policy):
    rt, fabric, metrics, total = _tree_runtime(algo, policy, seed=7)
    assert metrics["completed"] == 1.0, "runtime did not reach quiescence"
    ids = [t for t, _ in rt.executed]
    assert len(ids) == total, f"lost tasks: {len(ids)}/{total}"
    assert len(set(ids)) == len(ids), "a task executed twice"
    for key, hist in fabric.shard_history.items():
        res = check_linearizable(hist)
        assert res.ok, f"shard {key} history not linearizable: {res.reason}"


@pytest.mark.parametrize("algo", ["glfq", "sfq"])
def test_exactly_once_without_stealing(algo):
    rt, fabric, metrics, total = _tree_runtime(algo, "random", steal=False,
                                               seed=3)
    assert metrics["completed"] == 1.0
    ids = [t for t, _ in rt.executed]
    assert len(ids) == total and len(set(ids)) == len(ids)
    assert metrics["steals"] == 0


def test_stealing_engages_under_affinity_skew():
    """All arrivals pinned to one shard: workers homed elsewhere must steal
    (and without stealing those tasks would be unreachable for them)."""
    fabric = TaskFabric(algo="glfq", shards=2, capacity_per_shard=128,
                        num_threads=17, steal=True)
    rt = TaskRuntime(fabric, lambda rec: [],
                     ExecutorConfig(workers=16, policy="gang", seed=0))
    for i in range(64):
        rt.add_task(i, cost=4, affinity=0)
    m = rt.run()
    assert m["completed"] == 1.0
    assert m["steals"] > 0
    assert m["steal_rate"] > 0.02


def test_priority_lane_preempts():
    """Single consumer stuck in a long warmup task while both lanes fill:
    on resume it must drain the entire urgent lane first."""
    fabric = TaskFabric(algo="glfq", shards=1, capacity_per_shard=128,
                        num_threads=2, steal=False)
    rt = TaskRuntime(fabric, lambda rec: [],
                     ExecutorConfig(workers=1, policy="rr", seed=0))
    rt.add_task(("warmup", 0), priority=0, cost=2000)
    for i in range(12):
        rt.add_task(("lo", i), priority=1, cost=1)
    for i in range(12):
        rt.add_task(("hi", i), priority=0, cost=1)
    m = rt.run()
    assert m["completed"] == 1.0
    order = [fabric.tasks[t].payload[0] for t, _ in rt.executed
             if fabric.tasks[t].payload[0] != "warmup"]
    assert order[:12] == ["hi"] * 12, order


def test_executor_metrics_shape():
    _, _, m, _ = _tree_runtime("gwfq", "gang", seed=1)
    for key in ("throughput_ops_per_kstep", "idle_steps", "steal_rate",
                "load_imbalance", "worker_imbalance", "tasks_executed",
                "steps_per_op", "stall_steps_per_op"):
        assert key in m, key
    assert m["tasks_executed"] > 0
    assert m["idle_steps"] >= 0


# -- JAX face ----------------------------------------------------------------


def _tree_step():
    import jax.numpy as jnp

    def step(acc, vals, valid):
        acc = acc.at[jnp.where(valid, vals, 0)].add(
            valid.astype(jnp.int32))
        cv = jnp.stack([vals * 2, vals * 2 + 1], -1).astype(jnp.int32)
        cm = (valid & (vals < 8))[:, None]
        return acc, cv, cm
    return step


def test_rounds_exactly_once_and_deterministic():
    jnp = pytest.importorskip("jax.numpy")
    runner = RoundRunner(_tree_step(), capacity_log2=8, batch=16)
    acc, st = runner.run([1], acc=jnp.zeros(32, jnp.int32))
    counts = np.asarray(acc)
    # tasks 1..15 processed exactly once each
    assert counts[1:16].tolist() == [1] * 15
    assert counts[16:].sum() == 0 and counts[0] == 0
    assert runner.stats["drained"] == 1
    assert runner.stats["processed"] == 15
    # bit-determinism across reruns (fresh runner, same inputs)
    runner2 = RoundRunner(_tree_step(), capacity_log2=8, batch=16)
    acc2, st2 = runner2.run([1], acc=jnp.zeros(32, jnp.int32))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(acc2))
    for a, b in zip(st[:4], st2[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (st.head, st.tail) == (st2.head, st2.tail)
    assert runner.stats == runner2.stats


def test_mesh_task_round_single_device():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.distqueue import dist_queue_init
    from repro.jaxcompat import make_mesh
    from repro.runtime import mesh_task_round

    mesh = make_mesh((1,), ("data",))

    def inner(state, values, emask, want):
        return mesh_task_round(state, values, emask, want, "data")

    # replication checker ON: the psum-gathered rounds keep the replicated
    # planes replicated-typed (no check_vma=False escape hatch)
    io = (P(), P("data"), P("data"), P("data"))
    f = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=io, out_specs=io))
    state = dist_queue_init(16)
    vals = jnp.asarray([11, 12, 13, 14], jnp.int32)
    ones = jnp.ones(4, jnp.int32)
    state, granted, got, ok = f(state, vals, ones, ones)
    assert bool(granted.all()) and bool(ok.all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vals))  # FIFO


# -- rewired consumers --------------------------------------------------------


def test_bfs_runtime_matches_reference():
    from repro.apps import bfs
    g = bfs.kron_like(200, avg_deg=6, seed=2)
    ref = bfs.bfs_reference(g, 0)
    for algo in ("glfq", "sfq"):
        dist, info = bfs.bfs_runtime(g, 0, algo=algo, shards=2, workers=8,
                                     policy="random", seed=5)
        np.testing.assert_array_equal(dist, ref)
        assert info["tasks"] >= int((ref >= 0).sum()) - 1


def test_render_runtime_matches_queue():
    from repro.apps import raytrace
    scene = raytrace.cornell_scene()
    img_q, _ = raytrace.render_queue(scene, w=16, h=16)
    img_r, info = raytrace.render_runtime(scene, w=16, h=16, workers=4,
                                          shards=2, seed=1)
    np.testing.assert_allclose(img_r, img_q, rtol=1e-5, atol=1e-5)
    assert info["rays"] > 0 and info["tasks"] > 0


def test_engine_priority_admission():
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serving.engine import EngineConfig, Request, ServingEngine
    cfg = get_config("h2o-danube-1.8b").reduced()
    eng = ServingEngine(cfg, init_params(cfg),
                        EngineConfig(max_slots=1, page_size=16, num_pages=8,
                                     max_seq=64))
    rng = np.random.default_rng(0)

    def req(rid, pri):
        return Request(rid=rid,
                       prompt=rng.integers(0, cfg.vocab, 4).astype(np.int32),
                       max_new_tokens=2, priority=pri)

    for rid in range(4):
        assert eng.submit(req(rid, 1))
    for rid in (100, 101):
        assert eng.submit(req(rid, 0))
    m = eng.run(max_ticks=400)
    assert m["completed"] == 6
    # urgent lane admitted first despite arriving last (single slot)
    assert set(eng.admission_log[:2]) == {100, 101}, eng.admission_log


# -- bench acceptance ---------------------------------------------------------


def test_bench_runtime_acceptance_powerlaw():
    """≥32 sim workers, power-law task costs: sharded+stealing strictly
    beats the single shared queue on throughput and idle-steps."""
    from benchmarks.bench_runtime import run_scenario
    single = run_scenario("powerlaw", "glfq", "single", 1, False,
                          workers=32, n_tasks=96)
    fabric = run_scenario("powerlaw", "glfq", "sharded+steal", 4, True,
                          workers=32, n_tasks=96)
    assert fabric["throughput_ops_per_kstep"] > single["throughput_ops_per_kstep"]
    assert fabric["idle_steps"] < single["idle_steps"]
