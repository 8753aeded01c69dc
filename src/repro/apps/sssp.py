"""Delta-stepping single-source shortest paths on the priority mesh
rounds (DESIGN.md § 6) — the canonical priority-queue graph workload
(Chen et al.'s concurrent-heap case study, Wang et al.'s relaxed-order
load balancing), run through ``PriorityMeshRoundRunner``.

The queue carries ``(key, payload)`` pairs: the payload packs a tentative
distance claim as ``d·n + v`` (self-contained, like mesh BFS — a shard
can relax a vertex it has never seen), and the key is the delta-stepping
bucket ``d // delta``, so pops drain the lowest-distance buckets first.
The step is asynchronous label-correcting: a claim expands only if its
distance still improves (or matches) the shard's local label, children
are published only for strictly improving relaxations, and per-shard
labels are min-combined at quiescence.  Correctness therefore does NOT
depend on pop order — strict, k-relaxed, or adversarial order all
converge to exact Dijkstra distances (every shortest-path prefix is
claimed somewhere with its true distance and re-published on
improvement); priority order only bounds the *wasted* re-relaxations, so
``delta`` and ``relaxed`` trade queue pressure against round count
exactly as in CPU delta-stepping.

Determinism: the whole run is bit-deterministic for a fixed (graph,
source, mesh, batch, delta, relaxed) configuration — both engines
(``fused=True``/``False``) produce identical labels, heap planes, and
stats, asserted by tests.

Exactness is asserted against the ``dijkstra_reference`` heapq oracle on
road-like and kron-like weighted graphs at 1/2/4 shards.
"""

from __future__ import annotations

import heapq
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bfs import CSRGraph, neighbour_table, table_rows

BIG = np.iinfo(np.int32).max


def with_weights(g: CSRGraph, max_w: int = 8, seed: int = 0) -> np.ndarray:
    """Integer edge weights in ``[1, max_w]`` aligned with ``g.col_idx``."""
    rng = np.random.default_rng(seed)
    return rng.integers(1, max_w + 1, g.m).astype(np.int32)


def dijkstra_reference(g: CSRGraph, weights: np.ndarray,
                       source: int = 0) -> np.ndarray:
    """Plain heapq Dijkstra oracle; -1 marks unreachable vertices."""
    row_ptr, col_idx = g.row_ptr.tolist(), g.col_idx.tolist()
    weights = np.asarray(weights).tolist()
    dist = [-1] * g.n
    dist[source] = 0
    pq = [(0, source)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist[u]:
            continue
        for k in range(row_ptr[u], row_ptr[u + 1]):
            v = col_idx[k]
            nd = d + weights[k]
            if dist[v] < 0 or nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (nd, v))
    return np.asarray(dist, np.int64).astype(np.int32)


def sssp_mesh_rounds_runner(g: CSRGraph, weights: np.ndarray, *, mesh=None,
                            shards: int = None, axis: str = "data",
                            batch: int = 64, delta: int = 4,
                            relaxed: bool = True, fused: bool = True,
                            sync_every: int = 0, capacity_log2: int = None,
                            trace: bool = False, telemetry=None,
                            spans=None, compact=None,
                            split_payload: bool = False):
    """Build the priority-mesh SSSP runner for ``(g, weights)``.  Returns
    ``(runner, init_fn)`` where ``init_fn(source)`` builds the label
    accumulator and the source's seed is ``(key=0, payload=source)`` —
    callers that run SSSP repeatedly (benchmarks) reuse the runner to
    amortize the megaround compilation.

    ``relaxed=True`` pops per-shard local minima under the hint-ordered
    claim schedule (k-relaxed, ``sched.relaxed.mesh_relaxation_bound``);
    ``relaxed=False`` pops exact global bucket order from the replicated
    heap.  Both are exact at quiescence; ``fused`` picks host sync at
    quiescence vs per round (bit-identical engines).

    ``split_payload=True`` switches the queue to the two-plane
    ``(key, payload)`` layout: the payload carries the bare vertex id and
    the exact tentative distance rides the heap's aux rider plane, so
    nothing packs into ``d·n + v`` and the ``(max_d + max_w)·n < 2^31``
    packed cap disappears — only the distances themselves must stay below
    ``2^31``.  Seed with ``runner.run([0], [source], ...,
    initial_aux=[0])``.  Mutually exclusive with ``spans``;
    ``trace``/legacy still work (the aux plane threads the per-round
    state)."""
    from ..jaxcompat import make_mesh
    from ..runtime import PriorityMeshRoundRunner

    n = g.n
    if mesh is None:
        shards = shards or len(jax.devices())
        mesh = make_mesh((shards,), (axis,))
    weights = np.asarray(weights, np.int32)
    assert weights.shape == (g.m,)
    max_w = int(weights.max()) if g.m else 1
    # any finite tentative distance is a real path length ≤ (n-1)·max_w
    max_d = (n - 1) * max_w
    if split_payload:
        # two-plane layout: only the raw distances must fit in int32
        if max_d + max_w >= 2 ** 31:
            raise ValueError(
                f"graph too large even for split payloads: n={n}, "
                f"max_w={max_w} needs (n-1)*max_w + max_w < 2^31")
    elif (max_d + max_w) * n + (n - 1) >= 2 ** 31:
        raise ValueError(
            f"graph too large for packed (d, v) payloads: n={n}, "
            f"max_w={max_w} needs ((n-1)*max_w + max_w)*n + n < 2^31 "
            f"(use split_payload=True for the two-plane layout)")
    if delta < 1:
        raise ValueError(f"delta must be >= 1, got {delta}")
    nbr, fan = neighbour_table(g)
    wgt, _ = neighbour_table(g, weights, fill=0)

    def _relax(dist, v, d, valid):
        """Shared label-correcting core: claim (v, d) pairs in, winning
        child relaxations ``(dist, ck, wf, ndf, win, shape)`` out."""
        b = v.shape[0]
        # expand unless the local label already beats the claim (labels are
        # real path lengths ≥ the true distance, so a true-distance claim
        # is never stale; ``==`` claims re-expand but spawn only improving
        # children, which keeps the recursion finite)
        fresh = valid & (d <= dist[v])
        dist = dist.at[jnp.where(fresh, v, n)].min(d, mode="drop")
        w = jnp.where(fresh[:, None], table_rows(nbr, v, fan), -1)  # (B, F)
        wc = jnp.clip(w, 0, n - 1)
        nd = d[:, None] + table_rows(wgt, v, fan)
        elig = (w >= 0) & (nd < dist[wc])
        # in-batch winner per target: smallest nd, then row-major order —
        # two scatter-mins, so no packed winner key to overflow
        ef = elig.reshape(-1)
        wf = w.reshape(-1)
        ndf = nd.reshape(-1)
        tgt = jnp.where(ef, wf, n)
        claim_nd = jnp.full((n + 1,), BIG, jnp.int32).at[tgt].min(
            jnp.where(ef, ndf, BIG))
        tie = ef & (claim_nd[tgt] == ndf)
        order = jnp.arange(b * w.shape[1], dtype=jnp.int32)
        claim_ord = jnp.full((n + 1,), BIG, jnp.int32).at[tgt].min(
            jnp.where(tie, order, BIG))
        win = tie & (claim_ord[tgt] == order)
        dist = dist.at[jnp.where(win, wf, n)].min(ndf, mode="drop")
        ck = jnp.where(win, ndf // delta, 0)
        return dist, ck, wf, ndf, win, w.shape

    def step(dist, keys, payloads, valid):
        del keys                                  # bucket only orders pops
        p = jnp.where(valid, payloads, 0)
        dist, ck, wf, ndf, win, shape = _relax(dist, p % n, p // n, valid)
        cv = jnp.where(win, ndf * n + jnp.clip(wf, 0, n - 1), 0)
        return (dist, ck.reshape(shape), cv.reshape(shape),
                win.reshape(shape))

    def step_split(dist, keys, payloads, aux, valid):
        del keys                                  # bucket only orders pops
        v = jnp.where(valid, payloads, 0)         # bare vertex plane
        d = jnp.where(valid, aux, 0)              # exact distance rider
        dist, ck, wf, ndf, win, shape = _relax(dist, v, d, valid)
        cv = jnp.where(win, jnp.clip(wf, 0, n - 1), 0)
        ca = jnp.where(win, ndf, 0)
        return (dist, ck.reshape(shape), cv.reshape(shape),
                ca.reshape(shape), win.reshape(shape))

    def combine(stacked):                        # (shards, n) labels
        m = stacked.min(0)
        return jnp.where(m == BIG, -1, m)

    nshards = int(mesh.shape[axis])
    if capacity_log2 is None:
        per_shard = max(4 * n // max(nshards, 1), 4 * batch, 16)
        capacity_log2 = int(np.ceil(np.log2(per_shard)))
        if not relaxed:
            capacity_log2 = int(np.ceil(np.log2(
                max(4 * n, 4 * batch * nshards, 16))))
    runner = PriorityMeshRoundRunner(step_split if split_payload else step,
                                     mesh=mesh, axis=axis,
                                     capacity_log2=capacity_log2,
                                     batch=batch, relaxed=relaxed,
                                     fused=fused, sync_every=sync_every,
                                     combine=combine, trace=trace,
                                     telemetry=telemetry, spans=spans,
                                     compact=compact, split=split_payload)

    def init_fn(source: int):
        # all labels unvisited (BIG) — the source's 0 arrives via its seed
        # claim (pre-setting it would make that claim non-improving and
        # suppress the very first expansion)
        del source
        return jnp.full((n,), BIG, jnp.int32)

    return runner, init_fn


def sssp_mesh_rounds(g: CSRGraph, weights: np.ndarray, source: int = 0, *,
                     mesh=None, shards: int = None, batch: int = 64,
                     delta: int = 4, relaxed: bool = True,
                     fused: bool = True, sync_every: int = 0,
                     compact=None, split_payload: bool = False,
                     max_rounds: int = 100_000) -> Tuple[np.ndarray, Dict]:
    """Delta-stepping SSSP on the priority mesh engine across ≥1 shards:
    exact Dijkstra distances at quiescence, host sync only at quiescence
    when ``fused=True``.  Returns ``(dist, stats)``."""
    runner, init_fn = sssp_mesh_rounds_runner(
        g, weights, mesh=mesh, shards=shards, batch=batch, delta=delta,
        relaxed=relaxed, fused=fused, sync_every=sync_every,
        compact=compact, split_payload=split_payload)
    kw = {"initial_aux": [0]} if split_payload else {}
    dist, _ = runner.run([0], [source], acc=init_fn(source),
                         max_rounds=max_rounds, **kw)
    return np.asarray(dist), dict(runner.stats)
