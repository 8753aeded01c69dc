"""Level-synchronous BFS with queue-managed frontiers (paper § V-B-a).

Two implementations over CSR graphs:

* ``bfs_queue`` — the paper's design: two frontier queues alternate across
  levels; frontier expansion is the Pallas ``frontier_expand`` kernel whose
  next-frontier enqueue is ticket reservation (aggregate-then-commit).
* ``bfs_baseline`` — the Gunrock-style stand-in: dense boolean frontier
  masks with a segment-sum sweep over all vertices per level (no queue) —
  the comparison baseline for benchmarks/bench_bfs.py.

Synthetic graph generators mirror the Table IV families: road-like (low
degree, high diameter), kron/social-like (power-law), delaunay-like
(constant degree).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops


@dataclass
class CSRGraph:
    row_ptr: np.ndarray  # (n+1,) int32
    col_idx: np.ndarray  # (m,) int32
    name: str = "g"

    @property
    def n(self) -> int:
        return len(self.row_ptr) - 1

    @property
    def m(self) -> int:
        return len(self.col_idx)


def road_like(n: int, seed: int = 0) -> CSRGraph:
    """Grid-ish graph: low avg degree, long diameter (road_usa family)."""
    side = int(np.sqrt(n))
    n = side * side
    r, c = np.divmod(np.arange(n, dtype=np.int64), side)
    # (n, 4) neighbour table in (right, down, left, up) order per vertex
    rr = r[:, None] + np.array([0, 1, 0, -1])
    cc = c[:, None] + np.array([1, 0, -1, 0])
    ok = (rr >= 0) & (rr < side) & (cc >= 0) & (cc < side)
    rows = np.broadcast_to(np.arange(n)[:, None], ok.shape)[ok]
    return _to_csr(n, rows, (rr * side + cc)[ok], f"road_{n}")


def kron_like(n: int, avg_deg: int = 16, seed: int = 0) -> CSRGraph:
    """Power-law graph (kron_g500 / hollywood family)."""
    rng = np.random.default_rng(seed)
    m = n * avg_deg
    # preferential-attachment-ish: sample endpoints from a zipf-weighted pool
    w = 1.0 / np.arange(1, n + 1) ** 0.6
    p = w / w.sum()
    src = rng.choice(n, m, p=p)
    dst = rng.choice(n, m, p=p)
    keep = src != dst
    return _to_csr(n, src[keep], dst[keep], f"kron_{n}")


def delaunay_like(n: int, deg: int = 6, seed: int = 0) -> CSRGraph:
    """Constant-degree random graph (delaunay family)."""
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), deg)
    dst = rng.integers(0, n, n * deg)
    return _to_csr(n, src, dst, f"delaunay_{n}")


def _to_csr(n: int, rows, cols, name: str) -> CSRGraph:
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    order = np.argsort(rows, kind="stable")
    rows, cols = rows[order], cols[order]
    row_ptr = np.zeros(n + 1, np.int32)
    np.add.at(row_ptr, rows + 1, 1)
    row_ptr = np.cumsum(row_ptr).astype(np.int32)
    return CSRGraph(row_ptr, cols.astype(np.int32), name)


# ---------------------------------------------------------------------------


def bfs_queue(g: CSRGraph, source: int = 0, *, use_kernel: bool = True
              ) -> Tuple[np.ndarray, Dict]:
    """Queue-driven BFS: alternate two frontier queues across levels."""
    n = g.n
    row_ptr = jnp.asarray(g.row_ptr)
    col_idx = jnp.asarray(g.col_idx)
    visited = jnp.zeros(n, jnp.int32).at[source].set(1)
    dist = np.full(n, -1, np.int32)
    dist[source] = 0
    frontier = jnp.full(max(n, 16), -1, jnp.int32).at[0].set(source)
    level, edges_scanned = 0, 0
    flen = 1
    while flen > 0:
        nxt, cnt, visited = ops.frontier_expand(
            row_ptr, col_idx, frontier, visited, max_out=max(n, 16),
            use_kernel=use_kernel)
        flen = int(cnt[0])
        level += 1
        f_np = np.asarray(nxt[:flen])
        edges_scanned += int(np.sum(g.row_ptr[np.asarray(frontier[frontier >= 0]) + 1]
                                    - g.row_ptr[np.asarray(frontier[frontier >= 0])]))
        dist[f_np] = level
        frontier = nxt
    return dist, {"levels": level, "edges_scanned": edges_scanned}


def bfs_baseline(g: CSRGraph, source: int = 0) -> Tuple[np.ndarray, Dict]:
    """Gunrock-style dense sweep: per level, scatter frontier over all edges
    with a boolean mask (no queue, no compaction)."""
    n = g.n
    row_ptr, col_idx = g.row_ptr, g.col_idx
    # edge source vector
    src = np.repeat(np.arange(n, dtype=np.int32),
                    np.diff(row_ptr).astype(np.int64))
    src_j = jnp.asarray(src)
    col_j = jnp.asarray(col_idx)
    front = jnp.zeros(n, jnp.bool_).at[source].set(True)
    visited = front
    dist = np.full(n, -1, np.int32)
    dist[source] = 0
    level = 0

    @jax.jit
    def sweep(front, visited):
        active = front[src_j]
        touched = jnp.zeros(n, jnp.bool_).at[col_j].max(active)
        new = touched & (~visited)
        return new, visited | new

    while bool(front.any()):
        front, visited = sweep(front, visited)
        level += 1
        newly = np.asarray(front)
        dist[newly & (dist == -1)] = level
        if not newly.any():
            break
    return dist, {"levels": level}


def bfs_runtime(g: CSRGraph, source: int = 0, *, algo: str = "glfq",
                shards: int = 4, workers: int = 16, steal: bool = True,
                policy: str = "gang", seed: int = 0
                ) -> Tuple[np.ndarray, Dict]:
    """Task-runtime BFS: frontier expansion as dynamically spawned tasks on
    the sharded fabric (DESIGN.md § 4.6).

    One task = relax one vertex; its handler scans the adjacency list
    (simulated cost = degree, so power-law graphs yield power-law task
    costs) and spawns a child for every neighbour whose tentative distance
    improves (the handler runs atomically between simulator instructions —
    the host stand-in for an atomic min on the distance array).  Unlike
    ``bfs_queue`` there is no level barrier: the fabric's interleaving may
    discover a vertex via a long path first, and the asynchronous relaxation
    re-spawns it when a shorter path arrives — distances are exact at
    quiescence (monotone label-correcting, Wang et al.'s dynamic
    load-balancing discipline), while the *fabric* still executes every
    spawned task exactly once."""
    from ..runtime import ExecutorConfig, TaskFabric, TaskRuntime, TaskSpec

    dist = np.full(g.n, -1, np.int32)
    dist[source] = 0
    edges_scanned = 0

    def handler(rec):
        nonlocal edges_scanned
        v = rec.payload
        dv = int(dist[v])
        lo, hi = int(g.row_ptr[v]), int(g.row_ptr[v + 1])
        edges_scanned += hi - lo
        children = []
        for w in g.col_idx[lo:hi]:
            w = int(w)
            nd = dv + 1
            if dist[w] < 0 or nd < dist[w]:   # atomic relax (host = one step)
                dist[w] = nd
                deg_w = int(g.row_ptr[w + 1]) - int(g.row_ptr[w])
                children.append(TaskSpec(w, cost=max(deg_w, 1)))
        return children

    fabric = TaskFabric(algo=algo, shards=shards,
                        capacity_per_shard=max(2 * g.n // max(shards, 1), 64),
                        num_threads=workers + 1, steal=steal)
    rt = TaskRuntime(fabric, handler,
                     ExecutorConfig(workers=workers, policy=policy, seed=seed,
                                    max_steps=50_000_000))
    rt.add_task(source,
                cost=max(int(g.row_ptr[source + 1]) - int(g.row_ptr[source]), 1))
    metrics = rt.run()
    info = {"tasks": len(rt.executed), "edges_scanned": edges_scanned,
            "steal_rate": metrics["steal_rate"],
            "idle_steps": metrics["idle_steps"],
            "load_imbalance": metrics["load_imbalance"],
            "throughput_ops_per_kstep": metrics["throughput_ops_per_kstep"]}
    return dist, info


def neighbour_table(g: CSRGraph, values=None, fill: int = -1):
    """Out-neighbour rows of ``g`` as a flat ``(n * fan,)`` device table,
    ``fan`` the max out-degree: row ``v`` holds ``values`` (default the
    column ids) of v's edges in CSR order, padded with ``fill``.  Flat,
    not ``(n, fan)``: the TPU tiles a 2-D array's minor dimension to 128
    lanes, so a 4-wide table would sit 32 times over in the program's
    constants.  Returns ``(table, fan)``; ``table_rows`` reads it."""
    n = g.n
    deg = np.diff(g.row_ptr).astype(np.int64)
    fan = max(int(deg.max()) if n else 0, 1)
    rows = np.repeat(np.arange(n, dtype=np.int64), deg)
    pos = np.arange(g.m) - np.repeat(g.row_ptr[:-1].astype(np.int64), deg)
    table = np.full(n * fan, fill, np.int32)
    table[rows * fan + pos] = g.col_idx if values is None else values
    return jnp.asarray(table), fan


def table_rows(table, v, fan: int):
    """Rows ``v`` (B,) of a ``neighbour_table``, as a (B, fan) block."""
    return table[v[:, None] * fan + jnp.arange(fan, dtype=jnp.int32)]


def bfs_rounds_runner(g: CSRGraph, *, batch: int = 64, fused: bool = True,
                      interpret=None, sync_every: int = 0, telemetry=None,
                      compact=None):
    """Build the round-engine BFS runner for ``g`` (see ``bfs_rounds``).
    Returns ``(runner, init_fn)`` where ``init_fn(source)`` produces the
    distance accumulator — callers that run BFS repeatedly (benchmarks)
    reuse the runner to amortize the megaround compilation."""
    from ..runtime import RoundRunner

    n = g.n
    nbr, fan = neighbour_table(g)
    big = np.iinfo(np.int32).max

    def step(dist, vals, valid):
        v = jnp.where(valid, vals, 0)
        dv = jnp.where(valid, dist[v], 0)
        w = jnp.where(valid[:, None], table_rows(nbr, v, fan), -1)  # (B, F)
        wc = jnp.clip(w, 0, n - 1)
        eligible = (w >= 0) & (dist[wc] < 0)
        b, f = w.shape
        wf = w.reshape(-1)
        elig_f = eligible.reshape(-1)
        tgt = jnp.where(elig_f, wf, n)                       # n = trash slot
        order = jnp.arange(b * f, dtype=jnp.int32)
        claim = jnp.full((n + 1,), big, jnp.int32).at[tgt].min(order)
        win = elig_f & (claim[tgt] == order)                 # first parent
        ndist = jnp.repeat(dv + 1, f)
        dist = dist.at[jnp.where(win, wf, n)].set(ndist, mode="drop")
        return dist, wc, win.reshape(b, f)

    capacity_log2 = max(int(np.ceil(np.log2(max(n + 1, 2 * batch)))), 4)
    runner = RoundRunner(step, capacity_log2=capacity_log2, batch=batch,
                         fused=fused, interpret=interpret,
                         sync_every=sync_every, telemetry=telemetry,
                         compact=compact)

    def init_fn(source: int):
        return jnp.full((n,), -1, jnp.int32).at[source].set(0)

    return runner, init_fn


def bfs_rounds(g: CSRGraph, source: int = 0, *, batch: int = 64,
               fused: bool = True, interpret=None, sync_every: int = 0,
               max_rounds: int = 100_000) -> Tuple[np.ndarray, Dict]:
    """BFS on the deterministic round engine (DESIGN.md § 4.3): the ring
    carries vertex ids, one jitted step relaxes a batch of vertices against
    a dense padded adjacency table and spawns the neighbours it newly
    claims.  Within a batch, a vertex reached by several parents goes to
    the row-major-first parent (a scatter-min claim) — the batched analogue
    of the sequential queue's first-visit rule, so distances are exact.

    ``fused=True`` (default) runs the whole loop device-resident with host
    sync only at quiescence; ``fused=False`` is the legacy per-round path.
    Both are bit-identical."""
    runner, init_fn = bfs_rounds_runner(g, batch=batch, fused=fused,
                                        interpret=interpret,
                                        sync_every=sync_every)
    dist, _ = runner.run([source], acc=init_fn(source),
                         max_rounds=max_rounds)
    return np.asarray(dist), dict(runner.stats)


def bfs_mesh_rounds_runner(g: CSRGraph, *, mesh=None, shards: int = None,
                           axis: str = "data", batch: int = 64,
                           fused: bool = True, sync_every: int = 0,
                           capacity_log2: int = None, telemetry=None,
                           compact=None):
    """Build the *mesh*-scope BFS runner (DESIGN.md § 2.3): frontier
    vertices flow through the replicated distqueue, each shard steps its
    claimed slice of the round, and children publish back with one psum
    per round.  Returns ``(runner, seeds, init_fn)``.

    The queue payload packs ``(distance, vertex)`` as ``d·n + v`` so a
    claim is self-contained — a shard can relax a vertex it has never seen
    (its local label array is stale for vertices other shards claimed).
    The step is asynchronous label-correcting: a claim expands only if its
    distance improves the shard's local label, and per-shard labels are
    min-combined at quiescence, which converges to exact BFS distances
    (every shortest-path prefix is claimed *somewhere* with its true
    distance and re-published on improvement).  Returns
    ``(runner, init_fn)`` where ``init_fn(source)`` builds the label
    accumulator."""
    from ..jaxcompat import make_mesh
    from ..runtime import MeshRoundRunner

    n = g.n
    if mesh is None:
        shards = shards or len(jax.devices())
        mesh = make_mesh((shards,), (axis,))
    nshards = int(mesh.shape[axis])
    if n * (n + 2) >= 2 ** 31:
        raise ValueError(f"graph too large for packed (d, v) payloads: "
                         f"n={n} needs n*(n+2) < 2^31")
    nbr, fan = neighbour_table(g)
    # the in-batch winner key is nd·(batch·fan) + order, nd ≤ n
    if (n + 1) * batch * fan >= 2 ** 31:
        raise ValueError(f"batch {batch} x max degree {fan} too wide for "
                         f"int32 winner keys on n={n}: needs "
                         f"(n+1)*batch*fan < 2^31")
    big = np.iinfo(np.int32).max

    def step(dist, vals, valid):
        b = vals.shape[0]
        v = jnp.where(valid, vals % n, 0)
        d = jnp.where(valid, vals // n, 0)
        # expand unless the local label already beats the claim (labels are
        # real path lengths ≥ the true distance, so a true-distance claim
        # is never stale; ``==`` claims re-expand but spawn only improving
        # children, which keeps the recursion finite)
        fresh = valid & (d <= dist[v])
        dist = dist.at[jnp.where(fresh, v, n)].min(d, mode="drop")
        w = jnp.where(fresh[:, None], table_rows(nbr, v, fan), -1)  # (B, F)
        wc = jnp.clip(w, 0, n - 1)
        nd = jnp.broadcast_to((d + 1)[:, None], w.shape)
        elig = (w >= 0) & (nd < dist[wc])
        # in-batch winner per target: smallest nd, then row-major order
        bf = b * w.shape[1]
        order = jnp.arange(bf, dtype=jnp.int32)
        key = nd.reshape(-1) * bf + order
        ef, wf, ndf = elig.reshape(-1), w.reshape(-1), nd.reshape(-1)
        tgt = jnp.where(ef, wf, n)
        claim = jnp.full((n + 1,), big, jnp.int32).at[tgt].min(
            jnp.where(ef, key, big))
        win = ef & (claim[tgt] == key)
        dist = dist.at[jnp.where(win, wf, n)].min(ndf, mode="drop")
        cv = jnp.where(win, ndf * n + jnp.clip(wf, 0, n - 1), 0)
        return dist, cv.reshape(w.shape), win.reshape(w.shape)

    def combine(stacked):                              # (shards, n) labels
        m = stacked.min(0)
        return jnp.where(m == big, -1, m)

    if capacity_log2 is None:
        capacity_log2 = max(
            int(np.ceil(np.log2(max(2 * n * nshards, 4 * batch * nshards)))),
            4)
    runner = MeshRoundRunner(step, mesh=mesh, axis=axis,
                             capacity_log2=capacity_log2, batch=batch,
                             fused=fused, sync_every=sync_every,
                             combine=combine, telemetry=telemetry,
                             compact=compact)

    def init_fn(source: int):
        # all labels unvisited (BIG) — the source's 0 arrives via its seed
        # claim (pre-setting it would make that claim non-improving and
        # suppress the very first expansion)
        del source
        return jnp.full((n,), big, jnp.int32)

    return runner, init_fn


def bfs_mesh_rounds(g: CSRGraph, source: int = 0, *, mesh=None,
                    shards: int = None, batch: int = 64, fused: bool = True,
                    sync_every: int = 0, max_rounds: int = 100_000
                    ) -> Tuple[np.ndarray, Dict]:
    """BFS on the mesh-fused round engine across ≥1 shards: exact distances
    at quiescence, host sync only at quiescence when ``fused=True``."""
    runner, init_fn = bfs_mesh_rounds_runner(g, mesh=mesh, shards=shards,
                                             batch=batch, fused=fused,
                                             sync_every=sync_every)
    dist, _ = runner.run([source], acc=init_fn(source),
                         max_rounds=max_rounds)
    return np.asarray(dist), dict(runner.stats)


def bfs_reference(g: CSRGraph, source: int = 0) -> np.ndarray:
    """Plain numpy BFS oracle: level-synchronous frontier sweeps over the
    CSR arrays (-1 marks unreachable vertices)."""
    dist = np.full(g.n, -1, np.int32)
    dist[source] = 0
    row_ptr = g.row_ptr.astype(np.int64)
    frontier = np.array([source], np.int64)
    level = 0
    while frontier.size:
        lo, hi = row_ptr[frontier], row_ptr[frontier + 1]
        deg = hi - lo
        # edge slots of every frontier vertex, concatenated
        edges = (np.arange(deg.sum()) - np.repeat(np.cumsum(deg) - deg, deg)
                 + np.repeat(lo, deg))
        nbr = g.col_idx[edges]
        frontier = np.unique(nbr[dist[nbr] < 0]).astype(np.int64)
        level += 1
        dist[frontier] = level
    return dist
