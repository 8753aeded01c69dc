"""The road-network stand-in and its plain references.

The graph is a ``side x side`` grid: every vertex has arcs to its right,
lower, left and upper neighbours, in that order, where they exist.  So it
is planar, of degree at most four and of diameter ``2 (side - 1)``, like
the DIMACS USA road graphs.  Arc weights, where a cell has them, are
integers drawn uniformly from ``[w_min, w_max]``, one per undirected
edge.

The references (level-synchronous BFS, heap Dijkstra) are written from
the definitions and share nothing with the program under test.
"""

from __future__ import annotations

import heapq
from typing import NamedTuple

import numpy as np

from bench.seeds import stream


class Grid(NamedTuple):
    side: int
    row_ptr: np.ndarray     # (n + 1,) int32
    col_idx: np.ndarray     # (arcs,) int32

    @property
    def n(self) -> int:
        return self.side * self.side

    @property
    def arcs(self) -> int:
        return len(self.col_idx)


def grid(side: int) -> Grid:
    """CSR arrays of the ``side x side`` grid, arcs of each vertex in
    (right, down, left, up) order."""
    n = side * side
    r, c = np.divmod(np.arange(n, dtype=np.int64), side)
    rr = r[:, None] + np.array([0, 1, 0, -1])
    cc = c[:, None] + np.array([1, 0, -1, 0])
    ok = (rr >= 0) & (rr < side) & (cc >= 0) & (cc < side)
    row_ptr = np.zeros(n + 1, np.int64)
    row_ptr[1:] = np.cumsum(ok.sum(1))
    col_idx = (rr * side + cc)[ok]          # row-major: rows stay sorted
    return Grid(side, row_ptr.astype(np.int32), col_idx.astype(np.int32))


def grid_edges(side: int) -> int:
    """Undirected edges of the ``side x side`` grid (closed form)."""
    return 2 * side * (side - 1)


def vertex(side: int, rc) -> int:
    """Vertex id of grid point ``rc = (row, col)``."""
    r, c = int(rc[0]), int(rc[1])
    if not (0 <= r < side and 0 <= c < side):
        raise ValueError(f"root {rc} is off the {side}x{side} grid")
    return r * side + c


def root_order(side: int, roots, seed: int) -> np.ndarray:
    """The cell's roots (``[row, col]`` pairs) as vertex ids, in an order
    drawn from ``seed``: every seed searches from the same roots."""
    ids = np.array([vertex(side, rc) for rc in roots], np.int64)
    return ids[stream(seed, 1).permutation(len(ids))]


def edge_weights(g: Grid, w_min: int, w_max: int,
                 rng: np.random.Generator) -> np.ndarray:
    """One integer weight per arc, uniform in ``[w_min, w_max]`` and drawn
    independently for each undirected edge: an arc and its reverse weigh
    the same, as a road does both ways."""
    src = np.repeat(np.arange(g.n, dtype=np.int64), np.diff(g.row_ptr))
    dst = g.col_idx.astype(np.int64)
    edge = np.minimum(src, dst) * g.n + np.maximum(src, dst)
    _, idx = np.unique(edge, return_inverse=True)
    raw = rng.integers(w_min, w_max + 1, idx.max() + 1 if idx.size else 0)
    return raw[idx].astype(np.int32)


def bfs_levels(g: Grid, root: int) -> np.ndarray:
    """Level-synchronous BFS: hop distance from ``root``, -1 if
    unreachable."""
    dist = np.full(g.n, -1, np.int32)
    dist[root] = 0
    row_ptr = g.row_ptr.astype(np.int64)
    frontier = np.array([root], np.int64)
    level = 0
    while frontier.size:
        lo = row_ptr[frontier]
        deg = row_ptr[frontier + 1] - lo
        starts = np.repeat(lo - np.cumsum(deg) + deg, deg)
        nbr = g.col_idx[starts + np.arange(deg.sum())]
        nbr = np.unique(nbr[dist[nbr] < 0])
        level += 1
        dist[nbr] = level
        frontier = nbr.astype(np.int64)
    return dist


def dijkstra(g: Grid, weights: np.ndarray, root: int) -> np.ndarray:
    """Heap Dijkstra over the arcs: weighted distance from ``root``, -1 if
    unreachable."""
    row_ptr = g.row_ptr.tolist()
    col_idx = g.col_idx.tolist()
    w = np.asarray(weights).tolist()
    dist = [-1] * g.n
    dist[root] = 0
    done = [False] * g.n
    pq = [(0, root)]
    while pq:
        d, u = heapq.heappop(pq)
        if done[u]:
            continue
        done[u] = True
        for k in range(row_ptr[u], row_ptr[u + 1]):
            v = col_idx[k]
            nd = d + w[k]
            if dist[v] < 0 or nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (nd, v))
    return np.asarray(dist, np.int64).astype(np.int32)


def component_edges(g: Grid, labels: np.ndarray) -> int:
    """Undirected edges with both ends reached (``labels >= 0``): the
    edges a search from the root traverses (Graph500 kernels 2 and 3).
    Every arc of the grid has its reverse, so this is half the arcs."""
    reached = labels >= 0
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    return int(np.count_nonzero(reached[src] & reached[g.col_idx])) // 2
