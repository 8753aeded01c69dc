"""BFS through ``apps.bfs.bfs_rounds_runner``: the fused ``RingEngine``
(``RoundRunner.run``), one root per search.

Cell keys: ``grid_side``, ``batch``, ``roots`` (``[row, col]`` pairs of
different eccentricity; a pass searches from each once, in an order drawn
from the seed), ``warm_rounds``, ``max_rounds``.  Compared per search:
every vertex's hop distance against ``bench.road.bfs_levels``."""

from __future__ import annotations

import numpy as np

from bench import query, road

WAVEFAA_LANES = 1024


class Query(query.Query):
    def __init__(self, cell, seed: int, devices) -> None:
        from repro.apps.bfs import CSRGraph, bfs_rounds_runner

        spec = self.spec = cell.spec
        self.g = road.grid(spec["grid_side"])
        self.roots = road.root_order(spec["grid_side"], spec["roots"], seed)
        self.pass_length = len(self.roots)
        self.runner, self.init_fn = bfs_rounds_runner(
            CSRGraph(self.g.row_ptr, self.g.col_idx, cell.name),
            batch=spec["batch"])
        self.engine = self.runner._engine
        lanes = spec["batch"] * 4                  # grid out-degree <= 4
        self.info = {"wavefaa_lanes":
                     -(-lanes // WAVEFAA_LANES) * WAVEFAA_LANES}
        self._ref = {}

    def root(self, i: int) -> int:
        return int(self.roots[i % len(self.roots)])

    def _run(self, i: int, max_rounds: int):
        r = self.root(i)
        dist, _ = self.runner.run([r], acc=self.init_fn(r),
                                  max_rounds=max_rounds)
        return dist

    def search(self, i: int):
        return np.asarray(self._run(i, self.spec["max_rounds"]))

    def reference(self, i: int) -> np.ndarray:
        r = self.root(i)
        if r not in self._ref:
            ref = road.bfs_levels(self.g, r)
            self._ref[r] = (ref, road.component_edges(self.g, ref))
        return self._ref[r]

    def check(self, i: int, labels: np.ndarray):
        from bench.harness import Check
        ref, edges = self.reference(i)
        return Check({"label_mismatches": int(np.count_nonzero(labels != ref))},
                     edges, int(np.count_nonzero(ref >= 0)))
