"""Delta-stepping SSSP through
``apps.sssp.sssp_mesh_rounds_runner(relaxed=True, split_payload=True)``:
``PriorityMeshRoundRunner.run`` (the fused ``MeshHeapEngine``) on a mesh
of ``shards`` chips, one root per search.

Cell keys: ``grid_side``, ``shards``, ``batch``, ``roots`` (``[row,
col]`` pairs of different eccentricity; a pass searches from each once,
in an order drawn from the seed), ``warm_rounds``, ``max_rounds``;
configuration keys: ``arc_weights`` (``[min, max]``), ``weights_seed``,
``engine``.  The weighted graph is the configuration's, the same in every
run.
Compared per search: every vertex's distance against
``bench.road.dijkstra``."""

from __future__ import annotations

import numpy as np

from bench import query, road
from bench.seeds import stream


class Query(query.Query):
    def __init__(self, cell, seed: int, devices) -> None:
        import jax
        from jax.sharding import Mesh
        from repro.apps.bfs import CSRGraph
        from repro.apps.sssp import sssp_mesh_rounds_runner

        spec = self.spec = cell.spec
        eng = cell.config["engine"]
        w_min, w_max = cell.config["arc_weights"]
        self.g = road.grid(spec["grid_side"])
        self.w = road.edge_weights(self.g, w_min, w_max,
                                   stream(cell.config["weights_seed"]))
        self.roots = road.root_order(spec["grid_side"], spec["roots"], seed)
        self.pass_length = len(self.roots)
        shards = spec["shards"]
        self.mesh = Mesh(np.array(devices[:shards]), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
        self.runner, self.init_fn = sssp_mesh_rounds_runner(
            CSRGraph(self.g.row_ptr, self.g.col_idx, cell.name), self.w,
            mesh=self.mesh, batch=spec["batch"], delta=eng["delta"],
            relaxed=eng["relaxed"], split_payload=eng["split_payload"])
        self.engine = self.runner._engine
        self.info = {}
        self._ref = {}

    def root(self, i: int) -> int:
        return int(self.roots[i % len(self.roots)])

    def _run(self, i: int, max_rounds: int):
        r = self.root(i)
        dist, _ = self.runner.run([0], [r], acc=self.init_fn(r),
                                  max_rounds=max_rounds, initial_aux=[0])
        return dist

    def warm(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        super().warm()
        # the shard combine runs only after a whole search: give it the
        # megaround's output layout once here
        stacked = jax.device_put(
            jnp.zeros((self.spec["shards"], self.g.n), jnp.int32),
            NamedSharding(self.mesh, P("data")))
        np.asarray(self.runner.combine(stacked))

    def search(self, i: int):
        return np.asarray(self._run(i, self.spec["max_rounds"]))

    def reference(self, i: int):
        r = self.root(i)
        if r not in self._ref:
            ref = road.dijkstra(self.g, self.w, r)
            self._ref[r] = (ref, road.component_edges(self.g, ref))
        return self._ref[r]

    def check(self, i: int, labels: np.ndarray):
        from bench.harness import Check
        ref, edges = self.reference(i)
        return Check({"label_mismatches": int(np.count_nonzero(labels != ref))},
                     edges, int(np.count_nonzero(ref >= 0)))
