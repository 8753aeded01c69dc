"""The benchmark's generators and references, at tiny sizes on the CPU:
each agrees with the closed form, with the program's own references
(``apps.bfs.bfs_reference``, ``apps.sssp.dijkstra_reference``) and with
the engine path the cells drive."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import road  # noqa: E402
from bench.seeds import stream  # noqa: E402

jnp = pytest.importorskip("jax.numpy")


def _csr(g):
    from repro.apps.bfs import CSRGraph
    return CSRGraph(g.row_ptr, g.col_idx, "grid")


@pytest.mark.parametrize("side", [1, 2, 5, 16])
def test_grid_matches_closed_form_and_road_like(side):
    from repro.apps.bfs import road_like
    g = road.grid(side)
    assert g.n == side * side
    assert g.arcs == 2 * road.grid_edges(side) == 4 * side * (side - 1)
    ref = road_like(side * side)
    np.testing.assert_array_equal(g.row_ptr, ref.row_ptr)
    np.testing.assert_array_equal(g.col_idx, ref.col_idx)
    full = np.zeros(g.n, np.int32)
    assert road.component_edges(g, full) == road.grid_edges(side)


def test_component_edges_counts_only_reached_ends():
    g = road.grid(3)
    labels = np.full(9, -1, np.int32)
    labels[[0, 1, 3, 4]] = 0                     # the top-left 2x2 square
    assert road.component_edges(g, labels) == 4


@pytest.mark.parametrize("side", [1, 2, 7, 10])
def test_edge_weights_give_both_arcs_of_an_edge_one_weight(side):
    g = road.grid(side)
    w = road.edge_weights(g, 1, 8, stream(4))
    assert w.shape == (g.arcs,) and w.dtype == np.int32
    assert ((w >= 1) & (w <= 8)).all()
    src = np.repeat(np.arange(g.n), np.diff(g.row_ptr))
    arc = {(u, v): k for k, (u, v) in
           enumerate(zip(src.tolist(), g.col_idx.tolist()))}
    for (u, v), k in arc.items():
        assert w[arc[(v, u)]] == w[k]
    np.testing.assert_array_equal(w, road.edge_weights(g, 1, 8, stream(4)))
    if side >= 7:                        # independent draws: every value
        per_edge = [w[k] for (u, v), k in arc.items() if u < v]
        assert len(per_edge) == road.grid_edges(side)
        assert set(per_edge) == set(range(1, 9))


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5, -3])
def test_root_order_searches_the_same_roots_in_a_seeded_order(seed):
    roots = [[0, 0], [0, 4], [4, 4], [2, 3]]
    ids = road.root_order(9, roots, seed)
    assert sorted(ids.tolist()) == [0, 4, 21, 40]
    np.testing.assert_array_equal(ids, road.root_order(9, roots, seed))
    orders = {tuple(road.root_order(9, roots, seed + k).tolist())
              for k in range(8)}
    assert len(orders) > 1
    with pytest.raises(ValueError):
        road.root_order(9, [[9, 0]], seed)


@pytest.mark.parametrize("side,root", [(7, 0), (7, 24), (12, 77)])
def test_bfs_levels_match_program_reference(side, root):
    from repro.apps.bfs import bfs_reference
    g = road.grid(side)
    np.testing.assert_array_equal(road.bfs_levels(g, root),
                                  bfs_reference(_csr(g), root))


@pytest.mark.parametrize("side,root,seed", [(6, 0, 1), (9, 40, 2)])
def test_dijkstra_matches_program_reference(side, root, seed):
    from repro.apps.sssp import dijkstra_reference
    g = road.grid(side)
    w = road.edge_weights(g, 1, 8, stream(seed))
    assert w.min() >= 1 and w.max() <= 8
    np.testing.assert_array_equal(road.dijkstra(g, w, root),
                                  dijkstra_reference(_csr(g), w, root))


def test_bfs_engine_path_matches_reference():
    from repro.apps.bfs import bfs_rounds_runner
    g = road.grid(10)
    runner, init_fn = bfs_rounds_runner(_csr(g), batch=8)
    for root in (0, 37):
        dist, _ = runner.run([root], acc=init_fn(root), max_rounds=10_000)
        np.testing.assert_array_equal(np.asarray(dist),
                                      road.bfs_levels(g, root))


def test_sssp_engine_path_matches_reference():
    from repro.apps.sssp import sssp_mesh_rounds_runner
    from repro.jaxcompat import make_mesh
    g = road.grid(8)
    w = road.edge_weights(g, 1, 8, stream(3))
    runner, init_fn = sssp_mesh_rounds_runner(
        _csr(g), w, mesh=make_mesh((1,), ("data",)), batch=8, delta=4,
        relaxed=True, split_payload=True)
    dist, _ = runner.run([0], [21], acc=init_fn(21), max_rounds=10_000,
                         initial_aux=[0])
    np.testing.assert_array_equal(np.asarray(dist), road.dijkstra(g, w, 21))
