"""Deterministic correctness tests for the three RST algorithms."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (Graph, bfs_rst, connected_components, pr_rst,
                        rooted_spanning_tree, tree_depth)
from repro.core.validate import (bfs_depths_reference, components_reference,
                                 validate_rst)
from repro.data import graphs as G

METHODS = ("bfs", "gconn_euler", "pr_rst")


def _check_all_methods(g, root, connected=True):
    for method in METHODS:
        res = rooted_spanning_tree(g, root, method=method)
        v = validate_rst(g, res.parent, root, connected=connected)
        assert v["all_ok"], (method, v)


def test_single_edge():
    g = Graph.from_numpy_undirected(2, np.array([[0, 1]]))
    _check_all_methods(g, 0)
    _check_all_methods(g, 1)


def test_triangle():
    g = Graph.from_numpy_undirected(3, np.array([[0, 1], [1, 2], [2, 0]]))
    _check_all_methods(g, 2)


def test_chain_step_counts():
    """The paper's core claim in miniature: BFS steps = diameter,
    connectivity methods = O(log n)."""
    n = 512
    g = G.chain(n)
    bfs = rooted_spanning_tree(g, 0, method="bfs")
    gce = rooted_spanning_tree(g, 0, method="gconn_euler")
    prr = rooted_spanning_tree(g, 0, method="pr_rst")
    assert int(bfs.steps) == n - 1
    assert int(gce.steps) <= 12          # << diameter
    assert int(prr.steps) <= 12


def test_grid():
    g = G.grid2d(12)
    _check_all_methods(g, 0)
    _check_all_methods(g, 77)


def test_rmat_power_law():
    g = G.rmat(8, edge_factor=8, seed=3)
    _check_all_methods(g, 0)


def test_bfs_distances_match_reference():
    g = G.erdos_renyi(300, avg_degree=6, seed=1)
    root = 17
    _, dist, _ = bfs_rst(g, root)
    ref = bfs_depths_reference(g, root)
    got = np.asarray(dist).astype(np.int64)
    got[got == np.iinfo(np.int32).max] = -1
    assert np.array_equal(got, ref)


def _broom(spokes):
    """Vertex 0 with ``spokes`` paths of lengths 1-5 hanging off it."""
    edges, nxt = [], 1
    for i in range(spokes):
        prev = 0
        for _ in range(1 + i % 5):
            edges.append((prev, nxt))
            prev, nxt = nxt, nxt + 1
    return Graph.from_numpy_undirected(nxt, np.array(edges))


def _bfs_min_src_reference(g, root):
    """Level-synchronous BFS where each new vertex takes its smallest
    frontier neighbour as parent (the conflict rule ``bfs_rst`` keeps)."""
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    parent = np.full(g.n_nodes, -1, np.int64)
    dist = np.full(g.n_nodes, -1, np.int64)
    parent[root], dist[root], level = root, 0, 0
    while True:
        on = (dist[src] == level) & (dist[dst] < 0)
        if not on.any():
            return parent, dist, level
        for v in np.unique(dst[on]):
            parent[v] = src[on & (dst == v)].min()
        dist[np.unique(dst[on])] = level + 1
        level += 1


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["dense_and_sparse", "dense_kernel"])
@pytest.mark.parametrize("name,g,root", [
    ("grid", G.grid2d(9), 40),
    ("rmat", G.rmat(7, edge_factor=4, seed=2), 0),
    # Vertex 6 has no edges at all and is the highest id: its out-edge
    # list is empty and sits past the last half-edge.
    ("isolated_tail", Graph.from_numpy_undirected(
        7, np.array([(0, 3), (3, 1), (1, 0), (4, 5), (2, 3), (2, 0)])), 3),
    # 300 spokes of lengths 1-5 around vertex 0: the first front (300)
    # overflows frontier_cap(901) = 256, so level 1 runs dense and the
    # levels after it sparse again.
    ("broom", _broom(300), 0),
    # Repeated half-edges: a sparse level must list each vertex once.
    ("multigraph", Graph.from_undirected(
        6, np.array([0, 0, 1, 1, 2, 3, 3]), np.array([1, 1, 2, 2, 3, 4, 5])),
     0),
    # No half-edges: the root alone, zero levels, before any loop.
    ("edgeless", Graph.from_numpy_undirected(4, np.zeros((0, 2))), 2),
])
def test_bfs_parent_is_min_frontier_neighbour(name, g, root, use_kernel):
    """Sparse and dense levels, and the kernel's all-dense levels, keep
    the minimum-src rule."""
    parent, dist, levels = bfs_rst(g, root, use_kernel=use_kernel)
    ref_parent, ref_dist, ref_levels = _bfs_min_src_reference(g, root)
    assert np.array_equal(np.asarray(parent), ref_parent), name
    got = np.asarray(dist).astype(np.int64)
    got[got == np.iinfo(np.int32).max] = -1
    assert np.array_equal(got, ref_dist), name
    assert int(levels) == ref_levels, name


def test_connectivity_matches_union_find():
    rng = np.random.default_rng(5)
    edges = np.stack([rng.integers(0, 200, 150),
                      rng.integers(0, 200, 150)], 1)
    g = Graph.from_numpy_undirected(200, edges)
    rep, forest, _ = connected_components(g)
    ref = components_reference(g)
    rep_np = np.asarray(rep)
    for i in range(0, 200, 7):
        for j in range(0, 200, 11):
            assert (rep_np[i] == rep_np[j]) == (ref[i] == ref[j])
    ncomp = len(set(ref.tolist()))
    assert int(np.asarray(forest).sum()) == 200 - ncomp


def test_disconnected_graph():
    edges = np.array([(0, 1), (1, 2), (4, 5)])
    g = Graph.from_numpy_undirected(7, edges)
    for method in ("gconn_euler", "pr_rst"):
        res = rooted_spanning_tree(g, 1, method=method)
        v = validate_rst(g, res.parent, 1, connected=False)
        assert v["all_ok"], (method, v)
        parent = np.asarray(res.parent)
        assert parent[1] == 1            # designated root
        assert parent[3] == 3            # isolated vertex self-rooted
        assert parent[6] == 6            # second-component root exists
    # BFS marks unreachable as -1
    res = rooted_spanning_tree(g, 1, method="bfs")
    parent = np.asarray(res.parent)
    assert parent[1] == 1 and (parent[[3, 4, 5, 6]] == -1).all()


def test_depth_tradeoff_direction():
    """Fig. 2's trade-off: connectivity trees are ≥ as deep as BFS trees."""
    g = G.grid2d(16, seed=0)
    bfs = rooted_spanning_tree(g, 0, method="bfs")
    gce = rooted_spanning_tree(g, 0, method="gconn_euler")
    d_bfs = int(tree_depth(bfs.parent))
    d_gce = int(tree_depth(gce.parent))
    assert d_bfs == int(bfs.steps)
    assert d_gce >= d_bfs                # deeper (or equal), never shallower


def test_rooted_at_requested_root():
    for seed in range(3):
        g = G.erdos_renyi(100, avg_degree=4, seed=seed)
        for method in METHODS:
            root = 41
            res = rooted_spanning_tree(g, root, method=method)
            assert int(res.parent[root]) == root


def test_connectivity_multigraph_honesty():
    """Multigraph regression (parallel edges + self-loops, no dedupe):
    forest_mask never marks two half-edges of one vertex pair, never a
    self-loop, and always exactly n - n_components slots."""
    rng = np.random.default_rng(13)
    for trial in range(8):
        n = int(rng.integers(3, 40))
        m = int(rng.integers(1, 120))
        u = rng.integers(0, n, m)
        v = np.where(rng.random(m) < 0.2, u, rng.integers(0, n, m))  # loops
        dup = rng.integers(0, m, m // 3)                 # parallel copies
        u = np.concatenate([u, u[dup]])
        v = np.concatenate([v, v[dup]])
        for alt in (False, True):
            g = Graph.from_undirected(n, jnp.asarray(u, jnp.int32),
                                      jnp.asarray(v, jnp.int32))
            rep, forest, _ = connected_components(g, alternate_hooking=alt)
            fm = np.asarray(forest)
            src = np.asarray(g.src)
            dst = np.asarray(g.dst)
            marked = [(min(src[e], dst[e]), max(src[e], dst[e]))
                      for e in np.nonzero(fm)[0]]
            ncomp = len(set(components_reference(g).tolist()))
            assert len(marked) == n - ncomp, (trial, alt)
            assert len(marked) == len(set(marked)), (trial, alt, marked)
            assert all(a != b for a, b in marked), (trial, alt, marked)
            # Canonical-half guarantee: winners live in slots [0, M).
            assert (np.nonzero(fm)[0] < g.n_edges).all(), (trial, alt)


def test_use_kernel_paths_agree():
    g = G.erdos_renyi(256, avg_degree=5, seed=9)
    p1, d1, l1 = bfs_rst(g, 3, use_kernel=False)
    p2, d2, l2 = bfs_rst(g, 3, use_kernel=True)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    r1, f1, _ = connected_components(g, use_kernel=False)
    r2, f2, _ = connected_components(g, use_kernel=True)
    assert np.array_equal(np.asarray(r1), np.asarray(r2))
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
