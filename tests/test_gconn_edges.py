"""GConn's edge-list loop against the two-half loop it replaced.

``connected_components`` hooks each undirected edge of a ``Graph`` once
(its canonical half) and tests for a cross edge before every round. The
loop it replaced hooked both halves and ran one more round to find none;
it is kept here as the oracle, and the two must agree bitwise on ``rep``,
``forest_mask`` and ``rounds``. The edge-list loop itself is checked on
unpaired lists (``core.bcc``'s auxiliary graph) against a union-find, and
the biconnectivity it feeds against the oracle's.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bcc as bcc_mod
from repro.core.compress import compress_full
from repro.core.connectivity import _components_of_edges, connected_components
from repro.core.euler import tour_numbering
from repro.core.graph import Graph
from repro.core.rst import rooted_spanning_tree

INF32 = jnp.iinfo(jnp.int32).max


@partial(jax.jit, static_argnames=("max_rounds", "use_kernel",
                                   "alternate_hooking"))
def _two_half_loop(graph: Graph, *, max_rounds=None, use_kernel=False,
                   alternate_hooking=False):
    """The replaced loop: both halves of every edge, one round per pass,
    ending on a pass that hooks nothing."""
    n = graph.n_nodes
    src, dst = graph.src, graph.dst
    m2 = src.shape[0]
    edge_id = jnp.arange(m2, dtype=jnp.int32)
    m = m2 // 2
    eid_canon = jnp.where(edge_id < m, edge_id, edge_id - m)

    def body(state):
        p, forest, rnd, _ = state
        ru = p[src]
        rv = p[dst]
        cross = ru != rv
        use_min = ((rnd % 2) == 0) if alternate_hooking else jnp.bool_(True)
        lo = jnp.minimum(ru, rv)
        hi = jnp.maximum(ru, rv)
        tgt = jnp.where(use_min, hi, lo)
        val = jnp.where(use_min, lo, hi)
        hooked_min = jnp.full((n,), INF32, jnp.int32).at[tgt].min(
            jnp.where(cross, val, INF32))
        hooked_max = jnp.full((n,), -1, jnp.int32).at[tgt].max(
            jnp.where(cross, val, -1))
        new_parent = jnp.where(use_min, hooked_min, hooked_max)
        got_hook = jnp.where(use_min, new_parent != INF32, new_parent >= 0)
        p_next = jnp.where(got_hook, new_parent, p)
        achieved = cross & (new_parent[tgt] == val)
        win_eid = jnp.full((n,), INF32, jnp.int32).at[tgt].min(
            jnp.where(achieved, eid_canon, INF32))
        is_winner = (achieved & (win_eid[tgt] == eid_canon)
                     & (edge_id == eid_canon))
        forest = forest | is_winner
        p_next = compress_full(p_next, use_kernel=use_kernel)
        return p_next, forest, rnd + 1, jnp.any(got_hook)

    def cond(state):
        _p, _f, rnd, changed = state
        bound = n if max_rounds is None else max_rounds
        return changed & (rnd < bound)

    p, forest, rounds, _ = jax.lax.while_loop(
        cond, body, (jnp.arange(n, dtype=jnp.int32),
                     jnp.zeros((m2,), jnp.bool_), jnp.int32(0),
                     jnp.bool_(True)))
    return p, forest, rounds - 1


def _multigraph(seed, n, m, pad=0):
    """Paired multigraph: random edges, self-loops, parallel copies and
    ``pad`` sentinel edges (``u = v = n``) at the end of the list."""
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    u[: m // 8] = v[: m // 8]                        # self-loops
    k = m // 6
    u[-k:], v[-k:] = v[:k][::-1], u[:k][::-1]        # parallel, reversed
    u = np.concatenate([u, np.full(pad, n)]).astype(np.int32)
    v = np.concatenate([v, np.full(pad, n)]).astype(np.int32)
    return Graph.from_undirected(n, jnp.asarray(u), jnp.asarray(v))


def _chains(n, length=10):
    """Paths of ``length`` vertices under shuffled labels: pure-min
    hooking needs several rounds to join each one."""
    i = np.arange(n - 1)
    i = i[(i + 1) % length != 0]
    perm = np.random.default_rng(n).permutation(n).astype(np.int32)
    return Graph.from_undirected(n, jnp.asarray(perm[i]),
                                 jnp.asarray(perm[i + 1]))


_EMPTY = Graph(n_nodes=5, src=jnp.zeros((0,), jnp.int32),
               dst=jnp.zeros((0,), jnp.int32))

CASES = {
    "multigraph": lambda: _multigraph(0, 60, 90),
    "multigraph_padded": lambda: _multigraph(1, 80, 70, pad=13),
    "sparse_padded": lambda: _multigraph(2, 200, 60, pad=40),
    "chains": lambda: _chains(97),
    "empty": lambda: _EMPTY,
    "single_edge": lambda: Graph.from_undirected(
        4, jnp.asarray([3], jnp.int32), jnp.asarray([1], jnp.int32)),
    "self_loop_only": lambda: Graph.from_undirected(
        3, jnp.asarray([2, 3], jnp.int32), jnp.asarray([2, 3], jnp.int32)),
}


@pytest.mark.parametrize("case,alt,max_rounds", [
    ("multigraph", False, None),
    ("multigraph", True, None),
    ("multigraph_padded", False, None),
    ("sparse_padded", True, None),
    ("chains", False, None),
    ("chains", True, None),
    ("chains", False, 1),
    ("chains", True, 2),
    ("sparse_padded", False, 1),
    ("empty", False, None),
    ("empty", True, None),
    ("single_edge", False, None),
    ("single_edge", True, 1),
    ("self_loop_only", False, None),
])
def test_connected_components_matches_two_half_loop(case, alt, max_rounds):
    g = CASES[case]()
    kw = dict(alternate_hooking=alt, max_rounds=max_rounds)
    rep, forest, rounds = connected_components(g, **kw)
    rep0, forest0, rounds0 = _two_half_loop(g, **kw)
    assert forest.shape == forest0.shape == (g.n_half_edges,)
    assert np.array_equal(np.asarray(rep), np.asarray(rep0))
    assert np.array_equal(np.asarray(forest), np.asarray(forest0))
    assert int(rounds) == int(rounds0)


def test_truncated_cases_are_cut():
    """The ``max_rounds`` cases above do stop short of convergence."""
    for case, alt, cut in (("chains", False, 1), ("chains", True, 2),
                           ("sparse_padded", False, 1)):
        _, _, full = _two_half_loop(CASES[case](), alternate_hooking=alt)
        assert int(full) > cut, case


def _union_find(n, u, v):
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    merged = 0
    for a, b in zip(u.tolist(), v.tolist()):
        if a >= n or b >= n:
            continue
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
            merged += 1
    return np.asarray([find(x) for x in range(n)]), merged


@pytest.mark.parametrize("seed,alt", [(0, False), (1, False), (2, True)])
def test_edge_list_core_on_unpaired_list(seed, alt):
    """An odd-length list with no pairing (the bcc aux case): every entry
    is hooked, and the forest spans each component without a cycle."""
    n = 120
    rng = np.random.default_rng(seed)
    length = 2 * 45 + 1
    u = rng.integers(0, n, length)
    v = rng.integers(0, n, length)
    u[::7] = v[::7]                                   # self-loops
    u[1::9] = v[1::9] = n                             # padding, interleaved
    u[-1], v[-1] = 5, 111                             # the lone odd entry
    rep, forest, rounds = _components_of_edges(
        n, jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
        alternate_hooking=alt)
    ref, merged = _union_find(n, u, v)
    rep = np.asarray(rep)
    # Same partition, labelled by a root of each component.
    assert len(set(zip(rep.tolist(), ref.tolist()))) == len(set(ref.tolist()))
    assert len(set(rep.tolist())) == len(set(ref.tolist()))
    assert (rep[rep] == rep).all()
    fm = np.asarray(forest)
    assert fm.shape == (length,)
    assert int(fm.sum()) == merged == n - len(set(ref.tolist()))
    assert ((u[fm] != v[fm]) & (u[fm] < n)).all()
    tree_ref, tree_merged = _union_find(n, u[fm], v[fm])
    assert tree_merged == merged                      # acyclic
    assert np.array_equal(tree_ref, ref)              # spanning
    assert int(rounds) >= 1


def _bcc_graph(seed, n, extra):
    """Simple graph: a random tree, chords, and one detached path."""
    rng = np.random.default_rng(seed)
    k = n - 6
    tree = [(int(rng.integers(0, i)), i) for i in range(1, k)]
    chords = rng.integers(0, k, (extra, 2))
    path = [(i, i + 1) for i in range(k, n - 1)]
    return Graph.from_numpy_undirected(
        n, np.asarray(tree + [tuple(c) for c in chords] + path))


@pytest.mark.parametrize("seed,scoped", [(0, False), (1, False), (2, True)])
def test_bcc_matches_two_half_aux_pass(monkeypatch, seed, scoped):
    g = _bcc_graph(seed, 48, 14)
    parent = rooted_spanning_tree(g, 0).parent
    tn = tour_numbering(parent)
    scope = None
    if scoped:
        # Component-closed: the detached path's component only.
        rep, _, _ = connected_components(g)
        scope = rep == rep[g.n_nodes - 1]

    def run():
        return jax.jit(lambda: bcc_mod.bcc_from_tour(g, parent, tn,
                                                      scope=scope))()

    out = run()
    calls = []

    def two_half_aux_pass(n, u, v, **kw):
        """How ``core.bcc`` ran GConn before: the aux list read as a
        ``Graph`` by the two-half loop."""
        calls.append(u.shape)
        return _two_half_loop(Graph(n_nodes=n, src=u, dst=v), **kw)

    monkeypatch.setattr(bcc_mod, "_components_of_edges", two_half_aux_pass)
    ref = run()
    assert calls == [(2 * g.n_edges + 2 * g.n_nodes,)]
    assert out.keys() == ref.keys()
    for key in out:
        assert np.array_equal(np.asarray(out[key]), np.asarray(ref[key])), key
    assert int(out["n_bcc"]) > 1
