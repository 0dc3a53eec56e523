"""Plain references that decide ``correct``: numpy and scipy on the host.

Nothing here imports the system under test. The references take the
inputs the benchmark itself generated (edge sets replayed from the
seed), and the program's answers only as the thing to judge: a parent
array is first checked to be a spanning forest of the reference's own
edge set, and only then are the tree queries it served recomputed on it.

Every check returns a count of wrong items; each count's limit is 0.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import connected_components, depth_first_order


def edge_keys(u: np.ndarray, v: np.ndarray, n: int) -> np.ndarray:
    """Sorted-pair keys ``min * n + max`` (int64) of undirected edges."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    return np.minimum(u, v) * n + np.maximum(u, v)


def components(edges: np.ndarray, n: int) -> np.ndarray:
    """Component label per vertex of the undirected graph ``edges``."""
    adj = coo_matrix((np.ones(edges.shape[0], np.int8),
                      (edges[:, 0], edges[:, 1])), shape=(n, n))
    return connected_components(adj, directed=False)[1]


def canonical(labels: np.ndarray) -> np.ndarray:
    """Relabel so that label k is the k-th distinct label met."""
    _, first, inverse = np.unique(labels, return_index=True,
                                  return_inverse=True)
    return np.argsort(np.argsort(first))[inverse]


def partition_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Vertices whose canonical component label differs (0 iff equal)."""
    return int(np.count_nonzero(canonical(a) != canonical(b)))


class Forest:
    """A rooted forest given by a parent array (roots point at themselves).

    Computes, by pointer doubling, each vertex's root and depth, and flags
    vertices that never reach a root (a cycle). The rest of the tables
    are built on demand.
    """

    def __init__(self, parent: np.ndarray):
        p = np.asarray(parent, np.int64)
        n = p.shape[0]
        self.n = n
        self.parent = p
        self.bad_range = int(np.count_nonzero((p < 0) | (p >= n)))
        p = np.clip(p, 0, n - 1)
        verts = np.arange(n)
        dist = (p != verts).astype(np.int64)
        nxt = p.copy()
        for _ in range(max(1, n.bit_length()) + 1):
            dist = dist + dist[nxt]
            nxt = nxt[nxt]
        self.root = nxt
        self.depth = dist
        self.cyclic = nxt[nxt] != nxt
        self._up = None

    def edges_bad(self, keys_sorted: np.ndarray) -> int:
        """Non-root vertices whose parent edge is not in the edge set."""
        v = np.nonzero(self.parent != np.arange(self.n))[0]
        k = edge_keys(v, np.clip(self.parent[v], 0, self.n - 1), self.n)
        pos = np.clip(np.searchsorted(keys_sorted, k), 0,
                      max(keys_sorted.shape[0] - 1, 0))
        hit = keys_sorted[pos] == k if keys_sorted.size else \
            np.zeros(k.shape, bool)
        return int(np.count_nonzero(~hit)) + self.bad_range

    def order_up(self) -> np.ndarray:
        """Vertices ordered deepest first (children before parents)."""
        return np.argsort(-self.depth, kind="stable")

    def subtree_sums(self, payload: np.ndarray) -> np.ndarray:
        """Sum of ``payload`` over each vertex's subtree (int64)."""
        acc = payload.astype(np.int64).tolist()
        par = self.parent.tolist()
        for v in self.order_up().tolist():
            p = par[v]
            if p != v:
                acc[p] += acc[v]
        return np.asarray(acc, np.int64)

    def up(self) -> list[np.ndarray]:
        if self._up is None:
            rows = [np.clip(self.parent, 0, self.n - 1)]
            for _ in range(max(1, (self.n - 1).bit_length())):
                rows.append(rows[-1][rows[-1]])
            self._up = rows
        return self._up

    def climb(self, x: np.ndarray, d: np.ndarray) -> np.ndarray:
        up = self.up()
        for k in range(len(up)):
            take = ((d >> k) & 1) == 1
            x = np.where(take, up[k][x], x)
        return x

    def lca(self, u: np.ndarray, v: np.ndarray) -> np.ndarray:
        """Lowest common ancestor, -1 where u and v are not connected."""
        up = self.up()
        du, dv = self.depth[u], self.depth[v]
        a = self.climb(u, np.maximum(du - dv, 0))
        b = self.climb(v, np.maximum(dv - du, 0))
        for k in range(len(up) - 1, -1, -1):
            move = up[k][a] != up[k][b]
            a = np.where(move, up[k][a], a)
            b = np.where(move, up[k][b], b)
        w = np.where(a == b, a, up[0][a])
        return np.where(self.root[u] == self.root[v], w, -1)

    def path_sum(self, u, v, payload) -> np.ndarray:
        w = self.lca(u, v)
        ok = w >= 0
        wc = np.where(ok, w, 0)
        s = self.root_sums(payload)
        out = s[u] + s[v] - 2 * s[wc] + payload[wc]
        return np.where(ok, out, 0)

    def root_sums(self, payload) -> np.ndarray:
        """Sum of ``payload`` on the path from each vertex to its root."""
        n = self.n
        p = np.clip(self.parent, 0, n - 1)
        nxt = np.append(np.where(p == np.arange(n), n, p), n)
        acc = np.append(payload.astype(np.int64), 0)
        for _ in range(max(1, n.bit_length()) + 1):
            acc = acc + acc[nxt]
            nxt = nxt[nxt]
        return acc[:n]

    def path_min(self, u, v, payload) -> np.ndarray:
        """Min of ``payload`` on the u..v path; int32 max if not connected."""
        w = self.lca(u, v)
        ok = w >= 0
        wc = np.where(ok, w, 0)
        up = self.up()
        tabs = [payload.astype(np.int64)]
        for k in range(len(up) - 1):
            tabs.append(np.minimum(tabs[-1], tabs[-1][up[k]]))
        out = payload[wc].astype(np.int64)
        for end in (u, v):
            x = end.copy()
            d = self.depth[end] - self.depth[wc]
            for k in range(len(up)):
                take = ((d >> k) & 1) == 1
                out = np.where(take, np.minimum(out, tabs[k][x]), out)
                x = np.where(take, up[k][x], x)
        return np.where(ok, out, np.iinfo(np.int32).max)


def spanning_forest_counts(parent, edges: np.ndarray, n: int,
                           labels: np.ndarray | None = None):
    """How far ``parent`` is from a spanning forest of ``edges``.

    Returns the ``Forest`` and the counts ``tree_edges_bad`` (parent links
    that are not edges), ``tree_cycles`` (vertices on a cycle) and
    ``partition_diff`` (the forest's components against the graph's).
    """
    f = Forest(parent)
    keys = np.sort(edge_keys(edges[:, 0], edges[:, 1], n))
    if labels is None:
        labels = components(edges, n)
    return f, {"tree_edges_bad": f.edges_bad(keys),
               "tree_cycles": int(np.count_nonzero(f.cyclic)),
               "partition_diff": partition_diff(f.root, labels)}


def tour_bad(forest: Forest, pre, size, last, comp) -> int:
    """Vertices at which a tour numbering is not a preorder of ``forest``.

    A numbering is one exactly when ``pre`` is a permutation, ``size`` is
    each subtree's size, ``last = pre + size - 1``, ``comp`` is each
    vertex's root, and every child's interval lies strictly inside its
    parent's: then the ``size[v]`` distinct numbers of subtree(v) all lie
    in ``[pre[v], last[v]]``, which holds ``size[v]`` numbers.
    """
    n = forest.n
    pre, size, last, comp = (np.asarray(a, np.int64)
                             for a in (pre, size, last, comp))
    ref_size = forest.subtree_sums(np.ones(n, np.int64))
    bad = (size != ref_size) | (last != pre + size - 1) \
        | (comp != forest.root) | (pre < 0) | (pre >= n)
    seen = np.bincount(np.clip(pre, 0, n - 1), minlength=n)
    bad |= seen[np.clip(pre, 0, n - 1)] != 1
    p = np.clip(forest.parent, 0, n - 1)
    child = p != np.arange(n)
    bad |= child & ~((pre[p] < pre) & (last <= last[p]))
    return int(np.count_nonzero(bad))


class Biconnectivity:
    """Bridges, articulation points and blocks of a simple graph, by DFS.

    One depth-first search covers every component: a path of auxiliary
    vertices ``n, n + 1, ...`` joins one vertex of each component by one
    edge each, so that no vertex has a long adjacency list to rescan and
    no block of the graph changes. That gives a DFS forest (no cross
    edges); ``low`` is the least preorder number reached from a subtree
    by one non-tree edge.
    """

    def __init__(self, edges: np.ndarray, n: int):
        self.n = n
        e = np.asarray(edges, np.int64)
        labels = components(e, n)
        _, heads = np.unique(labels, return_index=True)
        c = heads.shape[0]
        aux = n + np.arange(c)
        u = np.concatenate([e[:, 0], aux, aux[:-1]])
        v = np.concatenate([e[:, 1], heads, aux[1:]])
        total = n + c
        adj = coo_matrix((np.ones(2 * u.shape[0], np.int8),
                          (np.concatenate([u, v]), np.concatenate([v, u]))),
                         shape=(total, total)).tocsr()
        order, pred = depth_first_order(adj, n, directed=True,
                                        return_predecessors=True)
        pre = np.empty(total, np.int64)
        pre[order] = np.arange(total)
        size = np.ones(total, np.int64).tolist()
        predl = pred.tolist()
        for x in order[::-1].tolist():
            if predl[x] >= 0:
                size[predl[x]] += size[x]
        size = np.asarray(size, np.int64)
        s = n
        pred = np.where(pred < 0, s, pred).astype(np.int64)

        a, b = e[:, 0], e[:, 1]
        tree = (pred[a] == b) | (pred[b] == a)
        low0 = pre.copy()
        np.minimum.at(low0, a[~tree], pre[b[~tree]])
        np.minimum.at(low0, b[~tree], pre[a[~tree]])
        # Subtree minimum: range-min over [pre[x], pre[x] + size[x]).
        arr = low0[order]
        table = [arr]
        k = 1
        while 2 * k <= total:
            prev = table[-1]
            table.append(np.minimum(prev[:-k], prev[k:]))
            k *= 2
        lo, length = pre, size
        lvl = np.floor(np.log2(length)).astype(np.int64)
        low = np.empty(total, np.int64)
        for j in range(len(table)):
            sel = lvl == j
            if sel.any():
                t = table[j]
                low[sel] = np.minimum(t[lo[sel]],
                                      t[lo[sel] + length[sel] - (1 << j)])
        verts = np.arange(n)
        par = pred[:n]
        is_croot = par >= n
        nonroot = ~is_croot
        self.bridge_child = nonroot & (low[:n] >= pre[:n])
        art = np.zeros(n, bool)
        kids = np.bincount(par[nonroot], minlength=n)
        art |= is_croot & (kids >= 2)
        cut = nonroot & (low[:n] >= pre[par])
        par_nonroot = nonroot[np.clip(par, 0, n - 1)]
        art[par[cut & par_nonroot]] = True
        self.articulation = art
        # Block label of the tree edge into c: c where c starts a block,
        # else the label of the tree edge above pred[c].
        starts = ~nonroot | (low[:n] >= pre[par])
        ptr = np.where(starts, verts, np.clip(par, 0, n - 1))
        for _ in range(max(1, n.bit_length()) + 1):
            ptr = ptr[ptr]
        self.keys = edge_keys(a, b, n)
        deeper = np.where(pre[a] > pre[b], a, b)
        self.edge_block = ptr[deeper]
        self.edge_bridge = self.bridge_child[deeper] & tree
        self._sorted = np.argsort(self.keys)

    def lookup(self, u, v):
        """(present, bridge, block) of the edges {u, v}."""
        k = edge_keys(u, v, self.n)
        ks = self.keys[self._sorted]
        pos = np.clip(np.searchsorted(ks, k), 0, max(ks.shape[0] - 1, 0))
        idx = self._sorted[pos]
        hit = ks[pos] == k if ks.size else np.zeros(k.shape, bool)
        return hit, hit & self.edge_bridge[idx], np.where(
            hit, self.edge_block[idx], -1)


def bcc_bad(ref: Biconnectivity, pool_u, pool_v, pool_valid, bridge,
            edge_bcc, articulation) -> int:
    """Disagreements of a program's BCC labels with the reference.

    Counts articulation flags that differ, live pool edges whose bridge
    flag differs or that the reference does not have, and live edges
    placed in a different block partition.
    """
    cap = pool_u.shape[0]
    live = np.asarray(pool_valid, bool)
    u = np.asarray(pool_u)[live]
    v = np.asarray(pool_v)[live]
    hit, br, block = ref.lookup(u, v)
    bridge = np.asarray(bridge)
    edge_bcc = np.asarray(edge_bcc)
    bad = int(np.count_nonzero(~hit))
    for half in (bridge[:cap][live], bridge[cap:][live]):
        bad += int(np.count_nonzero(half != br))
    bad += int(np.count_nonzero(edge_bcc[:cap][live] != edge_bcc[cap:][live]))
    bad += partition_diff(edge_bcc[:cap][live], block)
    bad += int(np.count_nonzero(np.asarray(articulation, bool)
                                != ref.articulation))
    return bad
