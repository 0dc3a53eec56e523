"""Edge-update and read traffic of the benchmark, drawn from ``--seed``.

``Churn`` is ``repro.data.streams.churn`` at commit 345f140 turned into an
endless generator, with one change: where ``churn`` starts from a random
half of the graph's edges, the stream here starts from all of them but a
random ``dead_fraction`` (closed edges), so the live graph is the
configuration's graph under edits and not a random half of it. Every
batch deletes ``batch // 2`` live edges and inserts as many dead ones,
so the live count never changes. The batches are drawn one at a time, in
the same order for every run of a seed, however many the window consumes.

``Reads`` draws the read traffic: batches of queries, round-robin over
the op mix of ``repro.launch.serve_stream._ReadDriver`` at commit
345f140 (lca, connected, depth, subtree_add, path_add, path_min,
is_bridge, is_articulation). Where ``_ReadDriver`` draws both ends of a
pair uniformly, the second end here is the end of a short random walk
from the first, so that path answers cover short paths as well as long
ones, and ``is_bridge`` asks about an edge of the graph.
"""
from __future__ import annotations

import numpy as np

READ_OPS = ("lca", "connected", "depth", "subtree_add", "path_add",
            "path_min", "is_bridge", "is_articulation")


class Churn:
    """Steady-state churn over ``edges`` (int64 [M, 2]); ``n`` vertices."""

    def __init__(self, edges: np.ndarray, n: int, batch: int,
                 dead_fraction: float, rng: np.random.Generator):
        self.edges = edges
        self.n = n
        self.batch = batch
        self.rng = rng
        m = edges.shape[0]
        perm = rng.permutation(m)
        n_dead = self.dead_count(m, batch, dead_fraction)
        self.dead = perm[:n_dead]
        self.live = perm[n_dead:]
        self.init = edges[self.live].astype(np.int32)

    @staticmethod
    def dead_count(m: int, batch: int, dead_fraction: float) -> int:
        """Edges dead at the start: ``dead_fraction`` of ``m``, and at
        least one batch's inserts."""
        return max(int(m * dead_fraction), batch // 2)

    @classmethod
    def live_bound(cls, slots: int, batch: int, dead_fraction: float) -> int:
        """The most live edges of any graph of at most ``slots`` edges:
        the edge pool's capacity, the same for every seed."""
        return slots - cls.dead_count(slots, batch, dead_fraction)

    def _pad(self, pairs: np.ndarray):
        u = np.full(self.batch, self.n, np.int32)
        v = np.full(self.batch, self.n, np.int32)
        u[:pairs.shape[0]] = pairs[:, 0]
        v[:pairs.shape[0]] = pairs[:, 1]
        return u, v

    def next_batch(self):
        """(ins_u, ins_v, del_u, del_v), each int32[batch], n-padded."""
        k = max(1, self.batch // 2)
        kk = min(k, len(self.live), len(self.dead))
        rng = self.rng
        d_pos = np.sort(rng.choice(len(self.live), size=kk,
                                   replace=False))[::-1]
        i_pos = np.sort(rng.choice(len(self.dead), size=kk,
                                   replace=False))[::-1]
        dels, ins = self.live[d_pos], self.dead[i_pos]
        self.live = np.concatenate([np.delete(self.live, d_pos), ins])
        self.dead = np.concatenate([np.delete(self.dead, i_pos), dels])
        return (*self._pad(self.edges[ins]), *self._pad(self.edges[dels]))

    def live_edges(self) -> np.ndarray:
        """The live edge set after every batch drawn so far: int64 [L, 2]."""
        return self.edges[self.live]


class Reads:
    """Read batches over a graph, round-robin over ``READ_OPS``."""

    def __init__(self, edges: np.ndarray, n: int, batch: int, hops: int,
                 rng: np.random.Generator):
        self.edges = edges
        self.n = n
        self.batch = batch
        self.hops = hops
        self.rng = rng
        # CSR of the whole graph, for the random walks.
        src = np.concatenate([edges[:, 0], edges[:, 1]])
        dst = np.concatenate([edges[:, 1], edges[:, 0]])
        order = np.argsort(src, kind="stable")
        self.col = dst[order].astype(np.int32)
        self.start = np.searchsorted(src[order], np.arange(n + 1))
        self.payload = rng.integers(1, 100, n).astype(np.int32)
        self.count = 0

    def _walk(self, u: np.ndarray) -> np.ndarray:
        x = u.copy()
        for _ in range(self.hops):
            deg = self.start[x + 1] - self.start[x]
            step = self.start[x] + (self.rng.random(x.shape[0])
                                    * np.maximum(deg, 1)).astype(np.int64)
            x = np.where(deg > 0, self.col[np.minimum(step,
                                                      len(self.col) - 1)], x)
        return x

    def next_batch(self):
        """(op, u, v): one batch of ``batch`` queries (v unused by 1-ary ops)."""
        op = READ_OPS[self.count % len(READ_OPS)]
        self.count += 1
        if op == "is_bridge":
            pick = self.edges[self.rng.integers(0, self.edges.shape[0],
                                                self.batch)]
            u, v = pick[:, 0], pick[:, 1]
        else:
            u = self.rng.integers(0, self.n, self.batch)
            v = self._walk(u)
        return op, u.astype(np.int32), v.astype(np.int32)
