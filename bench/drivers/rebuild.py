"""Driver of ``kind: rebuild`` traffic: from-scratch rooted spanning trees.

The system under test is ``repro.core.rst.rooted_spanning_tree`` with the
traffic's ``method``, run back to back on one graph, each build from a
root drawn from the seed, and each build waited for before the next. The
window runs whole builds until ``--seconds`` have passed.

The graph comes from the configuration's generator (``bench.gen.graphs``)
and is padded to the generator's fixed slot count with the ``n``
sentinel, so every seed compiles the same programs. The reference judges
the builds against the generator's own host edge list.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as ref
from bench.gen import graphs

#: Harness annotations an idle gap of the trace may be charged to.
LABELS = ("build",)
#: Traffic keys cut for the CPU rehearsal.
TINY = {}


def _control_build(graph, root):
    """The control: Euler rooting skipped, each vertex hung from its
    component's root as GConn labels it (a rooted forest with the right
    components, but not of the graph's edges)."""
    import jax.numpy as jnp

    from repro.core.connectivity import connected_components

    rep, _, _ = connected_components(graph)
    root = jnp.asarray(root, jnp.int32)
    return jnp.where(rep == rep[root], root, rep)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.checked = 0

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core.graph import Graph
        from repro.core.rst import rooted_spanning_tree

        data = graphs.generate(self.config, self.ctx.seed)
        self.n, self.edges = data.n, data.edges
        u = np.full(data.slots, data.n, np.int32)
        v = np.full(data.slots, data.n, np.int32)
        u[:data.edges.shape[0]] = data.edges[:, 0]
        v[:data.edges.shape[0]] = data.edges[:, 1]
        self.graph = Graph.from_undirected(data.n, jnp.asarray(u),
                                           jnp.asarray(v))
        self.root_rng = np.random.default_rng([self.ctx.seed, 1])
        self.sample_rng = np.random.default_rng([self.ctx.seed, 2])
        method = self.traffic["method"]
        if self.ctx.control:
            build = _control_build
        else:
            def build(graph, root):
                return rooted_spanning_tree(graph, root, method=method).parent
        self.build = self.ctx.annotate("build", build)
        self.kept = []          # reservoir sample of (root, parent)
        self.seen = 0
        jax.block_until_ready(self.build(self.graph, self._root()))

    def _root(self) -> int:
        return int(self.root_rng.integers(0, self.n))

    def window(self, seconds: float):
        import jax

        k = self.traffic["checked_builds"]
        builds = 0
        t0 = time.perf_counter()
        while True:
            root = self._root()
            parent = self.build(self.graph, root)
            jax.block_until_ready(parent)
            builds += 1
            # Reservoir sample of the window's builds, drawn from the seed.
            self.seen += 1
            if len(self.kept) < k:
                self.kept.append((root, parent))
            else:
                j = int(self.sample_rng.integers(0, self.seen))
                if j < k:
                    self.kept[j] = (root, parent)
            if time.perf_counter() - t0 >= seconds:
                break
        t_end = time.perf_counter()
        return {"t0": t0, "t_end": t_end, "window_s": t_end - t0,
                "builds": builds}

    def traced_segment(self):
        """One build, run under the profiler."""
        return self.window(0.0)

    def traced_units(self, seg) -> int:
        """Builds in a traced segment (the per-build metrics divide by it)."""
        return seg["builds"]

    def counts(self, win) -> tuple[int, int]:
        """(attempted, failed) of the window: builds, none of which drop."""
        return win["builds"], 0

    def collect(self, win) -> dict:
        host = {"kept": [(r, np.asarray(p)) for r, p in self.kept]}
        self.graph = self.kept = None
        return host

    def check(self, host) -> dict:
        edges = self.edges
        labels = ref.components(edges, self.n)
        keys = np.sort(ref.edge_keys(edges[:, 0], edges[:, 1], self.n))
        checks = {"tree_edges_bad": 0, "tree_cycles": 0, "partition_diff": 0,
                  "root_wrong": 0}
        for root, parent in host["kept"]:
            f = ref.Forest(parent)
            checks["tree_edges_bad"] += f.edges_bad(keys)
            checks["tree_cycles"] += int(np.count_nonzero(f.cyclic))
            checks["partition_diff"] += ref.partition_diff(f.root, labels)
            checks["root_wrong"] += int(parent[root] != root)
        self.checked = len(host["kept"])
        return checks
