"""The Graph500 Kronecker graph, drawn on the host.

The R-MAT bit recursion is ``repro.data.graphs.rmat`` at commit 345f140,
with two changes that bring it to the Graph500 specification: the edge
factor and the initiator come from the configuration, and no spanning
tree is implanted (``rmat`` adds one through ``_implant_tree``; the
specification has none, so the graph keeps its isolated vertices and
its small components). As the specification asks, the vertex labels are
then permuted at random. Self-loops and duplicate edges are removed, as
``Graph.from_numpy_undirected`` does, and the edges come out sorted.
"""
from __future__ import annotations

import numpy as np

from bench.gen.graphs import GraphData

TINY = {"scale": 10}


def generate(config: dict, seed: int) -> GraphData:
    scale, edge_factor = config["scale"], config["edge_factor"]
    a, b, c = (float(x) for x in config["initiator"])
    rng = np.random.default_rng([seed, 0])
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    ab, lower, upper = a + b, a / (a + b), c / (1 - a - b)
    for _ in range(scale):
        r1 = rng.random(m)
        r2 = rng.random(m)
        src_bit = r1 > ab
        dst_bit = np.where(src_bit, r2 > upper, r2 > lower)
        src <<= 1
        src |= src_bit
        dst <<= 1
        dst |= dst_bit
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    lo = np.minimum(src, dst)
    hi = np.maximum(src, dst)
    keep = lo != hi
    key = np.unique(lo[keep] * n + hi[keep])
    edges = np.stack([key // n, key % n], axis=1)
    return GraphData(edges=edges, n=n, slots=m)
