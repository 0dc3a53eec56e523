"""Graph containers for the RST library.

Graphs are stored as fixed-shape, jit-friendly COO edge lists. An undirected
graph with M undirected edges is stored as 2M directed half-edges arranged so
that ``rev(e) = (e + M) % 2M`` — half-edge ``i`` and ``i + M`` are the two
directions of the same undirected edge. This is exactly the pairing the paper
uses for the Euler tour ("compute the corresponding reverse edge
((last[r] + E/2) mod E)").

All arrays are int32; vertex ids in ``[0, n)``. Padding (for ragged batches)
uses ``src == dst == n_nodes`` sentinel rows which every algorithm masks out.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class Graph:
    """COO undirected graph as paired directed half-edges.

    The pairing is a contract, not a convention: half-edges ``e`` and
    ``e + M`` are the two directions of one undirected edge. GConn
    (``connectivity.connected_components``) relies on it and reads only the
    canonical half ``e < M``; an edge list that is not paired so is not a
    ``Graph``.

    Attributes:
      n_nodes: static int, number of vertices.
      src, dst: int32[2M] directed half-edges; ``rev(e) = (e + M) % 2M``.
    """

    n_nodes: int
    src: jnp.ndarray
    dst: jnp.ndarray

    # -- pytree plumbing (n_nodes is static) --------------------------------
    def tree_flatten(self):
        return (self.src, self.dst), self.n_nodes

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst = children
        return cls(n_nodes=aux, src=src, dst=dst)

    # -- properties ----------------------------------------------------------
    @property
    def n_half_edges(self) -> int:
        return int(self.src.shape[0])

    @property
    def n_edges(self) -> int:
        """Number of undirected edges M."""
        return self.n_half_edges // 2

    def rev(self, e: jnp.ndarray) -> jnp.ndarray:
        """Index of the reverse half-edge."""
        m = self.n_edges
        return (e + m) % (2 * m)

    # -- constructors ---------------------------------------------------------
    @staticmethod
    def from_undirected(n_nodes: int, u: jnp.ndarray, v: jnp.ndarray) -> "Graph":
        """Build from M undirected edges (u[i], v[i])."""
        u = jnp.asarray(u, jnp.int32)
        v = jnp.asarray(v, jnp.int32)
        return Graph(n_nodes=n_nodes, src=jnp.concatenate([u, v]),
                     dst=jnp.concatenate([v, u]))

    @staticmethod
    def from_numpy_undirected(n_nodes: int, edges: np.ndarray) -> "Graph":
        """edges: int array [M, 2]. Removes self-loops and duplicates."""
        edges = np.asarray(edges, dtype=np.int64)
        if edges.size == 0:
            e = np.zeros((0,), np.int32)
            return Graph(n_nodes=n_nodes, src=jnp.asarray(e), dst=jnp.asarray(e))
        lo = np.minimum(edges[:, 0], edges[:, 1])
        hi = np.maximum(edges[:, 0], edges[:, 1])
        keep = lo != hi
        lo, hi = lo[keep], hi[keep]
        key = np.unique(lo * n_nodes + hi)
        u = (key // n_nodes).astype(np.int32)
        v = (key % n_nodes).astype(np.int32)
        return Graph.from_undirected(n_nodes, jnp.asarray(u), jnp.asarray(v))


def build_csr(graph: Graph) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """CSR over directed half-edges: (row_ptr[n+1], col[2M], half_edge_id[2M]).

    ``col`` / ``half_edge_id`` are sorted by (src, dst) lexicographically —
    the "circular adjacency list" ordering the Euler tour needs.
    """
    n = graph.n_nodes
    order = jnp.lexsort((graph.dst, graph.src))
    col = graph.dst[order]
    counts = jnp.bincount(graph.src, length=n)
    row_ptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    return row_ptr, col, order.astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_nodes",))
def degrees(src: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
    return jnp.bincount(src, length=n_nodes)
