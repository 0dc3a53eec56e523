"""Edge-centric BFS rooted spanning tree (the paper's baseline, §III-A).

TPU adaptation of Merrill et al.'s BFS. A ``lax.while_loop`` runs one
iteration per BFS level, reproducing the Θ(diam(G)) step complexity the
paper measures. Each level takes one of two forms:

* **dense** — relax *all* half-edges with vector ops: gather both endpoint
  distances, propose ``parent[dst] = src`` for edges whose src is on the
  frontier and whose dst is undiscovered, resolve write conflicts with a
  deterministic scatter-min (the minimum src wins each dst);
* **sparse** — expand only the frontier's out-edges through a CSR index,
  in fixed-capacity buffers (``frontier_cap(n)`` vertices, four times as
  many edge slots), with the same minimum-src rule.

A level is sparse when the frontier is listed and its out-edges fit the
slots, dense otherwise; both give the same tree. The sparse form is what
makes high-diameter graphs affordable on a TPU: there a gather costs
roughly 30 ns per element (16.8M half-edges: 0.5 s), so a dense level of
``grid2d(2048)`` takes about half a second and its 4,094 levels half an
hour, while its frontier never exceeds 2,048 vertices.

Returns (parent, dist, levels): ``parent[root] == root``; unreachable
vertices keep ``parent == -1`` and ``dist == INF32``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.graph import Graph

INF32 = jnp.iinfo(jnp.int32).max


def frontier_cap(n: int) -> int:
    """Sparse frontier capacity: 2·2^⌈log2 √n⌉, at least 256, at most n.

    The rule is sized for grids (a square grid's widest front from any
    root). A graph whose front is wider, such as a power-law graph's
    middle levels, runs those levels dense: the same tree, at the dense
    level's cost (about 0.46 s per level at 16.8M half-edges on a v5e).
    """
    side = 1 << math.ceil(math.log2(max(math.sqrt(n), 1)))
    return min(n, max(256, 2 * side))


@partial(jax.jit, static_argnames=("max_levels", "use_kernel"))
def bfs_rst(graph: Graph, root, *, max_levels: int | None = None,
            use_kernel: bool = False):
    """Level-synchronous BFS spanning tree (dense / sparse levels).

    Args:
      graph: Graph (paired half-edges).
      root: scalar int vertex id.
      max_levels: optional static bound on levels (defaults to n_nodes).
      use_kernel: route the per-level edge relaxation through the Pallas
        ``frontier_relax`` kernel (interpret mode on CPU); every level is
        then dense.

    Returns:
      parent: int32[n] parent array (-1 = unreachable, parent[root] = root).
      dist:   int32[n] hop distance (INF32 = unreachable).
      levels: int32 scalar, number of BFS levels executed (= tree depth).
    """
    n = graph.n_nodes
    src, dst = graph.src, graph.dst
    e = src.shape[0]
    root = jnp.asarray(root, jnp.int32)
    k = frontier_cap(n)
    c = 4 * k

    dist0 = jnp.full((n,), INF32, jnp.int32).at[root].set(0)
    parent0 = jnp.full((n,), -1, jnp.int32).at[root].set(root)

    if use_kernel:
        from repro.kernels.frontier_relax.ops import frontier_relax
    else:
        frontier_relax = None

    def relax(dist, level):
        """One edge-centric relaxation: returns per-edge (proposes, src)."""
        if frontier_relax is not None:
            return frontier_relax(dist, src, dst, level)
        d_src = dist[src]
        d_dst = dist[dst]
        active = (d_src == level) & (d_dst == INF32)
        return active

    def dense(dist, parent, level):
        active = relax(dist, level)
        # Deterministic conflict resolution: the minimum src id wins each dst.
        prop_parent = jnp.where(active, src, INF32)
        winner = jnp.full((n,), INF32, jnp.int32).at[dst].min(prop_parent)
        discovered = winner != INF32
        parent = jnp.where(discovered, winner, parent)
        dist = jnp.where(discovered, level + 1, dist)
        return dist, parent, discovered

    def cond(state):
        level, changed = state[-2:]
        bound = n if max_levels is None else max_levels
        return changed & (level < bound)

    if e == 0:
        bound = n if max_levels is None else max_levels
        return parent0, dist0, jnp.int32(min(bound, 1) - 1)

    # CSR over half-edges by src: out-edges of v are col[row[v]:row[v + 1]].
    col = dst[jnp.argsort(src, stable=True)]
    row = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                           jnp.cumsum(jnp.bincount(src, length=n),
                                      dtype=jnp.int32)])
    slots = jnp.arange(c, dtype=jnp.int32)

    def sparse(dist, parent, best, claim, front, level):
        """Expand ``front`` (k ids, ``n``-padded) into c edge slots."""
        valid = front < n
        fc = jnp.minimum(front, n - 1)
        deg = jnp.where(valid, row[fc + 1] - row[fc], 0)
        end = jnp.cumsum(deg)
        owner = jnp.minimum(jnp.searchsorted(end, slots, side="right"),
                            k - 1)
        live = slots < end[-1]
        u = front[owner]
        v = col[jnp.clip(row[fc[owner]] + slots - (end - deg)[owner],
                         0, e - 1)]
        active = live & (dist[v] == INF32)
        # Minimum src wins each dst; a repeated (u, v) half-edge then
        # keeps its first slot only. ``best`` / ``claim`` entries are
        # written only in the level that discovers their vertex.
        best = best.at[jnp.where(active, v, n)].min(u, mode="drop")
        win = active & (best[v] == u)
        claim = claim.at[jnp.where(win, v, n)].min(slots, mode="drop")
        new = win & (claim[v] == slots)
        at = jnp.where(new, v, n)
        dist = dist.at[at].set(level + 1, mode="drop")
        parent = parent.at[at].set(u, mode="drop")
        pos = jnp.cumsum(new) - 1
        front = jnp.full((k,), n, jnp.int32).at[
            jnp.where(new, pos, k)].set(v, mode="drop")
        count = jnp.sum(new)
        return dist, parent, best, claim, front, count

    def body(state):
        dist, parent, best, claim, front, listed, level, _changed = state
        fc = jnp.minimum(front, n - 1)
        out = jnp.sum(jnp.where(front < n, row[fc + 1] - row[fc], 0))

        def dense_level(dist, parent, best, claim, front, level):
            dist, parent, discovered = dense(dist, parent, level)
            front = jnp.nonzero(discovered, size=k, fill_value=n)[0]
            return (dist, parent, best, claim, front.astype(jnp.int32),
                    jnp.sum(discovered))

        dist, parent, best, claim, front, count = jax.lax.cond(
            listed & (out <= c), sparse, dense_level,
            dist, parent, best, claim, front, level)
        listed = (count <= k) & (not use_kernel)
        return (dist, parent, best, claim, front, listed, level + 1,
                count > 0)

    front0 = jnp.full((k,), n, jnp.int32).at[0].set(root)
    inf = jnp.full((n,), INF32, jnp.int32)
    dist, parent, *_, levels, _ = jax.lax.while_loop(
        cond, body, (dist0, parent0, inf, inf, front0,
                     jnp.bool_(not use_kernel), jnp.int32(0), jnp.bool_(True)))
    return parent, dist, levels - 1
