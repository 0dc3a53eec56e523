"""Unified rooted-spanning-tree API — the paper's three strategies.

``rooted_spanning_tree(graph, root, method=...)`` returns a parent array plus
per-method diagnostics (the step counts the paper's analysis revolves
around). All methods are jit-compiled with fixed shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Literal

import jax.numpy as jnp

from repro import obs
from repro.core.bfs import bfs_rst as _bfs_rst
from repro.core.connectivity import connected_components as _connected_components
from repro.core.euler import euler_tour_root as _euler_tour_root
from repro.core.pr_rst import pr_rst as _pr_rst
from repro.core.graph import Graph

Method = Literal["bfs", "gconn_euler", "pr_rst"]
METHODS: tuple[str, ...] = ("bfs", "gconn_euler", "pr_rst")


import jax


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RSTResult:
    parent: jnp.ndarray          # int32[n]
    method: str                  # static
    steps: jnp.ndarray           # parallel step count (levels or rounds)
    dist: jnp.ndarray | None = None      # BFS only: hop distances
    rep: jnp.ndarray | None = None       # gconn only: component reps

    def tree_flatten(self):
        return (self.parent, self.steps, self.dist, self.rep), self.method

    @classmethod
    def tree_unflatten(cls, aux, children):
        parent, steps, dist, rep = children
        return cls(parent=parent, method=aux, steps=steps, dist=dist,
                   rep=rep)


def gconn_euler_rst(graph: Graph, root, *, use_kernel: bool = False):
    """Paper's winning pipeline: connectivity → spanning forest → Euler rooting.

    ``use_kernel`` reaches both jump phases: GConn's shortcutting (multi-jump
    pointer_jump kernel) and the Euler list ranking (list_rank kernel).
    GConn's round count goes to the ambient ``obs`` ledger as ``gconn``.
    """
    n = graph.n_nodes
    rep, forest_mask, rounds = _connected_components(graph,
                                                     use_kernel=use_kernel)

    # Compact marked half-edges into n-1 fixed slots. Winners are always
    # canonical (e < M), so only the first half of the mask is searched.
    m = graph.n_edges
    t = max(n - 1, 1)
    slots = jnp.nonzero(forest_mask[:m], size=t, fill_value=m)[0]
    valid = slots < m
    safe = jnp.clip(slots, 0, m - 1)
    fu = jnp.where(valid, graph.src[safe], n)
    fv = jnp.where(valid, graph.dst[safe], n)

    # Component containing ``root`` is rooted at ``root``; others at their rep.
    root = jnp.asarray(root, jnp.int32)
    comp_root = jnp.where(rep == rep[root], root, rep)

    parent = _euler_tour_root(n, fu, fv, valid, comp_root,
                              use_kernel=use_kernel)
    # Recorded once Euler rooting is queued, so a traced pull waits on
    # GConn alone; nothing is recorded while tracing a jitted caller.
    if not isinstance(rounds, jax.core.Tracer):
        obs.record("gconn", lambda: int(rounds))
    return parent, rep, rounds


def rooted_spanning_tree(graph: Graph, root, method: Method = "gconn_euler",
                         *, use_kernel: bool = False, **kwargs) -> RSTResult:
    """Build a rooted spanning tree with the chosen strategy.

    ``use_kernel`` routes every jump/relax phase of the chosen pipeline
    through its Pallas kernel (interpret mode off-TPU); the default pure-XLA
    path shares the same amortized convergence engine (``core.compress``).
    """
    if method == "bfs":
        parent, dist, levels = _bfs_rst(graph, root, use_kernel=use_kernel,
                                        **kwargs)
        return RSTResult(parent=parent, method=method, steps=levels, dist=dist)
    if method == "gconn_euler":
        parent, rep, rounds = gconn_euler_rst(graph, root,
                                              use_kernel=use_kernel)
        return RSTResult(parent=parent, method=method, steps=rounds, rep=rep)
    if method == "pr_rst":
        parent, rounds = _pr_rst(graph, root, use_kernel=use_kernel, **kwargs)
        return RSTResult(parent=parent, method=method, steps=rounds)
    raise ValueError(f"unknown method {method!r}; choose from {METHODS}")


def tree_depth(parent: jnp.ndarray) -> jnp.ndarray:
    """Max depth of the rooted forest (engine-routed pointer doubling)."""
    from repro.core.compress import rank_to_root

    depth, _root = rank_to_root(parent)
    return jnp.max(depth)
