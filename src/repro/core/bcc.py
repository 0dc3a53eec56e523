"""Tarjan–Vishkin biconnectivity on top of any RST pipeline (DESIGN.md §4).

The paper motivates rooted spanning trees as the substrate for
biconnectivity; this module is that consumer, extending the three-way RST
comparison one level up the stack. The algorithm is the Euler-tour
formulation (Tarjan & Vishkin 1985; JaJa §5.3; cf. Polak, *Euler Meets
GPU*, and Dong et al.'s low/high characterization):

  1. **Tour numbering** — ``euler.tour_numbering`` turns the flavor's
     parent array into dense preorder numbers and subtree sizes, so
     subtree(v) is the contiguous interval ``[pre[v], pre[v] + size[v])``.
  2. **low/high** — per-vertex extremes of preorder reachable from the
     subtree through one non-tree edge, as idempotent payload-reduce
     doubling over the preorder-ordered array (engine
     ``compress.segment_reduce``).
  3. **Auxiliary graph** — one vertex per tree edge (identified with its
     child endpoint); two tree edges share a biconnected component iff
     connected under the three Tarjan–Vishkin rules (below). The final
     components pass reuses GConn's edge-list loop (``connectivity``).
  4. **Readout** — per-half-edge BCC labels (deeper endpoint's aux
     representative), bridges (subtree with no escaping non-tree edge),
     and articulation points (vertex incident to ≥ 2 distinct blocks).

Aux-graph edge rules, for tree edge aux(v) := (parent(v), v) (DESIGN.md §4):
  R1  non-tree edge {u, w}, u, w unrelated (disjoint preorder intervals):
      aux(u) — aux(w);
  R2  tree edge (w = parent(v), v) with low(v) < pre(w):   aux(v) — aux(w);
  R3  tree edge (w, v) with high(v) ≥ pre(w) + size(w):    aux(v) — aux(w).

Everything is jit-compatible and fixed-shape: the aux edge list has
exactly 2M + 2n slots (one per non-tree half-edge candidate, two per tree
edge), padded with the usual ``src = dst = n`` sentinels. ``bcc_batch``
vmaps the whole stack for the many-small-graphs serving scenario.

The module is layered so the static and incremental paths share one
auxiliary-graph construction (DESIGN.md §10): ``bcc_from_tour`` is the
tour-driven core — it consumes an existing ``TourNumbering`` instead of
recomputing one, takes an optional explicit per-half-edge ``tree_mask``
(the multigraph-honest classification the dynamic edge pool maintains),
and an optional component-closed ``scope`` mask that restricts every
phase (low/high via ``segment_reduce_scoped``, aux rules, GConn
labeling) to dirty components. ``bcc_from_parent`` / ``biconnectivity``
/ ``bcc_batch`` are the static entry points on top of it;
``repro.dynamic.bcc`` is the incremental one.

Multigraph caveat (static entry points only): parent arrays cannot
distinguish parallel copies of a tree edge, so *inferred* tree
classification requires simple graphs — which
``Graph.from_numpy_undirected`` (dedup + self-loop removal) guarantees.
Callers that know the classification (the dynamic layer's ``tree_mask``)
may pass it explicitly and feed multigraphs to ``bcc_from_tour``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.compress import segment_reduce, segment_reduce_scoped
from repro.core.connectivity import _components_of_edges
from repro.core.euler import tour_numbering
from repro.core.graph import Graph
from repro.core.rst import METHODS, rooted_spanning_tree

INF32 = jnp.iinfo(jnp.int32).max


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BCCResult:
    """Biconnectivity decomposition of a graph (all shapes fixed).

    Attributes:
      articulation: bool[n] — cut vertices.
      bridge:       bool[2M] per half-edge (both directions of a bridge
                    are marked; padding rows are False).
      edge_bcc:     int32[2M] biconnected-component label per half-edge
                    (an aux-graph representative id; −1 on padding rows).
                    Both directions of an edge carry the same label.
      n_bcc:        int32 scalar — number of biconnected components.
      pre, size:    int32[n] tour numbering diagnostics (DESIGN.md §4).
      low, high:    int32[n] subtree preorder extremes through one
                    non-tree edge.
      rst_steps:    int32 — parallel steps of the upstream RST build
                    (levels or rounds; the paper's Table I counts).
      aux_rounds:   int32 — GConn hook/compress rounds on the aux graph.
      seg_syncs:    int32 — doubling levels built for the low/high
                    sparse tables (both builds; the device-independent
                    cost the dynamic benchmarks compare, DESIGN.md §10).
      method:       static str — the ``rst_flavor`` that built the tree.
    """

    articulation: jnp.ndarray
    bridge: jnp.ndarray
    edge_bcc: jnp.ndarray
    n_bcc: jnp.ndarray
    pre: jnp.ndarray
    size: jnp.ndarray
    low: jnp.ndarray
    high: jnp.ndarray
    rst_steps: jnp.ndarray
    aux_rounds: jnp.ndarray
    seg_syncs: jnp.ndarray
    method: str = "gconn_euler"

    def tree_flatten(self):
        children = (self.articulation, self.bridge, self.edge_bcc,
                    self.n_bcc, self.pre, self.size, self.low, self.high,
                    self.rst_steps, self.aux_rounds, self.seg_syncs)
        return children, self.method

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, method=aux)


def bcc_from_tour(graph: Graph, parent: jnp.ndarray, tn, *,
                  tree_mask: jnp.ndarray | None = None,
                  scope: jnp.ndarray | None = None,
                  use_kernel: bool = False):
    """Tarjan–Vishkin core driven by an existing ``TourNumbering``.

    The shared auxiliary-graph construction under every entry point
    (DESIGN.md §10): the static wrappers below call it with a freshly
    computed numbering and no scope; ``repro.dynamic.bcc`` calls it with
    the maintained numbering, the pool's explicit tree classification,
    and the dirty-component scope.

    Traced through the caller's jit (optional arrays resolve to code
    paths at trace time, so this function is not jitted itself).

    Args:
      graph: Graph (paired half-edges; padding rows ``src == dst == n``;
        may be a multigraph iff ``tree_mask`` is explicit).
      parent: int32[n] rooted forest ``tn`` was built from (roots
        self-point; negative entries mark unspanned vertices).
      tn: ``euler.TourNumbering`` of ``parent`` — NOT recomputed here.
      tree_mask: optional bool[2M] — explicit per-half-edge tree
        classification (both halves of a tree edge True; at most one
        pool copy per vertex pair, the ``DynamicForest.tree_mask``
        invariant). ``None`` infers tree edges from ``parent`` endpoint
        adjacency, which is only sound on simple graphs.
      scope: optional bool[n] component-closed activity mask. When
        given, edges and vertices outside ``scope`` are treated as
        padding everywhere: their low/high/labels/articulation outputs
        are *garbage to be merged from a cache by the caller*, the
        low/high tables build only to the longest scoped component
        (``segment_reduce_scoped``), and the aux GConn pass hooks
        nothing outside the scope — clean components cost zero doubling
        work. ``n_bcc`` is only meaningful for ``scope=None``.
      use_kernel: route engine phases through their Pallas kernels
        (the scoped low/high build is XLA-only, see
        ``segment_reduce_scoped``).

    Returns:
      dict with keys articulation, bridge, edge_bcc, rep (int32[n]
      aux-component label per vertex — the label of the tree edge above
      v), n_bcc, low, high, aux_rounds, seg_syncs.
    """
    n = graph.n_nodes
    verts = jnp.arange(n, dtype=jnp.int32)
    pre, size, par = tn.pre, tn.size, tn.parent
    nonroot = par != verts
    spanned = parent >= 0

    src, dst = graph.src, graph.dst
    pad = (src >= n) | (dst >= n) | (src < 0) | (dst < 0)
    sc = jnp.clip(src, 0, n - 1)
    dc = jnp.clip(dst, 0, n - 1)
    # Edges touching unspanned vertices sit outside the decomposed
    # subgraph — treat them exactly like padding.
    pad = pad | ~spanned[sc] | ~spanned[dc]
    if scope is None:
        in_scope = jnp.ones((n,), jnp.bool_)
    else:
        # Component-closed: ``scope[sc] == scope[dc]`` on real edges.
        in_scope = scope
        pad = pad | ~in_scope[sc] | ~in_scope[dc]
    if tree_mask is None:
        is_tree = ~pad & ((par[dc] == sc) | (par[sc] == dc))
    else:
        is_tree = ~pad & tree_mask
    nontree = ~pad & ~is_tree

    # loc extremes: own preorder plus preorder over one non-tree edge.
    tgt = jnp.where(nontree, sc, n)
    loc_low = pre.at[tgt].min(jnp.where(nontree, pre[dc], INF32),
                              mode="drop")
    loc_high = pre.at[tgt].max(jnp.where(nontree, pre[dc], -1), mode="drop")

    # Subtree reduction = contiguous-interval reduction in preorder layout
    # (engine payload-reduce doubling table, DESIGN.md §4). Scoped
    # components occupy contiguous preorder blocks, so the scoped build
    # covers every active query with ⌈log2(max scoped comp size)⌉ levels.
    a_low = jnp.zeros((n,), jnp.int32).at[pre].set(loc_low)
    a_high = jnp.zeros((n,), jnp.int32).at[pre].set(loc_high)
    if scope is None:
        low = segment_reduce(a_low, pre, tn.last, "min",
                             use_kernel=use_kernel)
        high = segment_reduce(a_high, pre, tn.last, "max",
                              use_kernel=use_kernel)
        seg_syncs = jnp.int32(2 * max(1, (n - 1).bit_length()))
    else:
        low, s_lo = segment_reduce_scoped(a_low, pre, tn.last, in_scope,
                                          "min", return_syncs=True)
        high, s_hi = segment_reduce_scoped(a_high, pre, tn.last, in_scope,
                                           "max", return_syncs=True)
        seg_syncs = s_lo + s_hi

    # Aux edges. R1: unrelated non-tree edges (order by preorder so each
    # undirected edge contributes once; the reverse half-edge is inert).
    src_anc = (pre[sc] <= pre[dc]) & (pre[dc] < pre[sc] + size[sc])
    r1 = nontree & (pre[sc] < pre[dc]) & ~src_anc
    # R2/R3: tree edge (w = parent(v), v) joins its grandparent edge when
    # subtree(v) escapes below (low) or beyond (high) w's interval.
    w = par
    w_nonroot = par[w] != w
    r2 = nonroot & in_scope & w_nonroot & (low < pre[w])
    r3 = nonroot & in_scope & w_nonroot & (high >= pre[w] + size[w])

    aux_src = jnp.concatenate([jnp.where(r1, sc, n),
                               jnp.where(r2, verts, n),
                               jnp.where(r3, verts, n)])
    aux_dst = jnp.concatenate([jnp.where(r1, dc, n),
                               jnp.where(r2, w, n),
                               jnp.where(r3, w, n)])
    # One entry per aux edge, not a paired ``Graph``: GConn reads it whole.
    rep, _forest, aux_rounds = _components_of_edges(n, aux_src, aux_dst,
                                                    use_kernel=use_kernel)

    # Per-half-edge labels: every edge belongs to the block of the tree
    # edge above its deeper (larger-preorder) endpoint.
    deeper = jnp.where(pre[dc] > pre[sc], dc, sc)
    edge_bcc = jnp.where(pad, -1, rep[deeper])

    # Bridges: no non-tree edge escapes subtree(v) in either direction.
    bridge_v = nonroot & (low >= pre) & (high < pre + size)
    bridge = is_tree & bridge_v[deeper]

    # Articulation: ≥ 2 distinct block labels incident. Non-tree edges
    # never contribute a label their endpoint's tree edges don't already
    # carry, so it suffices to compare each vertex's own tree-edge label
    # with its children's. (Children share their parent's component, so
    # a scoped vertex only ever aggregates scoped children.)
    ptgt = jnp.where(nonroot, par, n)
    child_lab = jnp.where(nonroot, rep, INF32)
    mn = jnp.full((n,), INF32, jnp.int32).at[ptgt].min(child_lab,
                                                       mode="drop")
    mx = jnp.full((n,), -1, jnp.int32).at[ptgt].max(
        jnp.where(nonroot, rep, -1), mode="drop")
    has_child = mn != INF32
    articulation = jnp.where(nonroot,
                             has_child & ((mn != rep) | (mx != rep)),
                             has_child & (mn != mx))

    # One BCC per aux component that contains a tree edge; every block's
    # representative is one of its (non-root) members. (Pure-min hooking
    # makes labels content-determined — the minimum member id — which is
    # what lets the incremental path reuse cached clean-component labels
    # bit-identically, DESIGN.md §10.)
    n_bcc = jnp.sum((nonroot & (rep == verts)).astype(jnp.int32))

    return dict(articulation=articulation, bridge=bridge,
                edge_bcc=edge_bcc, rep=rep, n_bcc=n_bcc,
                low=low, high=high, aux_rounds=aux_rounds,
                seg_syncs=seg_syncs)


@partial(jax.jit, static_argnames=("use_kernel",))
def bcc_from_parent(graph: Graph, parent: jnp.ndarray, *,
                    use_kernel: bool = False):
    """Tarjan–Vishkin biconnectivity from an already-built parent array.

    Computes the tour numbering, then delegates to the shared
    ``bcc_from_tour`` core. The decomposition covers exactly the
    subgraph the forest spans: vertices the parent array leaves
    unspanned (BFS's unreachable −1) contribute no aux vertices, their
    incident edges carry label −1 and are never bridges, and they are
    never articulation points. Forest flavors (gconn_euler, pr_rst)
    span every component, so they decompose the whole graph; BFS
    decomposes the root's component only.

    Args:
      graph: Graph (paired half-edges; padding rows ``src == dst == n``).
      parent: int32[n] rooted spanning forest of ``graph`` (roots
        self-point; negative entries mark unspanned vertices).
      use_kernel: route engine phases through their Pallas kernels.

    Returns:
      dict with the BCCResult fields except ``rst_steps``/``method``.
    """
    tn = tour_numbering(parent, use_kernel=use_kernel)
    out = bcc_from_tour(graph, parent, tn, use_kernel=use_kernel)
    out.pop("rep")
    return dict(pre=tn.pre, size=tn.size, **out)


def biconnectivity(graph: Graph, root=0, *, rst_flavor: str = "gconn_euler",
                   use_kernel: bool = False, **rst_kwargs) -> BCCResult:
    """Biconnected components / bridges / articulation points of ``graph``.

    The ``rst_flavor`` knob selects which of the paper's three RST
    pipelines builds the spanning tree the Tarjan–Vishkin layer consumes
    (``"bfs"`` | ``"gconn_euler"`` | ``"pr_rst"``) — the decomposition is
    flavor-invariant, but the cost profile is not, which is what
    ``benchmarks/table3_bcc.py`` measures. Caveat on disconnected
    graphs: ``bfs`` spans (hence decomposes) only the root's component —
    edges elsewhere carry label −1; the forest flavors decompose every
    component, so flavor-invariance holds graph-wide only for connected
    inputs (see ``bcc_from_parent``).

    Args:
      graph: Graph (simple; paired half-edges).
      root: scalar int root vertex for the spanning tree.
      rst_flavor: RST pipeline name (see ``core.rst.METHODS``).
      use_kernel: route jump/relax/rank phases through Pallas kernels.
      **rst_kwargs: forwarded to the flavor (e.g. ``max_rounds``).

    Returns:
      BCCResult.
    """
    if rst_flavor not in METHODS:
        raise ValueError(
            f"unknown rst_flavor {rst_flavor!r}; choose from {METHODS}")
    res = rooted_spanning_tree(graph, root, method=rst_flavor,
                               use_kernel=use_kernel, **rst_kwargs)
    out = bcc_from_parent(graph, res.parent, use_kernel=use_kernel)
    return BCCResult(rst_steps=res.steps, method=rst_flavor, **out)


@partial(jax.jit, static_argnames=("n_nodes", "rst_flavor", "use_kernel"))
def bcc_batch(src: jnp.ndarray, dst: jnp.ndarray, roots: jnp.ndarray,
              *, n_nodes: int, rst_flavor: str = "gconn_euler",
              use_kernel: bool = False) -> BCCResult:
    """vmap-batched biconnectivity for many small same-shape graphs.

    The serving-scenario entry point: one compiled program decomposes a
    whole batch (recsys session graphs, molecule batches, ...) without
    host round-trips between graphs.

    Args:
      src, dst: int32[B, 2M] stacked half-edge lists sharing one padded
        shape (padding rows ``src == dst == n_nodes``).
      roots: int32[B] root vertex per graph.
      n_nodes: static vertex count shared by the batch.
      rst_flavor: RST pipeline name (see ``core.rst.METHODS``).

    Returns:
      BCCResult with every array field carrying a leading batch axis.
    """

    def one(s, d, r):
        return biconnectivity(Graph(n_nodes=n_nodes, src=s, dst=d), r,
                              rst_flavor=rst_flavor, use_kernel=use_kernel)

    return jax.vmap(one)(src, dst, roots)
