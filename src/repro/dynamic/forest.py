"""Batch-dynamic rooted spanning forest: state + update application.

The static pipelines rebuild a tree from a frozen edge list; this module
maintains one under an *edge-update stream* (DESIGN.md §9). State is a
``DynamicForest`` pytree: the rooted parent array, its component
representatives (the PR-RST incremental invariant ``rep == roots_of(parent)``
carried across batches), and a fixed-capacity undirected edge pool — the
live multigraph, of which the parent array is always a spanning forest.

``apply_batch`` processes one batch of insertions + deletions in O(log n)
compress-engine steps:

  * **Deletions** cut deleted tree edges in one masked scatter (the child
    endpoint becomes the root of its severed subtree) and re-establish
    representatives with a *scoped* compression masked to the components
    that had a cut (``compress.compress_scoped`` — untouched components
    cost zero syncs).
  * **Insertions** land in free pool slots; slot assignment is one
    cumsum + gather, overflow (pool full) is counted, never silent.
  * **The link loop** then restores the spanning invariant: while any
    pool edge crosses two components, each *smaller* component (strict
    (size, root-id) order — union-by-size, so a component is re-rooted
    O(log n) times over its lifetime) picks one winning edge, re-roots
    itself at that edge's endpoint via the shared PR-RST path-reversal
    primitive (``core.reroot.link_components``) and grafts. Winning slots
    become tree edges. This one loop serves both roles: freshly inserted
    cross edges are the *insertion* case, surviving pool edges that cross
    a cut are the *replacement search* after a tree-edge deletion — a
    batched re-run of GConn hooking restricted to affected components.

Deletions address pool slots (``delete_mask``); ``edge_slots`` resolves a
batch of (u, v) pairs to slots, multiset-aware: k requests for the same
pair claim k distinct parallel copies. The pool is honestly a multigraph —
parallel edges occupy distinct slots and at most one copy per vertex pair
is ever a tree edge (the invariant ``connected_components``' edge-id-level
dedupe establishes for ``forest_from_graph``).

``dirty`` marks vertices whose component's *tree structure* changed since
the last tour refresh (cuts, re-roots, grafts — not non-tree pool edits);
``dynamic.tour`` consumes and clears it.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.compress import DEFAULT_JUMPS, compress_scoped
from repro.core.connectivity import connected_components
from repro.core.euler import euler_tour_root
from repro.core.graph import Graph
from repro.core.reroot import link_components

INF32 = jnp.iinfo(jnp.int32).max


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DynamicForest:
    """Rooted spanning forest of a dynamic edge multiset.

    Attributes:
      n_nodes:    static vertex count n.
      parent:     int32[n] rooted forest; roots (and isolated vertices)
                  self-point. Always spans the pool graph's components.
      rep:        int32[n] component representative per vertex — the
                  incremental invariant ``rep == roots_of(parent)``.
      pool_src, pool_dst: int32[capacity] live undirected edge pool;
                  empty slots carry the ``n_nodes`` sentinel.
      pool_valid: bool[capacity] slot occupancy.
      tree_mask:  bool[capacity] — slot is a spanning-forest edge (exactly
                  n − n_components slots set; ≤ 1 per vertex pair).
      dirty:      bool[n] — vertex's component tree changed since the last
                  tour refresh (component-closed by construction).
      version:    int32 scalar, bumped by every structural mutation
                  (``apply_batch``, repair, rebuild). Derived-cache
                  consumers (``dynamic.queries.QuerySession``) stamp the
                  version they were built against and refuse/refresh on
                  mismatch (DESIGN.md §12).
    """

    n_nodes: int
    parent: jnp.ndarray
    rep: jnp.ndarray
    pool_src: jnp.ndarray
    pool_dst: jnp.ndarray
    pool_valid: jnp.ndarray
    tree_mask: jnp.ndarray
    dirty: jnp.ndarray
    version: jnp.ndarray

    def tree_flatten(self):
        return ((self.parent, self.rep, self.pool_src, self.pool_dst,
                 self.pool_valid, self.tree_mask, self.dirty, self.version),
                self.n_nodes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux, *children)

    @property
    def capacity(self) -> int:
        return int(self.pool_src.shape[0])

    @property
    def n_components(self) -> jnp.ndarray:
        return jnp.sum((self.rep == jnp.arange(self.n_nodes)).astype(
            jnp.int32))

    @property
    def n_live_edges(self) -> jnp.ndarray:
        return jnp.sum(self.pool_valid.astype(jnp.int32))


def forest_empty(n_nodes: int, capacity: int) -> DynamicForest:
    """Edgeless forest over n vertices with an empty pool."""
    verts = jnp.arange(n_nodes, dtype=jnp.int32)
    sent = jnp.full((capacity,), n_nodes, jnp.int32)
    off = jnp.zeros((capacity,), jnp.bool_)
    return DynamicForest(
        n_nodes=n_nodes, parent=verts, rep=verts,
        pool_src=sent, pool_dst=sent, pool_valid=off, tree_mask=off,
        dirty=jnp.zeros((n_nodes,), jnp.bool_),
        version=jnp.int32(0))


def forest_from_graph(graph: Graph, capacity: int | None = None,
                      root: int = 0, *, batch_hint: int = 16,
                      use_kernel: bool = False) -> DynamicForest:
    """Seed the dynamic state from a static graph (GConn + Euler build).

    The pool holds the graph's M undirected edges in its first M slots.
    ``capacity`` must be ≥ M; the default leaves insertion headroom —
    ``max(M + 4 * batch_hint, ceil(1.25 * M))`` — so a freshly seeded
    forest absorbs insert-only batches instead of overflowing on the
    first one (pass ``capacity=M`` explicitly for a zero-headroom pool).
    The forest is the GConn spanning forest rooted at ``root`` (its
    component) / component reps (others).
    """
    n = graph.n_nodes
    m = graph.n_edges
    if capacity is None:
        capacity = max(m + 4 * batch_hint, -(-5 * m // 4))
    if capacity < m:
        raise ValueError(f"capacity {capacity} < graph edges {m}")

    rep, forest_mask, _ = connected_components(graph, use_kernel=use_kernel)
    t = max(n - 1, 1)
    m2 = graph.src.shape[0]
    slots = jnp.nonzero(forest_mask, size=t, fill_value=m2)[0]
    in_range = slots < m2
    safe = jnp.clip(slots, 0, max(m2 - 1, 0))
    fu = jnp.where(in_range, graph.src[safe], n)
    fv = jnp.where(in_range, graph.dst[safe], n)
    root_arr = jnp.asarray(root, jnp.int32)
    comp_root = jnp.where(rep == rep[root_arr], root_arr, rep)
    parent = euler_tour_root(n, fu, fv, in_range, comp_root,
                             use_kernel=use_kernel)

    pad = capacity - m
    sent = jnp.full((pad,), n, jnp.int32)
    # Winner half-edges are always canonical (e < M), so the undirected
    # tree mask is exactly the first half of forest_mask (the regression
    # test on connected_components enforces the canonical-half guarantee).
    tree = forest_mask[:m]
    return DynamicForest(
        n_nodes=n,
        parent=parent,
        rep=comp_root,
        pool_src=jnp.concatenate([graph.src[:m], sent]),
        pool_dst=jnp.concatenate([graph.dst[:m], sent]),
        pool_valid=jnp.concatenate([jnp.ones((m,), jnp.bool_),
                                    jnp.zeros((pad,), jnp.bool_)]),
        tree_mask=jnp.concatenate([tree, jnp.zeros((pad,), jnp.bool_)]),
        dirty=jnp.zeros((n,), jnp.bool_),
        version=jnp.int32(0))


def live_graph(state: DynamicForest) -> Graph:
    """The pool as a (sentinel-padded) ``Graph`` — the from-scratch view."""
    u = jnp.where(state.pool_valid, state.pool_src, state.n_nodes)
    v = jnp.where(state.pool_valid, state.pool_dst, state.n_nodes)
    return Graph.from_undirected(state.n_nodes, u, v)


@jax.jit
def edge_slots(state: DynamicForest, del_u: jnp.ndarray,
               del_v: jnp.ndarray):
    """Resolve (u, v) deletion requests to pool slots, multiset-aware.

    One lexsort over pool slots + requests keyed by the sorted endpoint
    pair (two int32 keys — no packed 64-bit key, so any n fits): within
    each equal-pair segment, pool copies sort before requests, and the
    r-th request for a pair claims the r-th parallel copy. Requests with
    no remaining copy (or sentinel padding ``u == n``) report not-found.

    Args:
      del_u, del_v: int32[D] endpoints; ``n_nodes`` marks padding slots.

    Returns:
      (delete_mask: bool[capacity] — one True per matched request,
       found: bool[D] — request matched a live pool slot).
    """
    n = state.n_nodes
    cap = state.pool_src.shape[0]
    d = del_u.shape[0]
    total = cap + d

    q_ok = (del_u >= 0) & (del_v >= 0) & (del_u < n) & (del_v < n)
    plo = jnp.minimum(state.pool_src, state.pool_dst)
    phi = jnp.maximum(state.pool_src, state.pool_dst)
    qlo = jnp.where(q_ok, jnp.minimum(del_u, del_v), n)
    qhi = jnp.where(q_ok, jnp.maximum(del_u, del_v), n)

    lo = jnp.concatenate([jnp.where(state.pool_valid, plo, n), qlo])
    hi = jnp.concatenate([jnp.where(state.pool_valid, phi, n), qhi])
    is_query = jnp.concatenate([jnp.zeros((cap,), jnp.bool_),
                                jnp.ones((d,), jnp.bool_)])

    # Lexicographic (lo, hi, is_query, slot) order as two stable argsorts
    # (least significant first): the same permutation as a 4-key
    # lexsort, at a third of its compile time for a v5e (4.2M slots:
    # 140 s vs 2 x 22 s on the compiling host).
    assert n < 2**30, "hi * 2 + is_query must fit int32"
    by_hi = jnp.argsort(hi * 2 + is_query, stable=True)
    order = by_hi[jnp.argsort(lo[by_hi], stable=True)].astype(jnp.int32)
    slo, shi, squery = lo[order], hi[order], is_query[order]

    # Segment machinery over sorted (lo, hi) groups.
    pos = jnp.arange(total, dtype=jnp.int32)
    seg_start = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        (slo[1:] != slo[:-1]) | (shi[1:] != shi[:-1])])
    first_pos = jax.lax.cummax(jnp.where(seg_start, pos, 0))
    # Pool copies occupy ranks [0, c) of their segment; the r-th query
    # (rank c + r) claims the copy at sorted position first_pos + r.
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    pool_in_seg = jnp.zeros((total,), jnp.int32).at[seg_id].add(
        (~squery).astype(jnp.int32))
    c = pool_in_seg[seg_id]
    rank = pos - first_pos
    claim_pos = jnp.clip(first_pos + (rank - c), 0, total - 1)
    matched = (squery & (rank - c < c)
               & (slo[claim_pos] == slo) & (shi[claim_pos] == shi)
               & ~squery[claim_pos] & (slo < n))

    claimed_slot = jnp.where(matched, order[claim_pos], cap)
    delete_mask = jnp.zeros((cap,), jnp.bool_).at[claimed_slot].set(
        True, mode="drop")
    found = jnp.zeros((d,), jnp.bool_).at[
        jnp.where(matched, order - cap, d)].set(True, mode="drop")
    return delete_mask, found


@partial(jax.jit, static_argnames=("max_rounds", "n_jumps", "use_kernel"))
def apply_batch(state: DynamicForest, insert_src: jnp.ndarray,
                insert_dst: jnp.ndarray, delete_mask: jnp.ndarray, *,
                max_rounds: int | None = None,
                n_jumps: int = DEFAULT_JUMPS, use_kernel: bool = False):
    """Apply one batch of edge deletions + insertions.

    Args:
      state: current forest (its invariants are the precondition).
      insert_src, insert_dst: int32[B] inserted undirected edges; slots
        with ``u == v`` or endpoints outside [0, n) are inert padding
        (use the ``n_nodes`` sentinel).
      delete_mask: bool[capacity] pool slots to delete (``edge_slots``
        resolves (u, v) pairs; already-empty slots are ignored).
      max_rounds: optional static bound on *productive* link rounds. If
        it truncates the loop, the spanning invariant is not restored —
        ``stats["pending"]`` reports the cross edges left unlinked.

    Returns:
      (state', stats) — stats is a dict of int32 scalars: ``cuts``
      (tree edges severed), ``links`` (components re-linked: insertions
      that merged + replacements found), ``rounds`` (productive link
      rounds), ``overflow`` (insertions dropped because the pool was
      full), ``pending`` (cross edges still unlinked — nonzero only
      when ``max_rounds`` cut the loop short).
    """
    n = state.n_nodes
    cap = state.pool_src.shape[0]
    verts = jnp.arange(n, dtype=jnp.int32)
    levels = max(1, (n - 1).bit_length())

    p = state.parent
    rt = state.rep
    pool_src, pool_dst = state.pool_src, state.pool_dst
    pool_valid, tree_mask = state.pool_valid, state.tree_mask
    touched = jnp.zeros((n,), jnp.bool_)

    # ---- deletions: cut tree edges, invalidate slots -----------------------
    del_mask = delete_mask & pool_valid
    del_tree = del_mask & tree_mask
    u_ = jnp.clip(pool_src, 0, n - 1)
    v_ = jnp.clip(pool_dst, 0, n - 1)
    child_is_v = p[v_] == u_
    child = jnp.where(child_is_v, v_, u_)
    other = jnp.where(child_is_v, u_, v_)
    do_cut = del_tree & (child_is_v | (p[u_] == v_))
    cut_idx = jnp.where(do_cut, child, n)
    p = p.at[cut_idx].set(jnp.where(do_cut, child, 0), mode="drop")
    touched = touched.at[cut_idx].set(True, mode="drop")
    touched = touched.at[jnp.where(do_cut, other, n)].set(True, mode="drop")
    n_cuts = jnp.sum(do_cut.astype(jnp.int32))

    pool_valid = pool_valid & ~del_mask
    tree_mask = tree_mask & ~del_mask
    pool_src = jnp.where(del_mask, n, pool_src)
    pool_dst = jnp.where(del_mask, n, pool_dst)

    # Representatives after cuts: scoped compression over the components
    # that lost a tree edge (component-closed mask ⇒ contract satisfied;
    # untouched components pay zero doubling syncs).
    comp_cut = jnp.zeros((n,), jnp.bool_).at[
        jnp.where(do_cut, rt[child], n)].set(True, mode="drop")
    active = comp_cut[rt]
    rt = jnp.where(active,
                   compress_scoped(p, active, n_jumps=n_jumps,
                                   use_kernel=use_kernel),
                   rt)

    # ---- insertions: append to free pool slots -----------------------------
    b = insert_src.shape[0]
    overflow = jnp.int32(0)
    if b > 0:
        ins_ok = ((insert_src != insert_dst)
                  & (insert_src >= 0) & (insert_src < n)
                  & (insert_dst >= 0) & (insert_dst < n))
        free = jnp.nonzero(~pool_valid, size=b, fill_value=cap)[0].astype(
            jnp.int32)
        rank = jnp.cumsum(ins_ok.astype(jnp.int32)) - 1
        slot = jnp.where(ins_ok, free[jnp.clip(rank, 0, b - 1)], cap)
        overflow = jnp.sum((ins_ok & (slot >= cap)).astype(jnp.int32))
        pool_src = pool_src.at[slot].set(insert_src, mode="drop")
        pool_dst = pool_dst.at[slot].set(insert_dst, mode="drop")
        pool_valid = pool_valid.at[slot].set(True, mode="drop")
        tree_mask = tree_mask.at[slot].set(False, mode="drop")

    # ---- link loop: restore the spanning invariant -------------------------
    # Any pool edge crossing two components is either a fresh insertion or
    # a replacement candidate exposed by a cut; the loop drains them all.
    def body(carry):
        p, rt, tree_mask, touched, rnd, links, _ = carry
        pu = jnp.clip(pool_src, 0, n - 1)
        pv = jnp.clip(pool_dst, 0, n - 1)
        ru = rt[pu]
        rv = rt[pv]
        cand = pool_valid & (ru != rv)

        # Union-by-size mover choice: the smaller component re-roots.
        # (size, root id) is a strict total order fixed for the round, so
        # the graft overlay inside link_components stays acyclic.
        size = jnp.zeros((n,), jnp.int32).at[rt].add(1)
        su, sv = size[ru], size[rv]
        u_moves = (su < sv) | ((su == sv) & (ru > rv))
        start = jnp.where(u_moves, pu, pv)
        target = jnp.where(u_moves, pv, pu)

        p, rt, is_winner = link_components(
            p, rt, start, target, cand, levels=levels, n_jumps=n_jumps,
            use_kernel=use_kernel)
        tree_mask = tree_mask | is_winner
        touched = touched.at[jnp.where(is_winner, start, n)].set(
            True, mode="drop")
        touched = touched.at[jnp.where(is_winner, target, n)].set(
            True, mode="drop")
        n_won = jnp.sum(is_winner.astype(jnp.int32))
        rnd = rnd + (n_won > 0).astype(jnp.int32)   # productive rounds only
        return p, rt, tree_mask, touched, rnd, links + n_won, n_won > 0

    def cond(carry):
        _p, _rt, _tm, _t, rnd, _l, changed = carry
        bound = n if max_rounds is None else max_rounds
        return changed & (rnd < bound)

    p, rt, tree_mask, touched, rounds, links, _ = jax.lax.while_loop(
        cond, body,
        (p, rt, tree_mask, touched, jnp.int32(0), jnp.int32(0),
         jnp.bool_(True)))

    # Cross edges still pending = 0 unless ``max_rounds`` truncated the
    # loop (in which case the spanning invariant is NOT restored — the
    # caller asked for a bounded round budget and must check this).
    pending = jnp.sum((pool_valid
                       & (rt[jnp.clip(pool_src, 0, n - 1)]
                          != rt[jnp.clip(pool_dst, 0, n - 1)])
                       ).astype(jnp.int32))

    # ---- dirty propagation: whole components containing touched vertices ---
    comp_touched = jnp.zeros((n,), jnp.bool_).at[
        jnp.where(touched, rt, n)].set(True, mode="drop")
    dirty = state.dirty | comp_touched[rt]

    new_state = DynamicForest(
        n_nodes=n, parent=p, rep=rt, pool_src=pool_src, pool_dst=pool_dst,
        pool_valid=pool_valid, tree_mask=tree_mask, dirty=dirty,
        version=state.version + 1)
    stats = {"cuts": n_cuts, "links": links, "rounds": rounds,
             "overflow": overflow, "pending": pending}
    return new_state, stats
