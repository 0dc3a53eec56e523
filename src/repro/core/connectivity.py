"""Shiloach–Vishkin / GConn-style connectivity with spanning-forest extraction.

Implements the paper's §III-B: alternating *hooking* and *pointer jumping*
(shortcutting). Per Shiloach & Vishkin (1982), the union phase marks one
*spanning edge* per successful hook, so connectivity yields an (unrooted)
spanning forest for free. Rooting is done separately by the Euler tour
(``repro.core.euler``), mirroring the paper's GConn + Euler pipeline.

TPU adaptation (see DESIGN.md §2):
  * CUDA ``atomicMin`` hooking → deterministic ``.at[].min`` scatter.
  * Winner-edge selection is two-stage so it stays int32-exact: first
    scatter-min the candidate representative per hook target, then
    scatter-min the edge id among edges that achieved that rep.
  * Each undirected edge is hooked once: ``connected_components`` runs the
    edge-list loop (``_components_of_edges``) on a ``Graph``'s canonical
    half-edges only, and the loop tests for a cross edge before each round
    instead of running a round that hooks nothing.
  * Hooking is pure-min by default: the paper's min/max alternation (a
    CAS-era optimization) pathologically funnels to one hook per round on
    hub graphs under deterministic scatter-hooking (measured: 812 vs 3
    rounds on rmat-13; see EXPERIMENTS.md §Perf). ``alternate_hooking=True``
    keeps the paper-faithful variant for ablation. Each round hooks *roots
    only*, monotonically, so no cycles can form within a round.
  * Pointer jumping runs to full convergence between hooking rounds and can
    be routed through the multi-jump Pallas kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.compress import compress_full
from repro.core.graph import Graph

INF32 = jnp.iinfo(jnp.int32).max


def pointer_jump_full(p: jnp.ndarray, *, use_kernel: bool = False) -> jnp.ndarray:
    """Jump ``p[i] = p[p[i]]`` until convergence (full path compression).

    Routed through the unified engine (``core.compress``): amortized
    convergence checks on both the XLA and Pallas paths.
    """
    return compress_full(p, use_kernel=use_kernel)


def _components_of_edges(n: int, u: jnp.ndarray, v: jnp.ndarray, *,
                         max_rounds: int | None = None,
                         use_kernel: bool = False,
                         alternate_hooking: bool = False):
    """GConn over an undirected edge list: one entry per undirected edge.

    The hook loop under ``connected_components``, which hands it the
    canonical halves of a ``Graph``; ``core.bcc`` hands it its auxiliary
    edge list directly. Entries may repeat a vertex pair or be self-loops;
    padding entries are ``u = v = n``. Traced through the caller's jit.

    Each round hooks roots along the entries whose endpoints lie in two
    components, marks one winning entry per hooked root, and compresses to
    full convergence. The loop tests for such a cross entry before every
    round, so it ends on two gathers and an ``any`` rather than on a round
    that hooks nothing.

    Returns:
      rep:    int32[n] component representative per vertex (a root id).
      forest: bool[L] — True for the entries selected as spanning-forest
              edges (exactly n - n_components are set; never a self-loop,
              never two entries of one hook).
      rounds: int32 scalar — hook rounds executed (at most ``max_rounds``).
    """
    eid = jnp.arange(u.shape[0], dtype=jnp.int32)
    p0 = jnp.arange(n, dtype=jnp.int32)
    ru0, rv0 = p0[u], p0[v]
    bound = n if max_rounds is None else max_rounds

    def hook(ru, rv, cross, rnd):
        """(new parent per root, whether it got one, hook target, value)."""
        lo = jnp.minimum(ru, rv)
        hi = jnp.maximum(ru, rv)
        if not alternate_hooking:
            # Pure-min hooking: the larger root takes the smaller as parent.
            new_parent = jnp.full((n,), INF32, jnp.int32).at[hi].min(
                jnp.where(cross, lo, INF32))
            return new_parent, new_parent != INF32, hi, lo
        # The paper alternates min/max per round (an optimization for
        # CAS-based hooking); under DETERMINISTIC scatter-hooking the
        # alternation re-creates a single-hook funnel whenever the merged
        # component's root is the extreme id of every cross edge (hub
        # graphs: measured 812 rounds vs 3 on rmat-13) — pure min-hooking
        # flips the funnel into a broadcast every other round instead. It
        # stays available for the ablation benchmark.
        use_min = (rnd % 2) == 0
        tgt = jnp.where(use_min, hi, lo)     # root being re-pointed
        val = jnp.where(use_min, lo, hi)     # new parent for that root
        hooked_min = jnp.full((n,), INF32, jnp.int32).at[tgt].min(
            jnp.where(cross, val, INF32))
        hooked_max = jnp.full((n,), -1, jnp.int32).at[tgt].max(
            jnp.where(cross, val, -1))
        new_parent = jnp.where(use_min, hooked_min, hooked_max)
        got_hook = jnp.where(use_min, new_parent != INF32, new_parent >= 0)
        return new_parent, got_hook, tgt, val

    def body(state):
        p, ru, rv, forest, rnd, _ = state
        cross = ru != rv
        new_parent, got_hook, tgt, val = hook(ru, rv, cross, rnd)

        # Winner entry per successful hook → spanning edge: the least
        # entry index among those that achieved the hook, so parallel
        # entries of one vertex pair can never both be marked.
        achieved = cross & (new_parent[tgt] == val)
        win_eid = jnp.full((n,), INF32, jnp.int32).at[tgt].min(
            jnp.where(achieved, eid, INF32))
        forest = forest | (achieved & (win_eid[tgt] == eid))

        # Compress to full convergence before the next cross-entry test.
        p = pointer_jump_full(jnp.where(got_hook, new_parent, p),
                              use_kernel=use_kernel)
        ru, rv = p[u], p[v]
        return p, ru, rv, forest, rnd + 1, jnp.any(ru != rv)

    def cond(state):
        *_, rnd, live = state
        return live & (rnd < bound)

    p, _, _, forest, rounds, _ = jax.lax.while_loop(
        cond, body, (p0, ru0, rv0, jnp.zeros(u.shape, jnp.bool_),
                     jnp.int32(0), jnp.any(ru0 != rv0)))
    return p, forest, rounds


@partial(jax.jit, static_argnames=("max_rounds", "use_kernel", "alternate_hooking"))
def connected_components(graph: Graph, *, max_rounds: int | None = None,
                         use_kernel: bool = False,
                         alternate_hooking: bool = False):
    """Connectivity + spanning forest via alternating hook / compress rounds.

    Multigraph-honest: inputs may carry parallel edges and self-loops
    (``Graph.from_undirected`` does not dedupe — the dynamic layer's edge
    pool is exactly such a multigraph). Winner-edge selection is deduped
    at undirected-edge-id level, so at most one half-edge per vertex pair
    is ever marked and self-loops never claim a slot.

    Relies on ``Graph``'s pairing (``rev(e) = (e + M) % 2M``): half-edges
    ``e`` and ``e + M`` are one undirected edge, so GConn reads only the
    canonical half ``e < M`` (``_components_of_edges`` on ``src[:M]``,
    ``dst[:M]``). A list that is not paired that way would be read wrongly:
    an edge that appears only in the second half is never hooked.

    Returns:
      rep:         int32[n] component representative per vertex (a root id).
      forest_mask: bool[2M] — True for half-edges selected as spanning-forest
                   edges (only the canonical half e < M of an undirected edge
                   can be set; exactly n - n_components are set in total).
      rounds:      int32 scalar — hook/compress rounds executed (the paper's
                   O(log n) step count), at most ``max_rounds - 1``: a
                   loop that runs all ``max_rounds`` rounds leaves the last
                   one uncounted.
    """
    m = graph.n_edges
    rep, forest, rounds = _components_of_edges(
        graph.n_nodes, graph.src[:m], graph.dst[:m], max_rounds=max_rounds,
        use_kernel=use_kernel, alternate_hooking=alternate_hooking)
    if max_rounds is not None:
        rounds = jnp.minimum(rounds, max_rounds - 1)
    return rep, jnp.concatenate([forest, jnp.zeros((m,), jnp.bool_)]), rounds


def count_components(rep: jnp.ndarray) -> jnp.ndarray:
    """Number of distinct representatives (components), jit-friendly."""
    n = rep.shape[0]
    is_root = rep == jnp.arange(n, dtype=rep.dtype)
    return jnp.sum(is_root.astype(jnp.int32))
