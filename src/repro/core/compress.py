"""Unified pointer-compression engine for every O(log n) jump phase.

All three RST pipelines bottom out in pointer doubling over a parent /
successor table: GConn's shortcutting between hook rounds, PR-RST's
``roots_of``, Euler/Wyllie list ranking, and the tree-depth diagnostic.
The seed code paid for each instance separately with a hand-rolled
``while_loop(any(p[p] != p))`` loop — one device↔host convergence sync per
*single* doubling step, which is exactly the per-launch overhead the
paper's 5-jump-per-launch optimization exists to amortize.

This engine is the single home for those loops (DESIGN.md §3):

  * ``jump_k(p, k)``      — k chained doubling steps, zero convergence syncs;
  * ``compress_full(p)``  — full path compression; ``n_jumps`` doubling steps
                            are chained between ``jnp.any`` checks, so
                            convergence costs ⌈log2(depth)/k⌉ + 1 syncs
                            instead of ⌈log2(depth)⌉ + 1 — in the pure-XLA
                            path as well as the Pallas-kernel path;
  * ``roots_of(p)``       — alias of ``compress_full`` (non-destructive:
                            both are functional);
  * ``reduce_to_root(p, x, op)`` — doubling with a payload combine (add /
                            min / max) → (op over each v→root path, root);
  * ``rank_to_root(p)``   — the ``op="add"``, unit-payload instance →
                            (depth, root) per vertex;
  * ``segment_reduce(a, lo, hi, op)`` — idempotent range reduction via a
                            doubling sparse table (payload-reduce ``jump_k``
                            on the shift successor i ↦ i + 2^k) — the
                            subtree low/high primitive for biconnectivity
                            (DESIGN.md §4);
  * ``segment_reduce_scoped(a, lo, hi, active, op)`` — the activity-masked
                            variant (the BCC analogue of
                            ``compress_scoped``): the table build stops as
                            soon as every *active* query is covered, so
                            clean components pay zero doubling steps
                            (DESIGN.md §10);
  * ``wyllie_rank(s, v)`` — list ranking (−1-sentinel successor convention)
                            with the same amortization.

``interpret=None`` everywhere dispatches from ``jax.default_backend()``:
Mosaic on TPU, the Pallas interpreter elsewhere. The kernel path pads to
the (8, 128) tile once, *outside* the convergence loop, and runs the
whole loop on the padded 2-D table. Of the kernels behind
``use_kernel=True`` only ``segment_table`` compiles for a TPU; the
pointer_jump and list_rank kernels raise the compiler's refusal there
(DESIGN.md §3).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NO_SUCC = jnp.int32(-1)

#: Doubling steps chained between convergence checks (paper's 5-jump trick).
DEFAULT_JUMPS = 5


def default_interpret() -> bool:
    """Pallas interpret-mode dispatch: Mosaic on TPU, interpreter elsewhere.

    Single policy shared with every kernel ops wrapper
    (``repro.kernels.auto_interpret``)."""
    from repro.kernels import auto_interpret
    return auto_interpret()


def jump_k(p: jnp.ndarray, n_jumps: int = DEFAULT_JUMPS) -> jnp.ndarray:
    """Apply ``p = p[p]`` ``n_jumps`` times — no convergence check, no sync.

    Each application *doubles* the compressed distance, so ``jump_k``
    covers chains of depth up to ``2**n_jumps`` (DESIGN.md §3).

    Args:
      p: int32[n] parent table (roots self-point).
      n_jumps: number of chained doubling steps.

    Returns:
      int32[n] jumped table (functional — ``p`` is unchanged).
    """
    for _ in range(n_jumps):
        p = p[p]
    return p


@partial(jax.jit, static_argnames=("n_jumps", "use_kernel", "interpret",
                                   "return_syncs", "max_syncs"))
def compress_full(p: jnp.ndarray, *, n_jumps: int = DEFAULT_JUMPS,
                  use_kernel: bool = False, interpret: bool | None = None,
                  return_syncs: bool = False, max_syncs: int | None = None):
    """Fully compress ``p`` (every entry ends on its chain's fixed point).

    Amortization contract: the convergence loop performs ``n_jumps``
    doubling steps per ``jnp.any`` sync, so a table of maximum depth d
    costs ⌈log2(d)/n_jumps⌉ + 1 syncs (the +1 confirms convergence).

    Args:
      p: int32[n] parent table; roots self-point. (Cyclic inputs are not
         trees: odd cycles never converge — pass ``max_syncs`` to bound the
         loop — and even cycles collapse to *spurious* fixed points that
         are not roots of the original table; callers validating arbitrary
         inputs must re-check fixed points against the original ``p``, see
         ``validate.reaches_root``.)
      n_jumps: doubling steps chained between convergence checks.
      use_kernel: route each chained-jump group through the Pallas doubling
         kernel (one launch per sync); padding is hoisted out of the loop.
      interpret: Pallas interpret mode; None → ``default_interpret()``.
      return_syncs: also return the number of ``jnp.any`` convergence
         checks executed (int32) — the counting hook for tests/benchmarks.
      max_syncs: optional static bound on convergence checks.

    Returns:
      compressed table, or ``(compressed, syncs)`` if ``return_syncs``.
    """
    if use_kernel:
        if interpret is None:
            interpret = default_interpret()
        from repro.kernels.pointer_jump.ops import (pad_to_tile,
                                                    pointer_jump_double_k)
        p2d, n = pad_to_tile(p)

        def step(q):
            return pointer_jump_double_k(q, n_jumps=n_jumps,
                                         interpret=interpret)
    else:
        p2d, n = p, p.shape[0]

        def step(q):
            return jump_k(q, n_jumps)

    def body(state):
        q, _, syncs = state
        q2 = step(q)
        return q2, jnp.any(q2 != q), syncs + 1

    def cond(state):
        _q, changed, syncs = state
        if max_syncs is not None:
            changed = changed & (syncs < max_syncs)
        return changed

    out, _, syncs = jax.lax.while_loop(
        cond, body, (p2d, jnp.bool_(True), jnp.int32(0)))
    if use_kernel:
        out = out.reshape(-1)[:n]
    return (out, syncs) if return_syncs else out


def roots_of(p: jnp.ndarray, **kwargs):
    """int32[n] root of every vertex's chain (DESIGN.md §3).

    Alias of ``compress_full`` (functional, hence non-destructive —
    callers keep their original ``p``); same kwargs and sync contract.
    """
    return compress_full(p, **kwargs)


def compress_scoped(p: jnp.ndarray, active: jnp.ndarray, **kwargs):
    """Scoped compression: compress ``active`` rows, freeze the rest.

    The ``jump_k``-based dirty-vertex variant for the batch-dynamic layer
    (DESIGN.md §9): inactive rows are masked to self-loops *before* the
    convergence loop, so they are fixed points from the first step and the
    sync count is ⌈log2(max depth among active chains)/n_jumps⌉ + 1 —
    independent of how deep the untouched components are. Same kwargs and
    kernel path as ``compress_full``.

    Args:
      p: int32[n] parent table (roots self-point).
      active: bool[n] scope mask. Must be closed under ``p`` — every chain
        starting at an active vertex stays inside ``active`` (component-
        closed masks, e.g. "every vertex whose component had a cut",
        satisfy this; a chain that escapes the mask stops at the first
        inactive vertex instead of its true root).

    Returns:
      int32[n]: chain roots where ``active``, identity elsewhere (merge
      with the caller's cached representative array via ``jnp.where``).
    """
    n = p.shape[0]
    verts = jnp.arange(n, dtype=p.dtype)
    return compress_full(jnp.where(active, p, verts), **kwargs)


_COMBINE = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


@partial(jax.jit, static_argnames=("op", "n_jumps", "return_syncs"))
def reduce_to_root(parent: jnp.ndarray, payload: jnp.ndarray,
                   op: str = "add", *, n_jumps: int = DEFAULT_JUMPS,
                   return_syncs: bool = False):
    """Pointer doubling with a payload combine along every v→root path.

    The payload-reduce generalization of ``rank_to_root`` (DESIGN.md §3):
    the same ⌈log2(depth)/n_jumps⌉ + 1 sync contract, but each doubling
    step also folds the payload of the jumped-over segment, so the result
    is ``op`` over all vertices on the path from v to its root
    (inclusive of both endpoints).

    Args:
      parent: int32[n] self-rooted parent table (roots self-point; must be
        acyclic — this is a forest primitive, not a validator).
      payload: [n] per-vertex values, any dtype ``op`` supports. For
        ``op="add"`` the payload at roots must be the additive identity
        (0): doubling steps past convergence re-fold ``payload[root]``,
        which is a no-op only for idempotent ops (min/max) or identity
        payloads. ``rank_to_root`` satisfies this by construction.
      op: "add" | "min" | "max".
      n_jumps: doubling steps chained between convergence checks.
      return_syncs: also return the ``jnp.any`` convergence-check count.

    Returns:
      ``(red, root)`` — red[v] = op over payload on v's root path,
      root[v] = the chain's fixed point; plus ``syncs`` if requested.
    """
    combine = _COMBINE[op]

    def body(state):
        red, hop, _, syncs = state
        for _ in range(n_jumps):
            red = combine(red, red[hop])
            hop = hop[hop]
        return red, hop, jnp.any(hop != hop[hop]), syncs + 1

    red, hop, _, syncs = jax.lax.while_loop(
        lambda s: s[2], body,
        (payload, parent, jnp.bool_(True), jnp.int32(0)))
    # Uniform inclusive-of-root semantics: the loop may exit with red[v]
    # covering [v, root) only; one more fold of red[hop] (= payload[root],
    # stable at the fixed point) closes the interval for every vertex.
    red = combine(red, red[hop])
    return (red, hop, syncs) if return_syncs else (red, hop)


@partial(jax.jit, static_argnames=("n_jumps", "return_syncs"))
def rank_to_root(parent: jnp.ndarray, *, n_jumps: int = DEFAULT_JUMPS,
                 return_syncs: bool = False):
    """Pointer doubling with additive payload on a self-rooted parent array.

    The unit-payload ``op="add"`` instance of ``reduce_to_root``
    (DESIGN.md §3). Returns ``(depth, root)``: depth[v] = int32 #edges
    from v to its root, root[v] = the chain's fixed point. Roots carry
    depth 0 and hop = self, so extra chained steps past convergence are
    exact no-ops (``depth += depth[root] == 0``).

    Args:
      parent: int32[n] self-rooted acyclic parent table.
    """
    n = parent.shape[0]
    depth0 = (parent != jnp.arange(n, dtype=parent.dtype)).astype(jnp.int32)
    return reduce_to_root(parent, depth0, "add", n_jumps=n_jumps,
                          return_syncs=return_syncs)


@partial(jax.jit, static_argnames=("op", "use_kernel", "interpret"))
def segment_reduce(values: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                   op: str = "min", *, use_kernel: bool = False,
                   interpret: bool | None = None):
    """Idempotent range reduction: out[q] = op over values[lo[q] .. hi[q]].

    The payload-reduce analogue of ``jump_k`` on the shift successor
    ``i ↦ i + 2^k``: level k of the doubling (sparse) table holds
    ``T[k][i] = op over values[i : i + 2^k]``, built in ⌈log2 n⌉ chained
    doubling steps with zero convergence syncs (the table is
    depth-oblivious). Each query folds the two power-of-two segments
    covering [lo, hi] — which double-count their overlap, hence the
    idempotency requirement. This is the subtree low/high primitive for
    the biconnectivity layer (DESIGN.md §4): with ``values`` laid out in
    preorder, subtree(v) is the contiguous query
    ``[pre[v], pre[v] + size[v] - 1]``.

    Args:
      values: [n] array, any dtype ``op`` supports.
      lo, hi: int32[q] inclusive query bounds, ``0 <= lo <= hi < n``.
      op: "min" | "max" (idempotent ops only — "add" would double-count).
      use_kernel: build the sparse table in one whole-table Pallas launch
        (``kernels.segment_table``; the query fold stays XLA-side).
      interpret: Pallas interpret mode; None → ``default_interpret()``.

    Returns:
      [q] array of per-query reductions, same dtype as ``values``.
    """
    if op not in ("min", "max"):
        raise ValueError(f"segment_reduce needs an idempotent op, got {op!r}")
    combine = _COMBINE[op]
    n = values.shape[0]
    levels = max(1, (n - 1).bit_length())
    if use_kernel:
        if interpret is None:
            interpret = default_interpret()
        from repro.kernels.segment_table.ops import segment_table
        table = segment_table(values, levels=levels, op=op,
                              interpret=interpret)  # [levels+1, n]
    else:
        rows = [values]
        t = values
        for k in range(levels):
            # The shift successor i ↦ i + 2^k is static: a slice beats a
            # gather (chained whole-table gathers cost XLA quadratic
            # compile time — measured 37 s at n = 2000). Off-the-end
            # positions fold T[k][n-1], which covers {n-1} ⊆ any suffix,
            # so the fold is an idempotent no-op (add would be wrong
            # here).
            s = 1 << k
            if s < n:
                shifted = jnp.concatenate(
                    [t[s:], jnp.broadcast_to(t[n - 1], (s,))])
            else:
                shifted = jnp.broadcast_to(t[n - 1], (n,))
            t = combine(t, shifted)
            rows.append(t)
        table = jnp.stack(rows)                  # [levels+1, n]

    return _fold_queries(table, lo, hi, levels, combine)


def _fold_queries(table, lo, hi, levels, combine):
    """Fold the two power-of-two segments covering each [lo, hi] query."""
    length = hi - lo + 1
    # k = floor(log2(length)), int-exact (no float log at segment bounds).
    k = jnp.zeros_like(length)
    for j in range(1, levels + 1):
        k = k + (length >= (1 << j)).astype(length.dtype)
    span = jnp.left_shift(jnp.int32(1), k)       # 2^k <= length < 2^(k+1)
    return combine(table[k, lo], table[k, jnp.maximum(hi - span + 1, lo)])


@partial(jax.jit, static_argnames=("op", "return_syncs"))
def segment_reduce_scoped(values: jnp.ndarray, lo: jnp.ndarray,
                          hi: jnp.ndarray, active: jnp.ndarray,
                          op: str = "min", *, return_syncs: bool = False):
    """Activity-masked ``segment_reduce``: only *active* queries matter.

    The BCC analogue of ``compress_scoped`` (DESIGN.md §10): where
    ``segment_reduce`` builds the full ⌈log2 n⌉-level doubling sparse
    table unconditionally (depth-oblivious, zero convergence syncs),
    this variant builds levels in a ``while_loop`` that stops as soon as
    ``2^k`` covers the longest *active* query — so when a batch dirties
    only small components, the table build costs
    ⌈log2(max active length)⌉ doubling steps instead of ⌈log2 n⌉
    regardless of how large the clean remainder is. The per-level shift
    is a clamped gather (the dynamic shift amount rules out the static
    slice trick, but there is exactly one gather in the loop body, so
    the chained-gather XLA compile blowup the static path dodges cannot
    occur here). No kernel path: the Pallas ``segment_table`` build has
    a static grid, which is incompatible with the dynamic level count —
    the scoped variant exists precisely to make that count dynamic.

    Args:
      values: [n] array, any dtype ``op`` supports.
      lo, hi: int32[q] inclusive query bounds, ``0 <= lo <= hi < n``.
      active: bool[q] — queries that must be answered exactly. Inactive
        queries return a *defined but arbitrary* value (a partial fold
        over however many levels were built); callers merge them with a
        cached answer (`jnp.where(active, out, cached)`).
      op: "min" | "max" (idempotent ops only).
      return_syncs: also return the number of doubling levels built
        (int32) — the device-independent cost the dynamic-BCC
        benchmarks track (DESIGN.md §10).

    Returns:
      [q] per-query reductions (exact where ``active``), plus the level
      count if ``return_syncs``.
    """
    if op not in ("min", "max"):
        raise ValueError(f"segment_reduce needs an idempotent op, got {op!r}")
    combine = _COMBINE[op]
    n = values.shape[0]
    levels = max(1, (n - 1).bit_length())
    idx = jnp.arange(n, dtype=jnp.int32)
    max_len = jnp.max(jnp.where(active, hi - lo + 1, 1)).astype(jnp.int32)

    # Unbuilt rows are initialized to row 0 (= values) so inactive
    # queries index defined data; built rows overwrite in place.
    table0 = jnp.broadcast_to(values, (levels + 1, n))

    def body(state):
        table, t, k = state
        s = jnp.left_shift(jnp.int32(1), k)
        t = combine(t, t[jnp.minimum(idx + s, n - 1)])
        return table.at[k + 1].set(t), t, k + 1

    def cond(state):
        _table, _t, k = state
        return (jnp.left_shift(jnp.int32(1), k) < max_len) & (k < levels)

    table, _, built = jax.lax.while_loop(cond, body,
                                         (table0, values, jnp.int32(0)))
    out = _fold_queries(table, lo, hi, levels, combine)
    return (out, built) if return_syncs else out


@partial(jax.jit, static_argnames=("n_jumps", "use_kernel", "interpret",
                                   "return_syncs"))
def wyllie_rank(succ: jnp.ndarray, valid: jnp.ndarray, *,
                n_jumps: int = DEFAULT_JUMPS, use_kernel: bool = False,
                interpret: bool | None = None, return_syncs: bool = False):
    """Wyllie list ranking: d[e] = #list elements after e (DESIGN.md §3).

    −1-sentinel successor convention (Euler tour lists). The pure-XLA path
    chains ``n_jumps`` (dist, succ) doubling steps per ``jnp.any`` sync;
    the kernel path launches the multi-step list_rank Pallas kernel on
    once-padded 2-D tables. ``return_syncs`` counts convergence checks on
    both paths.

    Args:
      succ: int32[n] successor table; −1 terminates a list. Disjoint lists
        (one per Euler-tour component) rank independently.
      valid: bool[n] slot validity (padding slots rank 0).

    Returns:
      int32[n] distances to each element's own list end, or ``(d, syncs)``.
    """
    d0 = jnp.where(valid & (succ != NO_SUCC), 1, 0).astype(jnp.int32)

    if use_kernel:
        if interpret is None:
            interpret = default_interpret()
        from repro.kernels.list_rank.list_rank import list_rank_double_pallas
        from repro.kernels.list_rank.ops import pad_to_tile
        succ2d, dist2d, n = pad_to_tile(succ, d0)

        def kbody(state):
            s, d, syncs = state
            s2, d2 = list_rank_double_pallas(s, d, n_steps=n_jumps,
                                             interpret=interpret)
            return s2, d2, syncs + 1

        def kcond(state):
            s, _d, _syncs = state
            return jnp.any(s != NO_SUCC)

        _, dist2d, syncs = jax.lax.while_loop(
            kcond, kbody, (succ2d, dist2d, jnp.int32(0)))
        d = dist2d.reshape(-1)[:n]
        return (d, syncs) if return_syncs else d

    def body(state):
        d, s, syncs = state
        for _ in range(n_jumps):
            has = s != NO_SUCC
            safe = jnp.where(has, s, 0)
            d = jnp.where(has, d + d[safe], d)
            s = jnp.where(has, s[safe], s)
        return d, s, syncs + 1

    def cond(state):
        _d, s, _syncs = state
        return jnp.any(s != NO_SUCC)

    d, _, syncs = jax.lax.while_loop(cond, body, (d0, succ, jnp.int32(0)))
    return (d, syncs) if return_syncs else d
