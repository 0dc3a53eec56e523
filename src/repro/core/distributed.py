"""Multi-chip rooted spanning tree: the paper's algorithm at pod scale.

The paper runs on one GPU. To make RST construction a first-class primitive
of a 1000+-node framework, this module maps the hooking / pointer-jumping
rounds onto a device mesh with ``shard_map``:

  * **edges are sharded** across the mesh axis (the O(E) side scales out);
  * **the parent table is replicated** (O(V) per chip) — hook proposals are
    combined across chips with an elementwise min-reduction
    (``lax.pmin``-style via ``psum``/min tricks), the multi-chip analogue of
    the single-GPU atomicMin;
  * pointer jumping is purely local (replicated table ⇒ zero collectives),
    so each round costs exactly **two all-reduce-min collectives** (hook +
    winner-edge), independent of graph diameter.

Communication cost per round: 2 × n × 4 bytes all-reduce. Total rounds
O(log n) ⇒ collective volume O(n log n) — versus BFS whose level loop costs
one frontier all-reduce *per level*, i.e. O(diam) rounds. The paper's
diameter argument strengthens at scale (DESIGN.md §2).

For V beyond per-chip memory the design extends to vertex-partitioned
tables with all-to-all rep exchange; the replicated variant is what the
256-chip dry-run exercises (n=16M table = 64 MB replicated, fine).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compress import compress_full

INF32 = jnp.iinfo(jnp.int32).max


def _allmin(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Elementwise min across devices (all-reduce-min)."""
    neg = -x
    m = jax.lax.pmax(neg, axis_name)
    return -m


def distributed_cc_spanning_forest(mesh: Mesh, axis: str = "data"):
    """Build the sharded connectivity + spanning-forest step function.

    Returns a jit'd function ``f(src, dst, n_nodes) -> (rep, forest_mask,
    rounds)`` where src/dst are GLOBAL edge arrays sharded over ``axis``
    (callers pass arrays whose leading dim divides the axis size) and
    forest_mask is sharded the same way.
    """
    axis_size = mesh.shape[axis]

    def step_fn(src, dst, edge_gid, p0):
        n = p0.shape[0]

        # Pointer jumping on the replicated table is purely local — route
        # it through the shared engine (amortized convergence syncs).
        pointer_jump_full = compress_full

        def body(state):
            p, forest, rnd, _ = state
            ru = p[src]
            rv = p[dst]
            cross = ru != rv
            use_min = jnp.bool_(True)   # pure min-hooking (see connectivity.py)
            lo = jnp.minimum(ru, rv)
            hi = jnp.maximum(ru, rv)
            tgt = jnp.where(use_min, hi, lo)
            val = jnp.where(use_min, lo, hi)

            # Local hook proposal (min-encoded for both directions) ...
            enc = jnp.where(use_min, val, n - 1 - val)
            local = jnp.full((n,), INF32, jnp.int32).at[tgt].min(
                jnp.where(cross, enc, INF32))
            # ... combined across chips: ONE all-reduce-min.
            glob = _allmin(local, axis)
            got = glob != INF32
            new_parent = jnp.where(use_min, glob, n - 1 - glob)
            p_next = jnp.where(got, new_parent, p)

            # Winner edge (global edge id): second all-reduce-min.
            achieved = cross & got[tgt] & (new_parent[tgt] == val)
            local_win = jnp.full((n,), INF32, jnp.int32).at[tgt].min(
                jnp.where(achieved, edge_gid, INF32))
            glob_win = _allmin(local_win, axis)
            is_winner = achieved & (glob_win[tgt] == edge_gid)
            forest = forest | is_winner

            p_next = pointer_jump_full(p_next)
            changed = jnp.any(got)
            return p_next, forest, rnd + 1, changed

        def cond(state):
            _p, _f, rnd, changed = state
            return changed & (rnd < n)

        forest0 = jnp.zeros(src.shape, jnp.bool_)
        p, forest, rounds, _ = jax.lax.while_loop(
            cond, body, (p0, forest0, jnp.int32(0), jnp.bool_(True)))
        return p, forest, rounds - 1

    sharded = jax.shard_map(
        step_fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(), P(axis), P()),
        check_vma=False,
    )

    @partial(jax.jit, static_argnames=("n_nodes",))
    def run(src, dst, *, n_nodes: int):
        m = src.shape[0]
        assert m % axis_size == 0, (
            f"edge count {m} must divide mesh axis {axis}={axis_size}; "
            "pad with self-loop edges (0, 0)")
        gid = jnp.arange(m, dtype=jnp.int32)
        p0 = jnp.arange(n_nodes, dtype=jnp.int32)
        return sharded(src, dst, gid, p0)

    return run


def input_specs_rst(n_nodes: int, n_half_edges: int, mesh: Mesh,
                    axis: str = "data"):
    """ShapeDtypeStruct stand-ins for the dry-run (no allocation)."""
    ns_e = NamedSharding(mesh, P(axis))
    return dict(
        src=jax.ShapeDtypeStruct((n_half_edges,), jnp.int32, sharding=ns_e),
        dst=jax.ShapeDtypeStruct((n_half_edges,), jnp.int32, sharding=ns_e),
    )
