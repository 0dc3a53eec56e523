"""BFS edge-relaxation Pallas kernel (edge-centric Merrill baseline).

Per edge block: gather both endpoint distances from the VMEM-resident dist
table and emit the frontier-expansion mask

    active(e) = (dist[src] == level) & (dist[dst] == INF)

The deterministic parent scatter-min stays in XLA. The kernel fuses the two
gathers and both compares into one pass over the edge list — one launch
per BFS level, the Θ(diam) launch count the paper attributes BFS's poor
high-diameter behavior to. The TPU compiler refuses its ``jnp.take`` at
every size, as for pointer_jump: interpret mode only (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 8
INF32 = jnp.iinfo(jnp.int32).max


def _frontier_relax_kernel(src_ref, dst_ref, dist_ref, level_ref, out_ref):
    dist = dist_ref[...].reshape(-1)
    d_src = jnp.take(dist, src_ref[...], axis=0)
    d_dst = jnp.take(dist, dst_ref[...], axis=0)
    level = level_ref[0, 0]
    out_ref[...] = ((d_src == level) & (d_dst == INF32)).astype(jnp.int32)


def frontier_relax_pallas(src2d, dst2d, dist2d, level, *,
                          interpret: bool = True):
    rows = src2d.shape[0]
    dist_rows = dist2d.shape[0]
    assert src2d.shape[1] == LANES and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    full = pl.BlockSpec((dist_rows, LANES), lambda i: (0, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _frontier_relax_kernel,
        out_shape=jax.ShapeDtypeStruct(src2d.shape, jnp.int32),
        in_specs=[blk, blk, full, scalar],
        out_specs=blk,
        grid=grid,
        interpret=interpret,
    )(src2d, dst2d, dist2d, level)
