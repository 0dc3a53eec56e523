"""Edge-centric hooking-scan Pallas kernel.

For each edge block: gather both endpoint representatives from the
VMEM-resident rep table, detect cross edges, and emit the (target, value)
hook proposal under min- or max-hooking. This fuses the two gathers and the
compare/select logic of the paper's hooking kernel; the deterministic
scatter-min/max reduction stays outside (XLA scatter), replacing CUDA
atomics (DESIGN.md §2).

Outputs per half-edge:
  tgt: root being re-pointed (hi under min-hooking, lo under max-hooking),
       or ``n`` (dropped) for non-cross edges;
  val: proposed new parent (lo resp. hi).

Edge arrays are viewed as (E/128, 128) tiles; the rep table is VMEM-resident.
The TPU compiler refuses its ``jnp.take`` at every size, as for
pointer_jump: interpret mode only (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 8


def _hook_edges_kernel(src_ref, dst_ref, rep_ref, use_min_ref,
                       tgt_ref, val_ref, *, n_nodes: int):
    rep = rep_ref[...].reshape(-1)
    ru = jnp.take(rep, src_ref[...], axis=0)
    rv = jnp.take(rep, dst_ref[...], axis=0)
    cross = ru != rv
    lo = jnp.minimum(ru, rv)
    hi = jnp.maximum(ru, rv)
    use_min = use_min_ref[0, 0] != 0
    tgt = jnp.where(use_min, hi, lo)
    val = jnp.where(use_min, lo, hi)
    tgt_ref[...] = jnp.where(cross, tgt, n_nodes)
    val_ref[...] = val


def hook_edges_pallas(src2d, dst2d, rep2d, use_min, *, n_nodes: int,
                      interpret: bool = True):
    rows = src2d.shape[0]
    rep_rows = rep2d.shape[0]
    assert src2d.shape[1] == LANES and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    kernel = functools.partial(_hook_edges_kernel, n_nodes=n_nodes)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    full = pl.BlockSpec((rep_rows, LANES), lambda i: (0, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(src2d.shape, jnp.int32),
                   jax.ShapeDtypeStruct(src2d.shape, jnp.int32)),
        in_specs=[blk, blk, full, scalar],
        out_specs=(blk, blk),
        grid=grid,
        interpret=interpret,
    )(src2d, dst2d, rep2d, use_min)
