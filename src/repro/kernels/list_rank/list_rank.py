"""Wyllie list-ranking Pallas kernel: pointer doubling with additive payload.

One launch performs k chained (succ, dist) doubling steps entirely in VMEM —
the Euler-tour analogue of the multi-jump trick. Semantics per step:

    has  = succ != -1
    dist = dist + (has ? dist[succ] : 0)
    succ = has ? succ[succ] : -1

Layout matches pointer_jump: (R, 128) int32 tiles, full tables VMEM-resident,
8-sublane-aligned blocks — and, like pointer_jump, the whole-table
``jnp.take`` is refused by the TPU compiler at every size, so this kernel
runs in interpret mode only (DESIGN.md §3). Sentinel -1 terminates lists; padded slots carry
succ = -1, dist = 0 and are inert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 8
NO_SUCC = -1


def _list_rank_kernel(succ_blk_ref, dist_blk_ref, succ_full_ref,
                      dist_full_ref, succ_out_ref, dist_out_ref,
                      *, n_steps: int):
    succ = succ_blk_ref[...]
    dist = dist_blk_ref[...]
    succ_tab = succ_full_ref[...].reshape(-1)
    dist_tab = dist_full_ref[...].reshape(-1)
    # Chained gathers against one table snapshot give (k+1)-hop chain
    # prefix sums: d'[e] = Σ_{j=0..k} d[s^j(e)], s'[e] = s^{k+1}(e). The
    # invariant d[e] = dist(e, s[e]) telescopes, so the outer convergence
    # loop (ops.py) still yields exact distance-to-end ranks.
    for _ in range(n_steps):
        has = succ != NO_SUCC
        safe = jnp.where(has, succ, 0)
        dist = dist + jnp.where(has, jnp.take(dist_tab, safe, axis=0), 0)
        succ = jnp.where(has, jnp.take(succ_tab, safe, axis=0), NO_SUCC)
    succ_out_ref[...] = succ
    dist_out_ref[...] = dist


def _list_rank_double_kernel(succ_ref, dist_ref, succ_out_ref, dist_out_ref,
                             *, n_steps: int):
    """k true Wyllie *doubling* steps on the whole VMEM-resident tables.

    Unlike the per-block chain kernel above (fixed table snapshot ⇒ k+1
    hops per launch), both tables are updated between steps, so each step
    doubles the covered distance — giving the engine's convergence loop
    its ⌈log2(n)/k⌉ + 1 sync bound. Runs grid=1 (whole-table update),
    same VMEM budget as the chain kernel which already broadcasts both
    full tables to every block.
    """
    succ = succ_ref[...].reshape(-1)
    dist = dist_ref[...].reshape(-1)
    for _ in range(n_steps):
        has = succ != NO_SUCC
        safe = jnp.where(has, succ, 0)
        dist = dist + jnp.where(has, jnp.take(dist, safe, axis=0), 0)
        succ = jnp.where(has, jnp.take(succ, safe, axis=0), NO_SUCC)
    succ_out_ref[...] = succ.reshape(succ_ref.shape)
    dist_out_ref[...] = dist.reshape(dist_ref.shape)


def list_rank_double_pallas(succ2d: jnp.ndarray, dist2d: jnp.ndarray, *,
                            n_steps: int, interpret: bool = True):
    rows = succ2d.shape[0]
    assert succ2d.shape[1] == LANES and rows % BLOCK_ROWS == 0
    kernel = functools.partial(_list_rank_double_kernel, n_steps=n_steps)
    full = pl.BlockSpec((rows, LANES), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(succ2d.shape, succ2d.dtype),
                   jax.ShapeDtypeStruct(dist2d.shape, dist2d.dtype)),
        in_specs=[full, full],
        out_specs=(full, full),
        grid=(1,),
        interpret=interpret,
    )(succ2d, dist2d)


def list_rank_pallas(succ2d: jnp.ndarray, dist2d: jnp.ndarray, *,
                     n_steps: int, interpret: bool = True):
    rows = succ2d.shape[0]
    assert succ2d.shape[1] == LANES and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    kernel = functools.partial(_list_rank_kernel, n_steps=n_steps)
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    full = pl.BlockSpec((rows, LANES), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(succ2d.shape, succ2d.dtype),
                   jax.ShapeDtypeStruct(dist2d.shape, dist2d.dtype)),
        in_specs=[blk, blk, full, full],
        out_specs=(blk, blk),
        grid=grid,
        interpret=interpret,
    )(succ2d, dist2d, succ2d, dist2d)
