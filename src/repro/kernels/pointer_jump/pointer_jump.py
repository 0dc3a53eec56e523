"""k-step pointer-jumping Pallas kernel.

The paper's pointer-jumping optimization performs five jumps per thread
between global synchronizations to amortize kernel-launch cost. The TPU
restatement: the whole parent table is held VMEM-resident (one HBM→VMEM
fetch), each grid step processes a (ROWS, 128)-tile of vertices, and the k
gathers chain *inside* the kernel so intermediate hops never round-trip
through HBM.

Layout: vertex ids are viewed as a (n/128, 128) int32 matrix — rows of 128
lanes, the native VREG lane width — and blocks are (BLOCK_ROWS, 128) tiles,
8-sublane aligned. The gather is a flat ``jnp.take`` on the VMEM-resident
table: exact in interpret mode, but the TPU compiler refuses it at every
size ("Only 2D gather is supported" — Mosaic gathers only within a
vreg), so neither kernel here runs on a chip (``tests/test_tpu_compile.py``
holds them as strict xfails; DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 8  # (8, 128) int32 tile = 4 KB per block


def _pointer_jump_kernel(p_block_ref, p_full_ref, out_ref, *, n_jumps: int):
    """out[i] = P^(n_jumps+1)(i): chain k gathers without leaving VMEM.

    *Chain* semantics — the table snapshot is fixed, so each gather advances
    one hop. Used for the fixed-hop primitive (``pointer_jump_k``)."""
    idx = p_block_ref[...]
    table = p_full_ref[...].reshape(-1)
    for _ in range(n_jumps):
        idx = jnp.take(table, idx, axis=0)
    out_ref[...] = idx


def _pointer_jump_double_kernel(p_ref, out_ref, *, n_jumps: int):
    """k *doubling* steps ``table = table[table]`` on the whole VMEM table.

    Each step squares the compressed distance (2^k-fold compression per
    launch vs k+1 hops for the chain kernel), which is what gives the
    convergence path its ⌈log2(depth)/k⌉ + 1 sync bound. The whole table
    must be updated between steps, so this kernel runs grid=1 with the
    table as a single block — the same VMEM-residency budget as the chain
    kernel, which already broadcasts the full table to every block.
    """
    table = p_ref[...].reshape(-1)
    for _ in range(n_jumps):
        table = jnp.take(table, table, axis=0)
    out_ref[...] = table.reshape(p_ref.shape)


def pointer_jump_pallas(p2d: jnp.ndarray, *, n_jumps: int,
                        interpret: bool = True) -> jnp.ndarray:
    """p2d: int32[R, 128] parent table (padded; pad rows self-point)."""
    rows = p2d.shape[0]
    assert p2d.shape[1] == LANES and rows % BLOCK_ROWS == 0
    grid = (rows // BLOCK_ROWS,)
    kernel = functools.partial(_pointer_jump_kernel, n_jumps=n_jumps)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (0, 0)),  # full table
        ],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        grid=grid,
        interpret=interpret,
    )(p2d, p2d)


def pointer_jump_double_pallas(p2d: jnp.ndarray, *, n_jumps: int,
                               interpret: bool = True) -> jnp.ndarray:
    """k doubling steps over the whole padded table in one launch."""
    rows = p2d.shape[0]
    assert p2d.shape[1] == LANES and rows % BLOCK_ROWS == 0
    kernel = functools.partial(_pointer_jump_double_kernel, n_jumps=n_jumps)
    full = pl.BlockSpec((rows, LANES), lambda i: (0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(p2d.shape, p2d.dtype),
        in_specs=[full],
        out_specs=full,
        grid=(1,),
        interpret=interpret,
    )(p2d)
