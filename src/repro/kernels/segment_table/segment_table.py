"""Doubling sparse-table Pallas kernel (the ``segment_reduce`` build).

Level k of the table holds ``T[k][i] = op over values[i : i + 2^k]`` —
payload-reduce ``jump_k`` on the shift successor ``i ↦ i + 2^k``
(DESIGN.md §4). The build is depth-oblivious (exactly ⌈log2 n⌉ doubling
steps, zero convergence syncs), so there is no chain-vs-doubling split.

Layout: values are viewed as a padded (rows, 128) matrix cut into
(BLOCK_ROWS, 128) blocks, and the grid is (levels + 1, blocks), run in
order. Step (k, j) writes block j of level k to the output and to a
two-level ping-pong scratch in VMEM, from which step (k + 1, ·) reads its
two source blocks. Fast memory holds two levels of the table (2 · n · 4
bytes) plus four blocks, never the whole (levels + 1) · n output.

The shift successor is static per level, so the shifted operand is built
from at most two scratch blocks with ``pltpu.roll`` and iota masks — no
gather and no unaligned concatenate, both of which the TPU compiler
refuses. Three cases, by the shift s = 2^(k−1):

  * s ≥ one block: a whole block, ``s / block`` blocks further on;
  * 128 ≤ s < one block: a sublane roll of this block and the next;
  * s < 128: a lane roll, whose wrapped lanes come from the next row.

Off-the-end positions fold the op identity (``fill``); the wrapper
(``ops.segment_table``) pads the values with it, which makes those folds
no-ops exactly like the XLA path's idempotent clamp folds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 256        # (256, 128) int32 block = 128 KiB
#: Scoped-VMEM ceiling this kernel asks for (v5e has 128 MiB of VMEM).
VMEM_LIMIT_BYTES = 100 * 2**20


def block_rows(rows: int) -> int:
    """Block height for a table of ``rows`` (8-aligned) rows."""
    return min(BLOCK_ROWS, rows)


def vmem_bytes(rows: int, itemsize: int = 4) -> int:
    """Fast memory the kernel needs: two table levels + 4 I/O blocks."""
    return (2 * rows + 4 * block_rows(rows)) * LANES * itemsize


def _shifted(buf_ref, base, j, *, s: int, nb: int, b: int, fill):
    """Block j of level t shifted by s: out[i] = t[i + s] (fill past end).

    ``buf_ref[base + i]`` holds block i of t.
    """
    blk = b * LANES
    if s >= blk:
        src = j + s // blk
        x = buf_ref[base + jnp.minimum(src, nb - 1)]
        return jnp.where(src < nb, x, fill)
    cur = buf_ref[base + j]
    nxt = jnp.where(j + 1 < nb, buf_ref[base + jnp.minimum(j + 1, nb - 1)],
                    fill)
    row = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 0)
    if s >= LANES:
        q = s // LANES
        return jnp.where(row < b - q, pltpu.roll(cur, b - q, 0),
                         pltpu.roll(nxt, b - q, 0))
    col = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)
    a = pltpu.roll(cur, LANES - s, 1)      # a[r, c] = cur[r, (c + s) % 128]
    a_next = pltpu.roll(nxt, LANES - s, 1)
    if b > 1:
        below = jnp.where(row < b - 1, pltpu.roll(a, b - 1, 0),
                          pltpu.roll(a_next, b - 1, 0))
    else:
        below = a_next
    return jnp.where(col < LANES - s, a, below)


def _segment_table_kernel(v_ref, out_ref, buf_ref, *, levels: int, nb: int,
                          fill, op: str):
    combine = jnp.minimum if op == "min" else jnp.maximum
    b = v_ref.shape[0]
    k = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        x = v_ref[...]
        buf_ref[j] = x
        out_ref[...] = x

    for lvl in range(1, levels + 1):
        @pl.when(k == lvl)
        def _(lvl=lvl):
            prev = ((lvl - 1) % 2) * nb
            cur = (lvl % 2) * nb
            t = buf_ref[prev + j]
            res = combine(t, _shifted(buf_ref, prev, j, s=1 << (lvl - 1),
                                      nb=nb, b=b, fill=fill))
            buf_ref[cur + j] = res
            out_ref[...] = res


def segment_table_pallas(v2d: jnp.ndarray, *, levels: int, fill, op: str,
                         interpret: bool = True) -> jnp.ndarray:
    """v2d: [R, 128] padded values → [(levels + 1) · R, 128] table.

    ``R`` must be a multiple of ``block_rows(R)``. ``fill`` must be the
    op identity (max for min, min for max); pad slots of ``v2d`` must
    already carry it.
    """
    rows = v2d.shape[0]
    b = block_rows(rows)
    assert v2d.shape[1] == LANES and rows % b == 0 and b % 8 == 0
    need = vmem_bytes(rows, v2d.dtype.itemsize)
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"segment_table: {rows * LANES} values need {need} bytes of "
            f"VMEM (> {VMEM_LIMIT_BYTES}); use the XLA path "
            "(use_kernel=False) at this size")
    nb = rows // b
    kernel = functools.partial(_segment_table_kernel, levels=levels, nb=nb,
                               fill=fill, op=op)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(((levels + 1) * rows, LANES),
                                       v2d.dtype),
        # Level 0 streams the input in; later levels keep the last block
        # index, so the input is fetched once.
        in_specs=[pl.BlockSpec(
            (b, LANES), lambda k, j: (jnp.where(k == 0, j, nb - 1), 0))],
        out_specs=pl.BlockSpec((b, LANES), lambda k, j: (k * nb + j, 0)),
        scratch_shapes=[pltpu.VMEM((2 * nb, b, LANES), v2d.dtype)],
        grid=(levels + 1, nb),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(VMEM_LIMIT_BYTES, need + 8 * 2**20)),
        interpret=interpret,
    )(v2d)
