"""Embedding-bag Pallas kernel: fixed-hotness gather + reduce.

JAX has no native EmbeddingBag; this kernel is the TPU implementation used
by the DIEN recsys pipeline (multi-hot categorical fields) and as the dense
molecule-batch aggregation substrate for GNNs.

Tiling: grid = (bags/BAG_BLOCK, D/D_TILE). Each program gathers ``hot`` rows
for BAG_BLOCK bags restricted to one D_TILE-wide feature slice and reduces
over the hot axis — the working set is (BAG_BLOCK·hot + BAG_BLOCK) × D_TILE
floats plus the table slice. The table is streamed per D-tile (BlockSpec
partitions the feature axis), so VMEM holds only V × D_TILE of it; for
vocabularies beyond VMEM the production variant keeps the table in ANY/HBM
and double-buffers row DMAs — same body, different memory_space (documented
adaptation, cf. DESIGN.md §2).

sum/mean reduction; per-sample weights optional (weights == None → ones).
The TPU compiler refuses the row ``jnp.take`` ("Shape mismatch in input,
indices and output"): interpret mode only (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BAG_BLOCK = 8
D_TILE = 128


def _embed_bag_kernel(idx_ref, w_ref, table_ref, out_ref, *, mean: bool):
    idx = idx_ref[...]                      # (BAG_BLOCK, hot)
    w = w_ref[...]                          # (BAG_BLOCK, hot)
    table = table_ref[...]                  # (V, D_TILE)
    rows = jnp.take(table, idx.reshape(-1), axis=0)
    rows = rows.reshape(idx.shape[0], idx.shape[1], -1)
    acc = jnp.sum(rows * w[..., None].astype(rows.dtype), axis=1)
    if mean:
        denom = jnp.maximum(jnp.sum(w, axis=1, keepdims=True), 1e-9)
        acc = acc / denom.astype(acc.dtype)
    out_ref[...] = acc


def embed_bag_pallas(idx, weights, table, *, mean: bool = False,
                     interpret: bool = True):
    b, hot = idx.shape
    v, d = table.shape
    assert b % BAG_BLOCK == 0 and d % D_TILE == 0
    grid = (b // BAG_BLOCK, d // D_TILE)
    kernel = functools.partial(_embed_bag_kernel, mean=mean)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        in_specs=[
            pl.BlockSpec((BAG_BLOCK, hot), lambda i, j: (i, 0)),
            pl.BlockSpec((BAG_BLOCK, hot), lambda i, j: (i, 0)),
            pl.BlockSpec((v, D_TILE), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BAG_BLOCK, D_TILE), lambda i, j: (i, j)),
        grid=grid,
        interpret=interpret,
    )(idx, weights, table)
