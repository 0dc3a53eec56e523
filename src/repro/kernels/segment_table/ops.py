"""jit'd wrapper for the segment_table kernel (padding + launch plumbing).

``interpret=None`` dispatches from ``jax.default_backend()`` (compiled on
TPU, interpreter elsewhere) via the shared ``repro.kernels.auto_interpret``
policy. The query-side fold stays in ``core.compress.segment_reduce`` —
this wrapper only builds the [levels + 1, n] sparse table.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret as _auto_interpret
from repro.kernels.segment_table.segment_table import (LANES, block_rows,
                                                       segment_table_pallas)


@partial(jax.jit, static_argnames=("levels", "op", "interpret"))
def segment_table(values: jnp.ndarray, *, levels: int, op: str,
                  interpret: bool | None = None) -> jnp.ndarray:
    """[levels + 1, n] doubling sparse table over ``values`` (one launch).

    Pad slots carry the op identity — the kernel's slice-shift doubling
    folds pad values into boundary windows, and only the identity makes
    that a no-op (the padding contract of ``segment_table_pallas``).
    """
    if interpret is None:
        interpret = _auto_interpret()
    with jax.named_scope("segment_table"):
        n = values.shape[0]
        rows = -(-n // (8 * LANES)) * 8            # 8-sublane aligned
        b = block_rows(rows)
        rows = -(-rows // b) * b                   # whole blocks
        n_pad = rows * LANES - n
        if jnp.issubdtype(values.dtype, jnp.integer):
            info = jnp.iinfo(values.dtype)
        else:
            info = jnp.finfo(values.dtype)
        fill = info.max if op == "min" else info.min
        v2d = jnp.concatenate(
            [values,
             jnp.full((n_pad,), fill, values.dtype)]).reshape(-1, LANES)
        out = segment_table_pallas(v2d, levels=levels, fill=fill, op=op,
                                   interpret=interpret)
        return out.reshape(levels + 1, -1)[:, :n]
