"""Compile the main path and every Pallas kernel for a described v5e chip.

Nothing runs: the TPU compiler is installed here and compiles for a chip
that is described, not attached (``topologies.get_topology_desc``). A
refusal here is what the chip would refuse. Widths are the chip smoke's
(``chip_smoke.py``): the ``grid_2048`` churn stream — V = 4,194,304, a
4,192,256-slot edge pool, 4096-event batches.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file. Kernels the compiler still refuses are strict xfails carrying its
message, so a fix flips them to failures that ask for the mark to go.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = 2048 * 2048                 # grid_2048 vertices
CAP = N // 2 - 1024             # churn keeps half of grid_2048's edges live
BATCH = 4096
ROWS = N // 128


@pytest.fixture(scope="module")
def topo():
    # Without a log directory the TPU compiler logs to a fixed system path.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, **static):
    return jax.jit(partial(fn, **static)).lower(*args).compile()


# -- main path: the programs the smoke runs, at its widths --------------------

def _forest(S):
    from repro.dynamic.forest import DynamicForest
    return DynamicForest(
        N, S((N,)), S((N,)), S((CAP,)), S((CAP,)), S((CAP,), jnp.bool_),
        S((CAP,), jnp.bool_), S((N,), jnp.bool_), S(()))


@pytest.fixture(scope="module")
def S(one_chip):
    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return shape


def test_compress_full_compiles(S):
    from repro.core.compress import compress_full
    compiled = compress_full.lower(S((N,))).compile()
    assert compiled.memory_analysis().argument_size_in_bytes == N * 4


def test_bfs_compiles(S):
    """``grid2d(2048)``'s half-edges; sparse and dense levels both."""
    from repro.core.bfs import bfs_rst
    from repro.core.graph import Graph
    e = 4 * 2047 * 2048
    bfs_rst.lower(Graph(N, S((e,)), S((e,))), S(())).compile()


def test_tour_numbering_compiles(S):
    from repro.core.euler import tour_numbering
    tour_numbering.lower(S((N,))).compile()


def test_apply_batch_compiles(S):
    from repro.dynamic.forest import apply_batch, edge_slots
    state = _forest(S)
    edge_slots.lower(state, S((BATCH,)), S((BATCH,))).compile()
    apply_batch.lower(state, S((BATCH,)), S((BATCH,)),
                      S((CAP,), jnp.bool_)).compile()


def test_bcc_refresh_compiles(S, one_chip):
    from repro.core.euler import tour_numbering
    from repro.dynamic.bcc import _refresh_full, _refresh_incremental

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    state = _forest(S)
    tn = on_chip(jax.eval_shape(tour_numbering, S((N,))))
    cached = on_chip(jax.eval_shape(_refresh_full, state, tn))
    _refresh_incremental.lower(state, tn, cached).compile()


def test_segment_table_kernel_compiles(S):
    from repro.kernels.segment_table.ops import segment_table
    compiled = _compile(segment_table, S((N,)), levels=22, op="min",
                        interpret=False)
    assert "tpu_custom_call" in compiled.as_text()


# -- the kernels the compiler refuses -----------------------------------------

_GATHER = pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic: 'Only 2D gather is supported' — a whole-table "
           "jnp.take on a VMEM-resident table has no TPU lowering")


def _pointer_jump(S):
    from repro.kernels.pointer_jump.pointer_jump import pointer_jump_pallas
    return _compile(pointer_jump_pallas, S((ROWS, 128)), n_jumps=5,
                    interpret=False)


def _pointer_jump_double(S):
    from repro.kernels.pointer_jump.pointer_jump import (
        pointer_jump_double_pallas)
    return _compile(pointer_jump_double_pallas, S((ROWS, 128)), n_jumps=5,
                    interpret=False)


def _list_rank(S):
    from repro.kernels.list_rank.list_rank import list_rank_pallas
    return _compile(list_rank_pallas, S((ROWS, 128)), S((ROWS, 128)),
                    n_steps=5, interpret=False)


def _list_rank_double(S):
    from repro.kernels.list_rank.list_rank import list_rank_double_pallas
    return _compile(list_rank_double_pallas, S((ROWS, 128)),
                    S((ROWS, 128)), n_steps=5, interpret=False)


def _hook_edges(S):
    from repro.kernels.hook_edges.hook_edges import hook_edges_pallas
    e_rows = 2 * ROWS
    return _compile(hook_edges_pallas, S((e_rows, 128)), S((e_rows, 128)),
                    S((ROWS, 128)), S((1, 1)), n_nodes=N, interpret=False)


def _frontier_relax(S):
    from repro.kernels.frontier_relax.frontier_relax import (
        frontier_relax_pallas)
    e_rows = 2 * ROWS
    return _compile(frontier_relax_pallas, S((e_rows, 128)),
                    S((e_rows, 128)), S((ROWS, 128)), S((1, 1)),
                    interpret=False)


def _embed_bag(S):
    from repro.kernels.embed_bag.embed_bag import embed_bag_pallas
    return _compile(embed_bag_pallas, S((64, 4)), S((64, 4), jnp.float32),
                    S((1024, 128), jnp.float32), interpret=False)


@pytest.mark.parametrize("build", [
    pytest.param(_pointer_jump, id="pointer_jump", marks=_GATHER),
    pytest.param(_pointer_jump_double, id="pointer_jump_double",
                 marks=_GATHER),
    pytest.param(_list_rank, id="list_rank", marks=_GATHER),
    pytest.param(_list_rank_double, id="list_rank_double", marks=_GATHER),
    pytest.param(_hook_edges, id="hook_edges", marks=_GATHER),
    pytest.param(_frontier_relax, id="frontier_relax", marks=_GATHER),
    pytest.param(_embed_bag, id="embed_bag", marks=pytest.mark.xfail(
        strict=True, raises=ValueError,
        reason="Mosaic: 'Shape mismatch in input, indices and output' — "
               "the row gather jnp.take(table, idx) has no TPU lowering")),
])
def test_gather_kernel_compiles(S, build):
    build(S)
