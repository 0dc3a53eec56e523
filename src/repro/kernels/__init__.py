"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel lives in its own subpackage with three modules:
  <name>.py — the ``pl.pallas_call`` body with explicit BlockSpec tiling;
  ops.py    — the jit'd public wrapper (padding, grid, interpret switch);
  ref.py    — the pure-jnp oracle used by the allclose test sweeps.

Kernels (all validated in interpret mode on CPU). For a TPU (v5e, JAX
0.9.0) only ``segment_table`` compiles; the others do a whole-table
``jnp.take`` that the TPU compiler refuses at every size ("Only 2D gather
is supported"), so on a TPU they raise that error and the serving path
runs pure XLA (``tests/test_tpu_compile.py`` keeps them as strict xfails):
  pointer_jump   k-step pointer doubling with the parent table VMEM-resident
                 (the paper's "five jumps between global syncs", restated for
                 the HBM→VMEM hierarchy).
  list_rank      Wyllie list-ranking step: pointer doubling + additive payload.
  hook_edges     edge-centric hooking scan: gather both endpoint reps, emit
                 cross-edge hook proposals (min/max alternation).
  frontier_relax BFS edge relaxation: frontier/undiscovered tests per edge.
  embed_bag      gather + segment-reduce (recsys embedding bag, GNN message
                 aggregation substrate).
  segment_table  doubling sparse-table build for ``compress.segment_reduce``
                 (slice-shift successor, whole table in one launch).
"""


def auto_interpret() -> bool:
    """Shared interpret-mode dispatch: Mosaic on TPU, the Pallas
    interpreter elsewhere. Every ops wrapper resolves ``interpret=None``
    through this single policy (see DESIGN.md §3)."""
    import jax

    return jax.default_backend() != "tpu"
