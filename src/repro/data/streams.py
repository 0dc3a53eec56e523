"""Edge-stream workload generators over the synthetic graph suite.

Serving traffic for the batch-dynamic layer (DESIGN.md §9): each
generator turns a static ``data.graphs`` suite graph into a stream of
fixed-shape update batches — ``StreamBatch`` arrays padded with the
``n_nodes`` sentinel so every batch has identical shapes and the jitted
``dynamic.apply_batch`` compiles exactly once per stream.

Three traffic regimes (numpy-side, deterministic per seed):

  * ``sliding_window`` — batches of edges arrive in a random order and
    expire ``window`` batches later: the timestamped-graph regime
    (temporal networks, session graphs). Live set ≈ window · batch.
  * ``insert_heavy``  — the graph grows toward the full edge set with a
    small deletion rate ``p_delete``: the accretion regime (social /
    citation growth). Mostly exercises the insertion/link path.
  * ``churn``         — starts from a random half of the edges and swaps
    ``batch/2`` live edges for dead ones every step: the steady-state
    regime. Exercises cut + replacement search hardest.

Each generator returns an ``EdgeStream``: the initially-live edges (seed
state for ``dynamic.forest_from_graph`` or replay onto ``forest_empty``)
plus the batch list. Deletions are (u, v) pairs — resolve them to pool
slots with ``dynamic.edge_slots`` (multiset-aware) at apply time.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.graph import Graph


@dataclasses.dataclass(frozen=True)
class StreamBatch:
    """One update batch; all arrays int32, ``n_nodes``-sentinel padded.

    ins_u/ins_v: [batch] edges to insert; del_u/del_v: [batch] edges to
    delete (pairs, not pool slots).
    """

    ins_u: np.ndarray
    ins_v: np.ndarray
    del_u: np.ndarray
    del_v: np.ndarray


@dataclasses.dataclass(frozen=True)
class EdgeStream:
    """A replayable edge-update workload over n_nodes vertices."""

    name: str
    n_nodes: int
    init_u: np.ndarray          # edges live before the first batch
    init_v: np.ndarray
    batches: tuple[StreamBatch, ...]

    @property
    def n_events(self) -> int:
        """Total insert + delete events across all batches."""
        n = self.n_nodes
        return int(sum((b.ins_u < n).sum() + (b.del_u < n).sum()
                       for b in self.batches))


def _edges_of(graph: Graph) -> np.ndarray:
    """The M undirected edges as an int [M, 2] array."""
    m = graph.n_edges
    return np.stack([np.asarray(graph.src[:m]), np.asarray(graph.dst[:m])],
                    axis=1).astype(np.int64)


def _pad(pairs, width: int, n: int):
    """Pad a [k, 2] pair array (or list of pairs) to ``width`` with ``n``."""
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)[:width]
    u = np.full(width, n, np.int32)
    v = np.full(width, n, np.int32)
    u[:pairs.shape[0]] = pairs[:, 0]
    v[:pairs.shape[0]] = pairs[:, 1]
    return u, v


def _mk_batch(ins, dels, batch, n) -> StreamBatch:
    iu, iv = _pad(ins, batch, n)
    du, dv = _pad(dels, batch, n)
    return StreamBatch(ins_u=iu, ins_v=iv, del_u=du, del_v=dv)


def sliding_window(graph: Graph, *, batch: int = 64, window: int = 4,
                   n_batches: int | None = None, seed: int = 0) -> EdgeStream:
    """Edges arrive in random order and expire ``window`` batches later."""
    n = graph.n_nodes
    rng = np.random.default_rng(seed)
    edges = _edges_of(graph)
    order = rng.permutation(edges.shape[0])
    blocks = [edges[order[i:i + batch]]
              for i in range(0, edges.shape[0], batch)]
    if n_batches is not None:
        blocks = blocks[:n_batches]
    batches = []
    for t, blk in enumerate(blocks):
        ins = [tuple(e) for e in blk]
        dels = ([tuple(e) for e in blocks[t - window]]
                if t >= window else [])
        batches.append(_mk_batch(ins, dels, batch, n))
    return EdgeStream(name="sliding_window", n_nodes=n,
                      init_u=np.zeros(0, np.int32),
                      init_v=np.zeros(0, np.int32),
                      batches=tuple(batches))


def insert_heavy(graph: Graph, *, batch: int = 64, p_delete: float = 0.1,
                 n_batches: int | None = None, seed: int = 0) -> EdgeStream:
    """Growth regime: insert toward the full edge set, rare deletions."""
    n = graph.n_nodes
    rng = np.random.default_rng(seed)
    edges = _edges_of(graph)
    order = rng.permutation(edges.shape[0])
    live: list[tuple[int, int]] = []
    batches = []
    n_ins = max(1, batch - int(batch * p_delete))
    total = (edges.shape[0] + n_ins - 1) // n_ins
    if n_batches is not None:
        total = min(total, n_batches)
    for t in range(total):
        blk = edges[order[t * n_ins:(t + 1) * n_ins]]
        ins = [tuple(e) for e in blk]
        k = min(int(rng.binomial(batch, p_delete)), len(live))
        dels = []
        if k:
            for i in sorted(rng.choice(len(live), size=k, replace=False),
                            reverse=True):
                dels.append(live.pop(i))
        live += ins
        batches.append(_mk_batch(ins, dels, batch, n))
    return EdgeStream(name="insert_heavy", n_nodes=n,
                      init_u=np.zeros(0, np.int32),
                      init_v=np.zeros(0, np.int32),
                      batches=tuple(batches))


def churn(graph: Graph, *, batch: int = 64, n_batches: int = 16,
          seed: int = 0) -> EdgeStream:
    """Steady state: half the edges live; swap batch/2 per step."""
    n = graph.n_nodes
    rng = np.random.default_rng(seed)
    edges = _edges_of(graph)
    m = edges.shape[0]
    perm = rng.permutation(m)
    # live / dead hold edge ids in list order; a step pops the chosen
    # positions (highest first) and appends the swapped edges at the end.
    live = perm[:m // 2]
    dead = perm[m // 2:]
    init_u = edges[live, 0].astype(np.int32)
    init_v = edges[live, 1].astype(np.int32)
    k = max(1, batch // 2)
    batches = []
    for _ in range(n_batches):
        kk = min(k, len(live), len(dead))
        d_pos = np.sort(rng.choice(len(live), size=kk, replace=False))[::-1]
        i_pos = np.sort(rng.choice(len(dead), size=kk, replace=False))[::-1]
        dels, ins = live[d_pos], dead[i_pos]
        live = np.concatenate([np.delete(live, d_pos), ins])
        dead = np.concatenate([np.delete(dead, i_pos), dels])
        batches.append(_mk_batch(edges[ins], edges[dels], batch, n))
    return EdgeStream(name="churn", n_nodes=n,
                      init_u=init_u, init_v=init_v,
                      batches=tuple(batches))


#: name → generator, mirroring ``data.graphs.SUITE``'s shape.
STREAMS = {
    "sliding_window": sliding_window,
    "insert_heavy": insert_heavy,
    "churn": churn,
}
