"""GraphBatch builders: synthetic graphs, triplets, molecule batching,
neighbor sampling (fanout), and RST-based locality reordering.

This is where the paper's technique is wired into the GNN pipeline:
``reorder_by_rst`` runs the RST library over the input graph and relabels
nodes by tree order, improving gather locality for sharded message passing.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.models.gnn import GraphBatch


def build_triplets(src: np.ndarray, dst: np.ndarray, n_nodes: int,
                   max_triplets: int) -> tuple[np.ndarray, np.ndarray]:
    """Triplet index arrays for DimeNet: pairs of edges (k→j, j→i).

    Returns (trip_in, trip_out) of length max_triplets, padded with E
    (sentinel). trip_in[t] is the edge id of (k→j); trip_out[t] of (j→i).
    """
    e = len(src)
    in_edges: list[list[int]] = [[] for _ in range(n_nodes)]
    for eid in range(e):
        in_edges[dst[eid]].append(eid)
    ti, to = [], []
    for eid in range(e):
        j = src[eid]               # edge j→i
        for kin in in_edges[j]:    # edge k→j
            if src[kin] == dst[eid]:
                continue           # exclude backtracking k == i
            ti.append(kin)
            to.append(eid)
            if len(ti) >= max_triplets:
                break
        if len(ti) >= max_triplets:
            break
    ti = np.asarray(ti + [e] * (max_triplets - len(ti)), np.int32)
    to = np.asarray(to + [e] * (max_triplets - len(to)), np.int32)
    return ti, to


def random_graph_batch(n_nodes: int, n_edges: int, d_feat: int, *,
                       seed: int = 0, positions: bool = False,
                       atom_types: bool = False, n_graphs: int = 1,
                       max_triplets: int = 0) -> GraphBatch:
    """Random connected-ish GraphBatch with optional 3D positions/triplets."""
    rng = np.random.default_rng(seed)
    # Tree backbone + random extra edges, directed both ways.
    tree_dst = np.arange(1, n_nodes)
    tree_src = (rng.random(n_nodes - 1) * tree_dst).astype(np.int64)
    m_extra = max(n_edges // 2 - (n_nodes - 1), 0)
    ex = rng.integers(0, n_nodes, (m_extra, 2))
    und = np.concatenate([np.stack([tree_src, tree_dst], 1), ex])
    src = np.concatenate([und[:, 0], und[:, 1]])[:n_edges]
    dst = np.concatenate([und[:, 1], und[:, 0]])[:n_edges]
    if len(src) < n_edges:                       # pad with sentinels
        pad = n_edges - len(src)
        src = np.concatenate([src, np.full(pad, n_nodes)])
        dst = np.concatenate([dst, np.full(pad, n_nodes)])

    if atom_types:
        feat = rng.integers(0, 10, n_nodes)
        node_feat = jnp.asarray(feat, jnp.int32)
    else:
        node_feat = jnp.asarray(rng.standard_normal((n_nodes, d_feat)),
                                jnp.float32)
    pos = jnp.asarray(rng.standard_normal((n_nodes, 3)) * 2.0,
                      jnp.float32) if positions else None
    gid = jnp.asarray(rng.integers(0, n_graphs, n_nodes), jnp.int32) \
        if n_graphs > 1 else jnp.zeros((n_nodes,), jnp.int32)

    ti = to = None
    if max_triplets:
        ti_np, to_np = build_triplets(src.astype(np.int64),
                                      dst.astype(np.int64), n_nodes,
                                      max_triplets)
        ti, to = jnp.asarray(ti_np), jnp.asarray(to_np)

    return GraphBatch(n_nodes=n_nodes, node_feat=node_feat,
                      src=jnp.asarray(src, jnp.int32),
                      dst=jnp.asarray(dst, jnp.int32),
                      positions=pos, graph_id=gid, n_graphs=n_graphs,
                      trip_in=ti, trip_out=to)


def neighbor_sample(row_ptr: np.ndarray, col: np.ndarray,
                    seeds: np.ndarray, fanouts: list[int],
                    seed: int = 0) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Multi-hop uniform neighbor sampler (GraphSAGE-style, fanout list).

    Returns (nodes, sub_src, sub_dst): sampled node set (seeds first) and
    the sampled subgraph edges in *local* node numbering.
    """
    rng = np.random.default_rng(seed)
    nodes = list(dict.fromkeys(seeds.tolist()))
    local = {v: i for i, v in enumerate(nodes)}
    sub_src, sub_dst = [], []
    frontier = list(nodes)
    for fan in fanouts:
        nxt = []
        for v in frontier:
            lo, hi = int(row_ptr[v]), int(row_ptr[v + 1])
            deg = hi - lo
            if deg == 0:
                continue
            k = min(fan, deg)
            picks = rng.choice(deg, size=k, replace=False)
            for pk in picks:
                u = int(col[lo + pk])
                if u not in local:
                    local[u] = len(nodes)
                    nodes.append(u)
                    nxt.append(u)
                sub_src.append(local[u])
                sub_dst.append(local[v])
        frontier = nxt
    return (np.asarray(nodes, np.int64), np.asarray(sub_src, np.int64),
            np.asarray(sub_dst, np.int64))


def sampled_batch(row_ptr, col, seeds, fanouts, d_feat: int, *,
                  pad_nodes: int, pad_edges: int, seed: int = 0,
                  feats: np.ndarray | None = None) -> GraphBatch:
    """Fixed-shape GraphBatch from a neighbor sample (pads to static dims)."""
    nodes, s, d = neighbor_sample(row_ptr, col, seeds, fanouts, seed)
    nodes = nodes[:pad_nodes]
    keep = (s < pad_nodes) & (d < pad_nodes)
    s, d = s[keep][:pad_edges], d[keep][:pad_edges]
    n_pad = pad_nodes - len(nodes)
    e_pad = pad_edges - len(s)
    rng = np.random.default_rng(seed + 1)
    if feats is None:
        f = rng.standard_normal((pad_nodes, d_feat)).astype(np.float32)
    else:
        f = np.zeros((pad_nodes, d_feat), np.float32)
        f[:len(nodes)] = feats[nodes]
    src = np.concatenate([s, np.full(e_pad, pad_nodes)])
    dst = np.concatenate([d, np.full(e_pad, pad_nodes)])
    return GraphBatch(n_nodes=pad_nodes, node_feat=jnp.asarray(f),
                      src=jnp.asarray(src, jnp.int32),
                      dst=jnp.asarray(dst, jnp.int32))


def reorder_by_rst(graph_src: np.ndarray, graph_dst: np.ndarray,
                   n_nodes: int, method: str = "gconn_euler"):
    """Relabel nodes by RST order (paper technique in the data pipeline).

    ``graph_src``/``graph_dst`` must be a paired half-edge list, as a
    ``Graph``'s own arrays are: entry ``e + M`` reverses entry ``e``.
    GConn reads only the first half, so an edge that appears only in the
    second half is never hooked.

    Returns perm such that perm[old_id] = new_id; nodes contiguous within
    subtrees → better gather locality after sharding.
    """
    from repro.core import Graph, rooted_spanning_tree

    g = Graph(n_nodes=n_nodes, src=jnp.asarray(graph_src, jnp.int32),
              dst=jnp.asarray(graph_dst, jnp.int32))
    res = rooted_spanning_tree(g, 0, method=method)
    parent = np.asarray(res.parent)
    # Order nodes by (depth, parent) chain — stable DFS-ish labeling from
    # the parent array without host recursion.
    order = np.lexsort((np.arange(n_nodes), parent))
    perm = np.empty(n_nodes, np.int64)
    perm[order] = np.arange(n_nodes)
    return perm
