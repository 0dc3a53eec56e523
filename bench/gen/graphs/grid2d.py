"""A side x side lattice: ``repro.data.graphs.grid2d`` at commit 345f140.

Copied as an edge list (the source builds the system's ``Graph`` from
it); the lattice is the same for every seed.
"""
from __future__ import annotations

import numpy as np

from bench.gen.graphs import GraphData

TINY = {"side": 24}


def grid2d_edges(side: int) -> np.ndarray:
    """side x side lattice as an int64 [M, 2] edge array (diameter 2(side-1))."""
    n = side * side
    ids = np.arange(n).reshape(side, side)
    horiz = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()], axis=1)
    vert = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()], axis=1)
    return np.concatenate([horiz, vert]).astype(np.int64)


def generate(config: dict, seed: int) -> GraphData:
    edges = grid2d_edges(config["side"])
    return GraphData(edges=edges, n=config["side"] ** 2,
                     slots=edges.shape[0])
