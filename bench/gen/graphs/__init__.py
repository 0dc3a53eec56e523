"""Graph generators of the benchmark, one file each, found by name.

A configuration's ``generator`` names ``bench/gen/graphs/<generator>.py``.
Each such module defines

- ``generate(config, seed) -> GraphData``: the configuration's graph,
  drawn from ``seed`` on the host, and
- ``TINY``: the configuration keys that cut it to a toy size for the CPU
  rehearsal (``python3 -m bench.rehearse``).

The drivers build the system's own ``Graph`` from ``GraphData`` and keep
the host edge list for the plain reference, so the reference never reads
a graph back from the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness


@dataclasses.dataclass(frozen=True)
class GraphData:
    """One generated graph.

    ``edges`` is int64 [M, 2], undirected, with no self-loop and no
    duplicate; ``n`` the vertex count; ``slots`` a fixed bound on M that
    holds for every seed, so that a driver that pads to it gives every
    seed of a configuration the same shapes (and the same compiled
    programs).
    """

    edges: np.ndarray
    n: int
    slots: int


def generator(config: dict):
    return harness.load("gen/graphs", config["generator"])


def generate(config: dict, seed: int) -> GraphData:
    data = generator(config).generate(config, seed)
    if data.edges.shape[0] > data.slots:
        raise ValueError(f"{config['generator']} drew {data.edges.shape[0]} "
                         f"edges, more than its {data.slots} slots")
    return data


def tiny(config: dict) -> dict:
    """``config`` cut to its generator's toy size."""
    return dict(config, **generator(config).TINY)
