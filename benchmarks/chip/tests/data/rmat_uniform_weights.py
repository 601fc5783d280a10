"""A weighted generator for the tests: the Graph500 Kronecker graph of
``generators/rmat.py`` with integer edge weights drawn uniformly from
``1 .. max_weight``, after the graph, from the same generator; fresh
pairs draw from the same law."""
from __future__ import annotations

import numpy as np

from benchmarks.chip.generators import rmat


def generate(params: dict, rng: np.random.Generator):
    n, edges = rmat.generate(params, rng)
    return n, edges, draw_weights(params, rng, edges.shape[0])


def draw_weights(params: dict, rng: np.random.Generator, k: int):
    return rng.integers(1, int(params["max_weight"]) + 1, size=k)
