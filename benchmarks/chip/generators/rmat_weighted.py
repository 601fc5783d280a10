"""Graph500 Kronecker (R-MAT) edges with the specification's SSSP edge
weights, quantized to integers.

The graph is ``generators/rmat.py``'s, edge for edge: ``generate``
calls ``rmat.generate`` unchanged and then, from the same generator,
draws the specification's kernel 3 weight ``u ~ U[0, 1)`` for each edge
and returns ``w = 1 + floor(weight_levels * u)``, uniform on ``1 ..
weight_levels``. The weighted engine and the weighted core take positive
integers, so the weight is quantized. ``draw_weights`` draws the weights
of fresh pairs from the same law.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.generators import rmat


def generate(params: dict, rng: np.random.Generator):
    n, edges = rmat.generate(params, rng)
    return n, edges, draw_weights(params, rng, edges.shape[0])


def draw_weights(params: dict, rng: np.random.Generator, k: int):
    u = rng.random(k)
    return 1 + np.floor(int(params["weight_levels"]) * u).astype(np.int64)
