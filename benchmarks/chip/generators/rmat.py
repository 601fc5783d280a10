"""Graph500 Kronecker (R-MAT) edges.

``kronecker`` is a copy of the repository's ``graph.generators.rmat``,
kept here so that the benchmark's graphs do not move when the
program's generator changes: initiator a/b/c (d = 1 - a - b - c) per
level, ``edge_factor << scale`` edge tuples drawn (with 40%
oversampling to survive self-loop removal), self loops dropped, the
first ``edge_factor << scale`` kept.

``generate`` then does what the Graph500 specification's generator
does after the Kronecker draws: it permutes the vertex labels at random
and shuffles the edge tuples, both from the seed, so that the hubs do
not sit at the lowest vertex ids. Duplicates are merged last. Returns
``(n, edges)`` with ``edges`` the unique undirected edges as an
``[m, 2]`` int64 array, ``lo < hi``, sorted by key.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator):
    n, src, dst = kronecker(params, rng)
    perm = rng.permutation(n)
    order = rng.permutation(src.size)
    return n, canonical(n, perm[src[order]], perm[dst[order]])


def kronecker(params: dict, rng: np.random.Generator):
    """``(n, src, dst)``: the Kronecker tuples before the permutation."""
    scale = int(params["scale"])
    m = int(params["edge_factor"]) << scale
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    n = 1 << scale
    k = int(m * 1.4) + 16
    src = np.zeros(k, dtype=np.int64)
    dst = np.zeros(k, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(k)
        in_a = r < a
        in_b = (r >= a) & (r < a + b)
        in_c = (r >= a + b) & (r < a + b + c)
        in_d = ~in_a & ~in_b & ~in_c
        src = src * 2 + (in_c | in_d)
        dst = dst * 2 + (in_b | in_d)
    keep = src != dst
    return n, src[keep][:m], dst[keep][:m]


def canonical(n: int, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Unique undirected edges ``lo < hi`` sorted by ``lo * n + hi``."""
    key = np.unique(np.minimum(u, v) * n + np.maximum(u, v))
    return np.stack([key // n, key % n], axis=1)
