"""Barabási–Albert preferential attachment in linear time.

The model as networkx's ``barabasi_albert_graph`` builds it: a star of
``k + 1`` vertices (vertex 0 joined to 1..k), then every later vertex
``v`` joins ``k`` distinct earlier vertices, each drawn with probability
proportional to its degree. Degree-proportional draws are uniform draws
from the list of edge endpoints, which grows by ``2k`` entries per
vertex in a preallocated array, so the whole graph costs O(n k) and not
the O(n^2) of rebuilding the endpoint list for every vertex.

Every vertex after the star has degree >= k, so nearly every vertex has
core number k. Returns ``(n, edges)`` as ``generators/rmat.py`` does.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, rng: np.random.Generator):
    n = int(params["n"])
    k = int(params["edges_per_vertex"])
    if not 1 <= k < n:
        raise ValueError(f"need 1 <= edges_per_vertex < n, got {k}, {n}")
    ends = np.empty(2 * k * n, dtype=np.int64)
    src = np.empty(k * (n - k), dtype=np.int64)
    dst = np.empty_like(src)
    # the star: vertex 0 joined to 1..k
    src[:k], dst[:k] = 0, np.arange(1, k + 1)
    ends[: 2 * k : 2], ends[1 : 2 * k : 2] = 0, np.arange(1, k + 1)
    fill, e = 2 * k, k
    for v in range(k + 1, n):
        picks: list = []
        while len(picks) < k:
            for t in ends[rng.integers(0, fill, size=2 * k)].tolist():
                if t not in picks:
                    picks.append(t)
                    if len(picks) == k:
                        break
        src[e : e + k] = v
        dst[e : e + k] = picks
        ends[fill : fill + k] = v
        ends[fill + k : fill + 2 * k] = picks
        fill += 2 * k
        e += k
    key = np.minimum(src, dst) * n + np.maximum(src, dst)
    key = np.sort(key)  # distinct by construction
    return n, np.stack([key // n, key % n], axis=1)
