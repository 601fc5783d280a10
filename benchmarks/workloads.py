"""Shared benchmark workloads: graphs + edit batches (paper §5.1/§5.2).

The paper's experiment: sample 100k edges, remove them, then re-insert,
measuring accumulated wall time. CPU-container sizes are scaled down
(graphs ~20-50k vertices, batches 256-4096) but keep the paper's graph
families (ER / BA / RMAT power-law) and its protocol.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.graph.csr import CSRGraph
from repro.graph.generators import barabasi_albert, erdos_renyi, rmat


def paper_graphs(scale: float = 1.0) -> Dict[str, CSRGraph]:
    n = int(20000 * scale)
    m = int(80000 * scale)
    return {
        "ER": erdos_renyi(n, m, seed=1),
        "BA": barabasi_albert(n, deg=8, seed=1),
        "RMAT": rmat(max(8, int(np.log2(n)) + 1), m, seed=1),
    }


def sample_removals(g: CSRGraph, k: int, seed: int = 0) -> np.ndarray:
    edges = g.edge_array()
    rng = np.random.default_rng(seed)
    idx = rng.choice(edges.shape[0], size=min(k, edges.shape[0]),
                     replace=False)
    return edges[idx]


def sample_insertions(g: CSRGraph, k: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    out = []
    seen = set()
    while len(out) < k:
        u = int(rng.integers(0, g.n))
        v = int(rng.integers(0, g.n))
        if u == v:
            continue
        key = (min(u, v), max(u, v))
        if key in seen or g.has_edge(*key):
            continue
        seen.add(key)
        out.append(key)
    return np.asarray(out, dtype=np.int64)

