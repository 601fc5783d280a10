"""Weighted-coreness ORACLE and reference kernels (paper §6 future work).

The PRODUCTION weighted engine lives in the engine matrix now:
``CoreMaintainer(weighted=True)`` threads a per-slot weight column
through ``core/engine.py::batch_program`` (and its halo twin), runs
both maintenance phases through the shared decrease-only weighted
h-index fixpoint (``core/remove.py::weighted_core_fixpoint_pass`` /
``core/insert.py::weighted_promotion_fixpoint``, each round's h-index
from ``core/graph_ops.py::weighted_h_index``: one sort on one device, a
bisection of ``weighted_support`` passes under a mesh axis), and
is audited by the committed ``weighted`` / ``weighted_sharded`` budget
manifests. This module is what that engine is PINNED against: the
numpy peeling oracle (``weighted_core_oracle``), a standalone
single-device fixpoint (``weighted_core_fixpoint``), and the small
``WeightedCoreMaintainer`` reference harness
(tests/test_weighted_core.py, tests/test_churn_streams.py).

Weighted coreness (Zhou et al., WWW'21): the weighted degree of v is the
sum of incident edge weights; the weighted k-core is the maximal subgraph
with weighted degree >= k inside it; integer weights give integer cores.

The decrease-only fixpoint generalizes from mcd to the *weighted
h-index*:

    H_w(v) = max{ h : sum of w(u,v) over neighbors with core(u) >= h  >= h }

Iterating ``c <- min(c, H_w(c))`` from ANY upper bound converges to the
exact weighted core numbers (same monotone argument as the unweighted
mcd fixpoint — the fixpoint set {v: c(v) >= k} induces a subgraph of
weighted degree >= k, and values at the true core never drop). Upper
bounds: the weighted degree (decomposition), the current cores
(removals), current cores + TOTAL batch inserted weight (insertions —
docs/DESIGN.md §4.5 derives why the per-vertex incident bound is not
sound).

H_w is computed data-parallel with a per-vertex bisection: O(log maxW)
masked segment-sums per round — every edge and every vertex of every
level in parallel, the paper's parallelism claim carried to the weighted
setting.
"""
from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# numpy oracle: generalized peeling
# ---------------------------------------------------------------------------
def weighted_core_oracle(n: int, edges: np.ndarray,
                         weights: np.ndarray) -> np.ndarray:
    """Exact weighted cores by min-weighted-degree peeling (BZ analogue)."""
    import heapq

    adj: List[List[Tuple[int, int]]] = [[] for _ in range(n)]
    for (u, v), w in zip(edges, weights):
        adj[int(u)].append((int(v), int(w)))
        adj[int(v)].append((int(u), int(w)))
    wdeg = np.array([sum(w for _, w in a) for a in adj], dtype=np.int64)
    heap = [(int(wdeg[v]), v) for v in range(n)]
    heapq.heapify(heap)
    removed = np.zeros(n, dtype=bool)
    core = np.zeros(n, dtype=np.int64)
    k = 0
    while heap:
        d, v = heapq.heappop(heap)
        if removed[v] or d != wdeg[v]:
            continue
        removed[v] = True
        k = max(k, int(wdeg[v]))
        core[v] = k
        for u, w in adj[v]:
            if not removed[u]:
                wdeg[u] -= w
                heapq.heappush(heap, (int(wdeg[u]), u))
    return core


# ---------------------------------------------------------------------------
# JAX weighted h-index fixpoint
# ---------------------------------------------------------------------------
def _weighted_h_index(src, dst, w, valid, c, n):
    """Per-vertex H_w via simultaneous bisection (all vertices at once)."""
    lo = jnp.zeros(n, jnp.int32)
    hi = c  # H_w(v) <= c(v) suffices for a decrease-only iteration

    def cond(state):
        lo, hi = state
        return jnp.any(lo < hi)

    def body(state):
        lo, hi = state
        mid = (lo + hi + 1) // 2
        to_src = jnp.where(valid & (c[dst] >= mid[src]), w, 0)
        to_dst = jnp.where(valid & (c[src] >= mid[dst]), w, 0)
        s = (
            jax.ops.segment_sum(to_src, src, num_segments=n)
            + jax.ops.segment_sum(to_dst, dst, num_segments=n)
        )
        ok = s >= mid
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = jax.lax.while_loop(cond, body, (lo, hi))
    return lo


@partial(jax.jit, static_argnames=("n",))
def weighted_core_fixpoint(src: Array, dst: Array, w: Array, valid: Array,
                           upper: Array, n: int) -> Array:
    """Exact weighted cores from any per-vertex upper bound."""

    def cond(state):
        _, changed = state
        return changed

    def body(state):
        c, _ = state
        h = _weighted_h_index(src, dst, w, valid, c, n)
        new_c = jnp.minimum(c, h)
        return new_c, jnp.any(new_c != c)

    c, _ = jax.lax.while_loop(cond, body, (upper, jnp.bool_(True)))
    return c


class WeightedCoreMaintainer:
    """Dynamic weighted-core maintenance over COO slots (host wrapper)."""

    def __init__(self, n: int, edges: np.ndarray, weights: np.ndarray,
                 capacity: int | None = None):
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        weights = np.asarray(weights, dtype=np.int32)
        m = edges.shape[0]
        capacity = capacity or max(16, 2 * m)
        self.n = n
        self.capacity = capacity
        src = np.zeros(capacity, np.int32)
        dst = np.zeros(capacity, np.int32)
        wgt = np.zeros(capacity, np.int32)
        val = np.zeros(capacity, bool)
        src[:m], dst[:m], wgt[:m], val[:m] = (
            edges[:, 0], edges[:, 1], weights, True
        )
        self.src = jnp.asarray(src)
        self.dst = jnp.asarray(dst)
        self.w = jnp.asarray(wgt)
        self.valid = jnp.asarray(val)
        self.n_edges = m
        self.edge_slot = {
            (int(min(a, b)), int(max(a, b))): i
            for i, (a, b) in enumerate(edges)
        }
        wdeg = (
            jax.ops.segment_sum(self.w * self.valid, self.src,
                                num_segments=n)
            + jax.ops.segment_sum(self.w * self.valid, self.dst,
                                  num_segments=n)
        ).astype(jnp.int32)
        self.core = weighted_core_fixpoint(
            self.src, self.dst, self.w, self.valid, wdeg, n
        )

    def cores(self) -> np.ndarray:
        return np.asarray(self.core)

    def insert_edges(self, edges: np.ndarray, weights: np.ndarray) -> None:
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        weights = np.asarray(weights, dtype=np.int32)
        base = self.n_edges
        assert base + len(edges) < self.capacity, "grow not implemented"
        src = np.asarray(self.src).copy()
        dst = np.asarray(self.dst).copy()
        wgt = np.asarray(self.w).copy()
        val = np.asarray(self.valid).copy()
        for i, ((a, b), ww) in enumerate(zip(edges, weights)):
            key = (int(min(a, b)), int(max(a, b)))
            self.edge_slot[key] = base + i
            src[base + i], dst[base + i] = key
            wgt[base + i], val[base + i] = ww, True
        self.n_edges = base + len(edges)
        self.src, self.dst = jnp.asarray(src), jnp.asarray(dst)
        self.w, self.valid = jnp.asarray(wgt), jnp.asarray(val)
        # upper bound: ANY vertex's weighted core can rise by at most the
        # total inserted weight (the weighted analogue of "+1 per inserted
        # edge", which applies to every vertex of V*, not just endpoints)
        upper = (self.core + jnp.int32(int(weights.sum()))).astype(jnp.int32)
        self.core = weighted_core_fixpoint(
            self.src, self.dst, self.w, self.valid, upper, self.n
        )

    def remove_edges(self, edges: np.ndarray) -> None:
        edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        val = np.asarray(self.valid).copy()
        for a, b in edges:
            key = (int(min(a, b)), int(max(a, b)))
            slot = self.edge_slot.pop(key, None)
            if slot is not None:
                val[slot] = False
        self.valid = jnp.asarray(val)
        # current cores upper-bound the post-removal cores
        self.core = weighted_core_fixpoint(
            self.src, self.dst, self.w, self.valid, self.core, self.n
        )
