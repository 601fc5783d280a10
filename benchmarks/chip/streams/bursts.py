"""The general burst generator: a closed-loop stream of mixed bursts
whose shape a traffic file sets as data.

A traffic file (``traffic/<name>.json``, with ``"stream": "bursts"``)
gives:

* ``remove``: edges removed per burst (0: insert-only);
* ``remove_from``: ``"live"``, a uniform sample of the live edges, or
  ``"oldest"``, the edges live the longest (sliding-window expiry; the
  graph's own edges age in an order drawn from ``rng``);
* ``insert``: edges inserted per burst (0: remove-only);
* ``insert_from``: ``"removed"``, the previous burst's removals (the
  paper's remove-then-reinsert protocol, §5; needs ``insert ==
  remove``), or ``"absent"``, fresh vertex pairs absent from the graph,
  uniform over pairs;
* ``cycle`` or ``bursts``: with ``"live"`` and ``"removed"``, ``cycle``
  removal sets are drawn and the stream repeats after ``cycle`` bursts,
  so it never runs out; otherwise ``bursts`` bursts are drawn, and a
  window that reaches their end stops there.

The warm-up burst removes ``remove`` edges and inserts ``insert`` fresh
absent ones: the same lanes as every later burst, all of them applied.
Every burst's removals are live and its insertions absent when it is
sent, so the count of edges applied equals the count sent.

Given the graph's edge ``weights``, the stream keeps one weight per key
it touches: the graph's edges keep their own, an edge re-inserted
(``"removed"``) comes back with the weight it had, and the fresh absent
pairs take ``draw_weights(k)`` in the order they were drawn. The
weights come from a generator of their own, so ``rng`` draws the same
numbers in the same order with weights as without.

The stream is drawn over the graph as the generator made it, and
``perm`` then relabels the vertices of everything it returns: the
harness draws the bursts from the traffic's own ``stream_seed`` and the
labels from the run's seed, so every run does the same work under
other vertex ids.

All arrays are built before the window; a window burst only indexes
them. The host knows every state by replaying index sets into a mask
over ``pool``, every key the stream touches.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class Stream:
    n: int
    pool: np.ndarray      # sorted keys of every edge the stream touches
    live0: np.ndarray     # bool mask over ``pool`` after the warm-up
    steps: list           # per burst (insert, remove) as indices into pool
    edges: list           # per burst (insert_edges, remove_edges,
    #                       insert_weights); the weights None unweighted
    warmup: tuple         # the same three of the warm-up
    period: Optional[int]  # bursts repeat with this period; None: finite
    max_live: int         # the most edges live after any burst
    weights: Optional[np.ndarray] = None  # per pool key; None: unweighted

    @property
    def n_bursts(self) -> Optional[int]:
        """Bursts before the stream runs out; ``None``: never."""
        return None if self.period else len(self.steps)

    def burst_edges(self, i: int):
        """``(insert_edges, remove_edges, insert_weights)`` of window
        burst ``i``; ``insert_weights`` is None on an unweighted
        stream."""
        return self.edges[i % self.period if self.period else i]

    def live_after(self, i: int, weights: bool = False):
        """Sorted keys of the edge set after window burst ``i`` (``-1``:
        after the warm-up); with ``weights``, ``(keys, their
        weights)``."""
        mask = self.live0.copy()
        last = i % self.period if self.period and i >= 0 else i
        for ins, rm in self.steps[: last + 1]:
            mask[rm] = False
            mask[ins] = True
        return (self.pool[mask], self.weights[mask]) if weights else \
            self.pool[mask]


def _sample(rng, mask: np.ndarray, k: int, excluded=None) -> np.ndarray:
    """``k`` distinct indices where ``mask`` holds and ``excluded`` (an
    index array) does not, uniform."""
    ok = mask.copy()
    if excluded is not None:
        ok[excluded] = False
    if np.count_nonzero(ok) < k:
        raise ValueError(f"cannot draw {k} live edges from "
                         f"{np.count_nonzero(ok)}")
    out = np.zeros(0, dtype=np.int64)
    while out.size < k:
        cand = np.unique(rng.integers(0, mask.size,
                                      size=2 * (k - out.size) + 16))
        cand = cand[ok[cand]]
        ok[cand] = False
        out = np.concatenate([out, rng.permutation(cand)[: k - out.size]])
    return np.sort(out)


def _absent(rng, n: int, live: np.ndarray, k: int) -> np.ndarray:
    """``k`` distinct keys of vertex pairs absent from sorted ``live``,
    in the order drawn."""
    out = np.zeros(0, dtype=np.int64)
    while out.size < k:
        uv = rng.integers(0, n, size=(2 * (k - out.size) + 16, 2))
        uv = uv[uv[:, 0] != uv[:, 1]]
        cand = np.unique(np.minimum(uv[:, 0], uv[:, 1]) * n
                         + np.maximum(uv[:, 0], uv[:, 1]))
        pos = np.minimum(np.searchsorted(live, cand), live.size - 1)
        cand = np.setdiff1d(cand[live[pos] != cand], out)
        out = np.concatenate([out, rng.permutation(cand)[: k - out.size]])
    return out


def build(n: int, edges: np.ndarray, traffic: dict,
          rng: np.random.Generator, perm: np.ndarray, weights=None,
          draw_weights=None) -> Stream:
    """The stream of one run over the graph ``edges`` (unique, sorted by
    key, as the generators return them), with vertex ``v`` renamed
    ``perm[v]`` in all it returns. A weighted stream takes the edges'
    positive integer ``weights``, row for row, and ``draw_weights(k)``,
    ``k`` weights for fresh pairs from a generator apart from ``rng``."""
    n_rm, n_ins = int(traffic["remove"]), int(traffic["insert"])
    rm_from = traffic.get("remove_from", "live")
    ins_from = traffic.get("insert_from", "absent")
    cyclic = "cycle" in traffic
    count = int(traffic["cycle"] if cyclic else traffic["bursts"])
    if rm_from not in ("live", "oldest") or ins_from not in ("removed",
                                                             "absent"):
        raise ValueError(f"unknown remove_from {rm_from!r} or insert_from "
                         f"{ins_from!r}")
    if ins_from == "removed" and n_ins != n_rm:
        raise ValueError("insert_from 'removed' needs insert == remove")
    if cyclic and (rm_from, ins_from) != ("live", "removed"):
        raise ValueError("only remove_from 'live' with insert_from "
                         "'removed' repeats; give 'bursts' instead")
    if n_rm + n_ins == 0 or count < (2 if cyclic else 1):
        raise ValueError(f"traffic sends nothing: {traffic}")
    live = edges[:, 0] * n + edges[:, 1]
    if 3 * n_rm > live.size:
        raise ValueError(f"bursts of {n_rm} removals need 3 * remove <= m, "
                         f"m={live.size}")
    fresh_n = n_ins * (1 if ins_from == "removed" else count + 1)
    fresh = _absent(rng, n, live, fresh_n)
    pool = np.union1d(live, fresh)
    fresh_idx = np.searchsorted(pool, fresh)
    mask = np.zeros(pool.size, dtype=bool)
    mask[np.searchsorted(pool, live)] = True
    pool_w = None
    if weights is not None:
        pool_w = np.zeros(pool.size, dtype=np.int64)
        pool_w[np.searchsorted(pool, live)] = weights
        pool_w[fresh_idx] = draw_weights(fresh.size)
        if (pool_w < 1).any():
            raise ValueError("edge weights must be positive integers")
    # sliding-window expiry: live edges in the order they expire
    queue = np.concatenate([rng.permutation(np.flatnonzero(mask)),
                            np.zeros(n_ins * (count + 1), dtype=np.int64)])
    head, tail = 0, int(mask.sum())

    def step(ins, rm_excluded=None, rm=None):
        nonlocal head, tail
        if rm is None and rm_from == "live":
            rm = _sample(rng, mask, n_rm, rm_excluded)
        elif rm is None:
            rm = np.sort(queue[head : head + n_rm])
            head += n_rm
            if head > tail:
                raise ValueError("sliding window ran out of live edges")
        mask[rm] = False
        mask[ins] = True
        queue[tail : tail + ins.size] = ins
        tail += ins.size
        return ins, rm

    warm = step(fresh_idx[:n_ins])
    live0 = mask.copy()
    max_live = int(mask.sum())
    steps, prev = [], warm[1]
    for j in range(count):
        if ins_from == "removed":
            ins = prev
        else:
            ins = fresh_idx[(j + 1) * n_ins : (j + 2) * n_ins]
        if cyclic and j == count - 1:
            s = step(ins, rm=warm[1])  # back to the state after warm-up
        else:
            excl = warm[1] if cyclic and j == count - 2 else None
            s = step(ins, rm_excluded=excl)
        steps.append(s)
        prev = s[1]
        max_live = max(max_live, int(mask.sum()))
    if cyclic:
        assert np.array_equal(mask, live0)

    # relabel: the pool's keys under ``perm``, re-sorted; ``new[i]`` is
    # the relabelled position of pool index ``i``
    a, b = perm[pool // n], perm[pool % n]
    keys = np.minimum(a, b) * n + np.maximum(a, b)
    order = np.argsort(keys)
    new = np.empty_like(order)
    new[order] = np.arange(order.size)
    pool = keys[order]

    if pool_w is not None:
        pool_w = pool_w[order]

    def as_edges(idx):
        k = pool[new[idx]]
        return np.stack([k // n, k % n], axis=1)

    def burst(ins, rm):
        w = None if pool_w is None else pool_w[new[ins]]
        return as_edges(ins), as_edges(rm), w

    return Stream(
        n=n, pool=pool, live0=live0[order],
        steps=[(new[i], new[r]) for i, r in steps],
        edges=[burst(i, r) for i, r in steps],
        warmup=burst(*warm),
        period=count if cyclic else None, max_live=max_live,
        weights=pool_w,
    )
