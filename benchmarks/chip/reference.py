"""The plain reference and the comparison that decides ``correct``.

The reference is the Batagelj–Zaversnik O(m) core decomposition
(arXiv cs/0310049, bin-sorted peel) in plain Python over a CSR built
here from the host's own edge set; for a weighted configuration, the
min-strength peel of weighted coreness (Zhou et al., WWW'21): a
vertex's core is the largest ``k`` such that it lies in a subgraph in
which every vertex's summed incident edge weight is at least ``k``. It
imports nothing of the program and takes nothing the program made.

A configuration names the checks its guarantees imply (``checks`` in
its file); ``readings`` computes exactly those, each a count of faults
with the limit 0:

* ``core_mismatch``: vertices whose core differs from the reference;
* ``certificate_violations``: vertices whose k-order successors
  outnumber their core (``dout(v) <= core(v)``, the certificate that
  the labels carry);
* ``slot_table_diff``: edges in the device's slot table and not in the
  host's edge set, or the other way round, plus duplicate slots; with
  weights, ``(edge, weight)`` pairs, so a slot that holds the right
  edge under a wrong weight counts;
* ``n_edges_diff``: distance of the device's live-edge count from the
  host's;
* ``burst_count_diff``: over every burst, the distance of the edges
  removed and inserted from those sent;
* ``probe_core_mismatch`` and ``probe_certificate_violations``: the
  same two checks of the cores and labels after one earlier burst of
  the window, drawn from the seed, against the host's edge set then.
"""
from __future__ import annotations

import heapq

import numpy as np

LIMITS = {
    "core_mismatch": 0,
    "certificate_violations": 0,
    "slot_table_diff": 0,
    "n_edges_diff": 0,
    "burst_count_diff": 0,
    "probe_core_mismatch": 0,
    "probe_certificate_violations": 0,
}
CHECKS = tuple(LIMITS)  # the order of a result line's checks


def edge_keys(n: int, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    return np.minimum(u, v) * n + np.maximum(u, v)


def core_numbers(n: int, keys: np.ndarray) -> np.ndarray:
    """Core number of every vertex of the graph whose edges are the
    distinct keys ``lo * n + hi`` (Batagelj–Zaversnik)."""
    lo, hi = keys // n, keys % n
    ends = np.concatenate([lo, hi])
    nbr = np.concatenate([hi, lo])
    order = np.argsort(ends, kind="stable")
    adj = nbr[order].tolist()
    start = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(ends, minlength=n), out=start[1:])
    start = start.tolist()
    deg = [start[v + 1] - start[v] for v in range(n)]
    md = max(deg, default=0)
    # bin sort of the vertices by degree
    bins = [0] * (md + 1)
    for d in deg:
        bins[d] += 1
    s = 0
    for d in range(md + 1):
        bins[d], s = s, s + bins[d]
    pos = [0] * n
    vert = [0] * n
    for v in range(n):
        pos[v] = bins[deg[v]]
        vert[pos[v]] = v
        bins[deg[v]] += 1
    for d in range(md, 0, -1):
        bins[d] = bins[d - 1]
    bins[0] = 0
    for i in range(n):
        v = vert[i]
        dv = deg[v]
        for j in range(start[v], start[v + 1]):
            u = adj[j]
            du = deg[u]
            if du > dv:
                pu, pw = pos[u], bins[du]
                w = vert[pw]
                if u != w:
                    pos[u], pos[w] = pw, pu
                    vert[pu], vert[pw] = w, u
                bins[du] += 1
                deg[u] = du - 1
    return np.asarray(deg, dtype=np.int64)


def weighted_core_numbers(n: int, keys: np.ndarray,
                          weights: np.ndarray) -> np.ndarray:
    """Weighted core number of every vertex of the graph whose edges are
    the distinct keys ``lo * n + hi`` with positive integer ``weights``:
    peel a vertex of least strength (the summed weight of its edges to
    vertices not yet peeled), which gets the largest strength any
    vertex had when it was peeled, until none is left."""
    lo, hi = keys // n, keys % n
    ends = np.concatenate([lo, hi])
    nbr = np.concatenate([hi, lo])
    wts = np.concatenate([weights, weights]).astype(np.int64)
    order = np.argsort(ends, kind="stable")
    adj, wadj = nbr[order].tolist(), wts[order].tolist()
    start = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(ends, minlength=n), out=start[1:])
    start = start.tolist()
    strength = np.zeros(n, dtype=np.int64)
    np.add.at(strength, ends, wts)
    strength = strength.tolist()
    heap = [(s, v) for v, s in enumerate(strength)]
    heapq.heapify(heap)
    peeled = [False] * n
    core = [0] * n
    level = 0
    while heap:
        s, v = heapq.heappop(heap)
        if peeled[v] or s != strength[v]:
            continue  # an entry superseded by a later push
        peeled[v] = True
        level = max(level, s)
        core[v] = level
        for j in range(start[v], start[v + 1]):
            u = adj[j]
            if not peeled[u]:
                strength[u] -= wadj[j]
                heapq.heappush(heap, (strength[u], u))
    return np.asarray(core, dtype=np.int64)


def certificate_violations(n: int, src, dst, core, label) -> int:
    """Vertices with more k-order successors among their neighbours than
    their core number: ``dout(v) = |{w ~ v : v precedes w}|`` where ``v``
    precedes ``w`` when ``(core, label)`` of ``v`` is lexicographically
    smaller."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    cs, cd = core[src], core[dst]
    d_after = (cd > cs) | ((cd == cs) & (label[dst] > label[src]))
    dout = np.bincount(np.where(d_after, src, dst), minlength=n)
    return int(np.count_nonzero(dout > core))


def slot_table_diff(n: int, src, dst, valid, live: np.ndarray, w=None,
                    live_w=None) -> int:
    """Symmetric difference of the valid slots' edges and the sorted host
    edge set ``live``, plus slots that hold an edge twice. With the slot
    table's weight column ``w`` and the host's weights ``live_w`` of
    ``live``, the difference is of ``(edge, weight)`` pairs."""
    valid = np.asarray(valid, dtype=bool)
    if w is not None:
        return _weighted_slot_table_diff(n, src, dst, valid, live, w, live_w)
    keys = np.sort(edge_keys(n, np.asarray(src)[valid], np.asarray(dst)[valid]))
    if np.array_equal(keys, live):
        return 0
    uniq = np.unique(keys)
    return int(keys.size - uniq.size) + int(np.setxor1d(uniq, live).size)


def _weighted_slot_table_diff(n, src, dst, valid, live, w, live_w) -> int:
    keys = edge_keys(n, np.asarray(src)[valid], np.asarray(dst)[valid])
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    w = np.asarray(w, dtype=np.int64)[valid][order]
    live_w = np.asarray(live_w, dtype=np.int64)
    if np.array_equal(keys, live) and np.array_equal(w, live_w):
        return 0
    dups = keys.size - np.unique(keys).size
    have = np.unique(np.stack([keys, w], axis=1), axis=0)
    _, seen = np.unique(np.concatenate([have, np.stack([live, live_w],
                                                       axis=1)]),
                        axis=0, return_counts=True)
    return int(dups) + int(np.count_nonzero(seen == 1))


def readings(n: int, state: dict, live: np.ndarray, want_core: np.ndarray,
             burst_count_diff: int, probe=None, checks=CHECKS,
             live_w=None) -> dict:
    """The numbers named in ``checks``, in the order of ``CHECKS``, from
    the device's final ``state`` (numpy ``src, dst, valid, core, label,
    n_edges``, and ``w`` where weighted), the host's sorted final edge
    keys ``live`` (with their weights ``live_w`` where weighted) and the
    reference cores ``want_core``; ``probe`` is ``(core, label, live,
    want_core)`` after an earlier burst, or ``None`` where the window
    held one burst, which leaves the probe's numbers out."""
    core = np.asarray(state["core"], dtype=np.int64)
    label = np.asarray(state["label"], dtype=np.int64)
    valid = np.asarray(state["valid"], dtype=bool)
    src = np.asarray(state["src"])[valid]
    dst = np.asarray(state["dst"])[valid]
    read = {
        "core_mismatch": lambda: int(np.count_nonzero(core != want_core)),
        "certificate_violations": lambda: certificate_violations(
            n, src, dst, core, label),
        "slot_table_diff": lambda: slot_table_diff(
            n, state["src"], state["dst"], valid, live,
            None if live_w is None else state["w"], live_w),
        "n_edges_diff": lambda: abs(int(state["n_edges"]) - int(live.size)),
        "burst_count_diff": lambda: int(burst_count_diff),
    }
    if probe:
        p_core, p_label, p_live, p_want = probe
        p_core = np.asarray(p_core, dtype=np.int64)
        p_label = np.asarray(p_label, dtype=np.int64)
        read["probe_core_mismatch"] = lambda: int(
            np.count_nonzero(p_core != p_want))
        read["probe_certificate_violations"] = lambda: (
            certificate_violations(n, p_live // n, p_live % n, p_core,
                                   p_label))
    return {k: read[k]() for k in CHECKS if k in checks and k in read}


def verdict(read: dict) -> bool:
    return all(read[k] <= LIMITS[k] for k in read)
