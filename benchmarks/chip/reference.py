"""The plain reference and the comparison that decides ``correct``.

The reference is the Batagelj–Zaversnik O(m) core decomposition
(arXiv cs/0310049, bin-sorted peel) in plain Python over a CSR built
here from the host's own edge set. It imports nothing of the program
and takes nothing the program made.

Every number compared is a count of faults with the limit 0:

* ``core_mismatch``: vertices whose core differs from the reference;
* ``certificate_violations``: vertices whose k-order successors
  outnumber their core (``dout(v) <= core(v)``, the certificate that
  the labels carry);
* ``slot_table_diff``: edges in the device's slot table and not in the
  host's edge set, or the other way round, plus duplicate slots;
* ``n_edges_diff``: distance of the device's live-edge count from the
  host's;
* ``burst_count_diff``: over every burst, the distance of the edges
  removed and inserted from those sent;
* ``probe_core_mismatch`` and ``probe_certificate_violations``: the
  same two checks of the cores and labels after one earlier burst of
  the window, drawn from the seed, against the host's edge set then.
"""
from __future__ import annotations

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


def slot_table_diff(n: int, src, dst, valid, live: np.ndarray) -> int:
    """Symmetric difference of the valid slots' edges and the sorted host
    edge set ``live``, plus slots that hold an edge twice."""
    valid = np.asarray(valid, dtype=bool)
    keys = np.sort(edge_keys(n, np.asarray(src)[valid], np.asarray(dst)[valid]))
    if np.array_equal(keys, live):
        return 0
    uniq = np.unique(keys)
    return int(keys.size - uniq.size) + int(np.setxor1d(uniq, live).size)


def readings(n: int, state: dict, live: np.ndarray, want_core: np.ndarray,
             burst_count_diff: int, probe=None) -> dict:
    """Every number compared, from the device's final ``state`` (numpy
    ``src, dst, valid, core, label, n_edges``), the host's sorted final
    edge keys ``live`` and the reference cores ``want_core``; ``probe``
    is ``(core, label, live, want_core)`` after an earlier burst, or
    ``None`` where the window held one burst."""
    core = np.asarray(state["core"], dtype=np.int64)
    label = np.asarray(state["label"], dtype=np.int64)
    valid = np.asarray(state["valid"], dtype=bool)
    src = np.asarray(state["src"])[valid]
    dst = np.asarray(state["dst"])[valid]
    return {
        "core_mismatch": int(np.count_nonzero(core != want_core)),
        "certificate_violations": certificate_violations(n, src, dst, core,
                                                         label),
        "slot_table_diff": slot_table_diff(n, state["src"], state["dst"],
                                           valid, live),
        "n_edges_diff": abs(int(state["n_edges"]) - int(live.size)),
        "burst_count_diff": int(burst_count_diff),
        **(probe_readings(n, *probe) if probe else {}),
    }


def probe_readings(n: int, core, label, live: np.ndarray,
                   want_core: np.ndarray) -> dict:
    core = np.asarray(core, dtype=np.int64)
    label = np.asarray(label, dtype=np.int64)
    return {
        "probe_core_mismatch": int(np.count_nonzero(core != want_core)),
        "probe_certificate_violations": certificate_violations(
            n, live // n, live % n, core, label),
    }


def verdict(read: dict) -> bool:
    return all(read[k] <= LIMITS[k] for k in read)
