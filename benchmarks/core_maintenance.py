"""Core-maintenance benchmarks mirroring the paper's figures/tables.

Paper measured wall-clock vs #workers on a 64-core CPU. This container
has 1 CPU core, so "parallelism" is expressed as the batch width processed
per bulk-synchronous round (the TPU analogue of worker count): width=1
degenerates to sequential-equivalent work; width=B processes the whole
batch in O(rounds) data-parallel sweeps. We report, per paper artifact:

  fig4  — accumulated edit time vs batch width (OurI/OurR = JAX
          Parallel-Order) + sequential baselines OI/OR (Simplified-Order
          oracle) and TI/TR (Traversal oracle).
  tab2  — speedup table (batch JAX vs OI/OR and TI/TR).
  fig5  — |V+| size distribution (locked-set sizes).
  fig6  — scalability: time ratio vs number of edited edges.
  fig7  — stability: variance across disjoint edge batches.
Extra (beyond paper): promotion/drop round counts — the bulk-synchronous
depth of each batch.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.core.api import CoreMaintainer
from repro.core.oracle import OrderCoreMaintainer, TraversalCoreMaintainer

from .workloads import paper_graphs, sample_insertions, sample_removals

Row = Dict[str, object]


def _fresh_jax(g, cap_mult=4):
    return CoreMaintainer.from_graph(
        g, capacity=max(64, cap_mult * g.edge_array().shape[0])
    )


def _run_jax_batched(m: CoreMaintainer, edges: np.ndarray, width: int,
                     kind: str) -> float:
    t0 = time.perf_counter()
    for i in range(0, len(edges), width):
        chunk = edges[i : i + width]
        if kind == "insert":
            m.insert_edges(chunk)
        else:
            m.remove_edges(chunk)
    # block on device
    m.core.block_until_ready()
    return time.perf_counter() - t0


def _run_oracle(m, edges: np.ndarray, kind: str) -> float:
    t0 = time.perf_counter()
    if kind == "insert":
        m.insert_batch(edges)
    else:
        m.remove_batch(edges)
    return time.perf_counter() - t0


def fig4_runtime(n_edges: int = 512, widths=(1, 32, 512)) -> List[Row]:
    rows: List[Row] = []
    for gname, g in paper_graphs(scale=0.5).items():
        removals = sample_removals(g, n_edges, seed=7)
        insertions = sample_insertions(g, n_edges, seed=7)
        for width in widths:
            mj = _fresh_jax(g)
            # warm the jit caches with a throwaway batch
            mj.insert_edges(sample_insertions(g, min(width, 64), seed=99))
            t_rm = _run_jax_batched(mj, removals, width, "remove")
            t_in = _run_jax_batched(mj, insertions, width, "insert")
            rows.append({"bench": "fig4", "graph": gname, "algo": "OurR",
                         "width": width, "seconds": t_rm})
            rows.append({"bench": "fig4", "graph": gname, "algo": "OurI",
                         "width": width, "seconds": t_in})
        for name, cls in (("O", OrderCoreMaintainer),
                          ("T", TraversalCoreMaintainer)):
            m = cls(g.n, g.edge_array())
            t_rm = _run_oracle(m, removals, "remove")
            t_in = _run_oracle(m, insertions, "insert")
            rows.append({"bench": "fig4", "graph": gname, "algo": f"{name}R",
                         "width": 1, "seconds": t_rm})
            rows.append({"bench": "fig4", "graph": gname, "algo": f"{name}I",
                         "width": 1, "seconds": t_in})
    return rows


def tab2_speedups(fig4_rows: List[Row]) -> List[Row]:
    rows = []
    by = {}
    for r in fig4_rows:
        by[(r["graph"], r["algo"], r["width"])] = r["seconds"]
    for gname in {r["graph"] for r in fig4_rows}:
        wmax = max(r["width"] for r in fig4_rows if r["algo"] == "OurI"
                   and r["graph"] == gname)
        for op in ("I", "R"):
            ours = by[(gname, f"Our{op}", wmax)]
            ours_w1 = by[(gname, f"Our{op}", 1)]
            rows.append({
                "bench": "tab2", "graph": gname, "op": op,
                "batch_vs_width1": ours_w1 / ours,
                "vs_order_seq": by[(gname, f"O{op}", 1)] / ours,
                "vs_traversal_seq": by[(gname, f"T{op}", 1)] / ours,
            })
    return rows


def fig5_vplus(n_edges: int = 400) -> List[Row]:
    rows = []
    for gname, g in paper_graphs(scale=0.25).items():
        m = OrderCoreMaintainer(g.n, g.edge_array())
        ins = sample_insertions(g, n_edges, seed=3)
        sizes_i = []
        for u, v in ins:
            m.insert_edge(int(u), int(v))
            sizes_i.append(m.last_v_plus)
        sizes_r = []
        for u, v in ins[::-1]:
            m.remove_edge(int(u), int(v))
            sizes_r.append(m.last_v_plus)
        for op, sizes in (("insert", sizes_i), ("remove", sizes_r)):
            arr = np.asarray(sizes)
            rows.append({
                "bench": "fig5", "graph": gname, "op": op,
                "frac_le_10": float(np.mean(arr <= 10)),
                "median": float(np.median(arr)),
                "p99": float(np.percentile(arr, 99)),
                "max": int(arr.max()),
            })
    return rows


def fig6_scalability(sizes=(128, 256, 512, 1024)) -> List[Row]:
    rows = []
    for gname, g in paper_graphs(scale=0.5).items():
        base = None
        for k in sizes:
            mj = _fresh_jax(g)
            mj.insert_edges(sample_insertions(g, 64, seed=99))  # warm jit
            ins = sample_insertions(g, k, seed=11)
            t = _run_jax_batched(mj, ins, k, "insert")
            base = t if base is None else base
            rows.append({
                "bench": "fig6", "graph": gname, "edges": k,
                "seconds": t, "ratio_vs_smallest": t / base,
            })
    return rows


def fig7_stability(n_batches: int = 8, batch: int = 128) -> List[Row]:
    rows = []
    for gname, g in paper_graphs(scale=0.25).items():
        mj = _fresh_jax(g, cap_mult=6)
        mj.insert_edges(sample_insertions(g, 64, seed=99))  # warm jit
        times = []
        for i in range(n_batches):
            ins = sample_insertions(g, batch, seed=100 + i)
            t0 = time.perf_counter()
            mj.insert_edges(ins)
            mj.core.block_until_ready()
            times.append(time.perf_counter() - t0)
        arr = np.asarray(times)
        rows.append({
            "bench": "fig7", "graph": gname, "mean_s": float(arr.mean()),
            "std_s": float(arr.std()), "cv": float(arr.std() / arr.mean()),
        })
    return rows


def rounds_depth(batch: int = 512) -> List[Row]:
    """Beyond-paper: bulk-synchronous depth (rounds) per batch."""
    rows = []
    for gname, g in paper_graphs(scale=0.5).items():
        mj = _fresh_jax(g)
        ins = sample_insertions(g, batch, seed=5)
        st = mj.insert_edges(ins)
        rows.append({
            "bench": "rounds", "graph": gname, "op": "insert",
            "rounds": int(st.rounds), "v_star": int(st.n_promoted),
            "v_plus": int(st.v_plus),
        })
        st = mj.remove_edges(ins)
        rows.append({
            "bench": "rounds", "graph": gname, "op": "remove",
            "rounds": int(st.rounds), "v_star": int(st.n_dropped),
            "v_plus": int(st.n_dropped),
        })
    return rows
