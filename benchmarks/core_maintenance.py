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

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Sequence

import numpy as np

from repro.analysis.benchcheck import BENCH_SCHEMA
from repro.core.api import CoreMaintainer
from repro.core.oracle import OrderCoreMaintainer, TraversalCoreMaintainer
from repro.graph.csr import build_csr
from repro.graph.generators import erdos_renyi
from repro.graph.stream import mixed_stream

from .workloads import (
    churn_workload,
    paper_graphs,
    sample_insertions,
    sample_removals,
    temporal_workload,
)

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


STREAM_ENGINES = ("host", "unified", "sharded", "vertex_sharded",
                  "frontier_sparse", "vertex_halo", "pallas", "weighted")

# engine NAME -> CoreMaintainer kwargs (the bench rows are engine
# configurations, not just engine strings, since PR 4's vertex layouts)
ENGINE_SPECS: Dict[str, Dict[str, object]] = {
    "host": {"engine": "host"},
    "unified": {"engine": "unified"},
    "sharded": {"engine": "sharded"},
    "vertex_sharded": {"engine": "sharded", "vertex_sharding": "range"},
    "frontier_sparse": {"engine": "sharded", "vertex_sharding": "range",
                        "frontier_exchange": "sparse"},
    # the 2-axis halo working set (degenerate (1, d) mesh on the bench
    # host; the mesh_scaling sweep times the proper factorizations)
    "vertex_halo": {"engine": "sharded", "vertex_sharding": "halo"},
    "pallas": {"engine": "unified", "kernel_backend": "pallas"},
    # the weighted h-index engine with every weight 1: weighted coreness
    # degenerates to plain coreness, so this row rides the SAME stream
    # and participates in engines_agree — the cross-check that the
    # weighted fixpoint path computes the same cores the order-based
    # path does, while its timing prices the bisection stat pass
    "weighted": {"engine": "unified", "weighted": True},
}


def round_launch_counts(n: int, cap: int) -> Dict[str, object]:
    """Static per-round kernel-launch histograms, lax vs pallas.

    Traces (never runs) the removal and promotion round bodies with both
    kernel backends and counts launch-class primitives via the jaxpr
    walker — the same counter the committed budget manifests pin
    (``repro.analysis.walker.count_round_launches``). On CPU the timed
    pallas rows run in interpret mode, so wall-clock does NOT show the
    launch win; this section records the claim the fusion actually
    makes: strictly fewer dispatches per fixpoint round on a real
    accelerator backend. ``total`` sums both rounds per backend.
    """
    import jax

    from repro.analysis.programs import (
        EDGE_AXIS,
        trace_promotion_round,
        trace_removal_round,
    )
    from repro.analysis.walker import count_round_launches

    mesh = jax.make_mesh((1,), (EDGE_AXIS,))
    out: Dict[str, object] = {}
    for backend in ("lax", "pallas"):
        rounds: Dict[str, object] = {}
        for rname, tracer in (("removal", trace_removal_round),
                              ("promotion", trace_promotion_round)):
            _, closed = tracer("replicated", n, cap, mesh,
                               kernel_backend=backend)
            rounds[rname] = count_round_launches(closed)
        rounds["total"] = sum(
            c
            for rname in ("removal", "promotion")
            for c in rounds[rname].values()  # type: ignore[union-attr]
        )
        out[backend] = rounds
    return out


TEMPORAL_ENGINES = ("host", "unified", "sharded", "weighted")


def temporal_bench(
    n: int = 1500,
    arrivals: int = 3000,
    horizon: int = 30,
    window: int = 6,
    stride: int = 3,
    engines: Sequence[str] = TEMPORAL_ENGINES,
) -> Dict[str, object]:
    """Sliding-window expiry stream (``workloads.temporal_workload``):
    every engine replays the SAME drained event sequence from an empty
    graph — each step bulk-removes the edges older than ``window`` and
    inserts the new stride's arrivals, so removals are structural
    (expiry by age) rather than sampled. Two replays per engine: an
    untimed one to populate the jit caches (batch widths vary per step,
    but the pow2 lane buckets collapse them to a handful of programs),
    then a timed one on a fresh maintainer. Because the stream drains,
    total insertions == total removals and every engine must end with
    all-zero cores — both recorded for the coherence gate alongside the
    cross-engine finals comparison."""
    n, _, events, max_live = temporal_workload(
        n=n, arrivals=arrivals, horizon=horizon, window=window,
        stride=stride,
    )
    capacity = max(256, 4 * max_live)
    empty = build_csr(n, np.zeros((0, 2), dtype=np.int64))
    total_ins = int(sum(len(ev.edges) for ev in events))
    total_rm = int(sum(len(ev.removals) for ev in events))
    per_engine: Dict[str, Dict[str, float]] = {}
    finals = {}
    for engine in engines:

        def replay():
            mt = CoreMaintainer.from_graph(empty, capacity=capacity,
                                           **ENGINE_SPECS[engine])
            for ev in events:
                if engine == "host":  # seed path: one program per kind
                    mt.remove_edges(ev.removals)
                    mt.insert_edges(ev.edges)
                else:
                    mt.apply_batch(insert_edges=ev.edges,
                                   remove_edges=ev.removals)
            mt.core.block_until_ready()
            return mt

        replay()  # warm replay — the timed pass hits the jit caches
        t0 = time.perf_counter()
        mt = replay()
        dt = time.perf_counter() - t0
        per_engine[engine] = {
            "seconds": dt,
            "batches_per_s": len(events) / dt,
            "edges_per_s": (total_ins + total_rm) / dt,
        }
        finals[engine] = mt.cores()
    agree = all(
        bool((finals[e] == finals[engines[0]]).all()) for e in engines
    )
    zero = all(bool((finals[e] == 0).all()) for e in engines)
    result: Dict[str, object] = {
        "window": window,
        "stride": stride,
        "arrivals": arrivals,
        "horizon": horizon,
        "n_events": len(events),
        "max_live": max_live,
        "capacity": capacity,
        "total_insertions": total_ins,
        "total_removals": total_rm,
        "drained": bool(total_ins == total_rm),
        "engines_agree": agree,
        "final_cores_zero": zero,
    }
    result.update(per_engine)
    return result


def stream_bench(
    n: int = 1500,
    m: int = 6000,
    n_batches: int = 30,
    batch_size: int = 128,
    warmup: int = 3,
    out_json: str = "BENCH_stream.json",
    engines: Sequence[str] = STREAM_ENGINES,
    scaling_device_counts: Sequence[int] = (),
    vertex_scaling_device_counts: Sequence[int] = (),
    frontier_scaling_device_counts: Sequence[int] = (),
    mesh_scaling_shapes: Sequence = (),
    temporal_arrivals: int = 3000,
    temporal_window: int = 6,
    temporal_stride: int = 3,
) -> Dict[str, object]:
    """Mixed insert+remove stream on the SAME events: the unified one-call
    engine (with both the lax and the fused-pallas kernel backends), the
    mesh-sharded engine (replicated AND range-sharded vertex state,
    bitmask AND sparse frontier exchange), the weighted h-index engine
    (unit weights — weighted coreness degenerates to plain coreness, so
    the row joins ``engines_agree`` while its timing prices the
    bisection stat pass) vs the seed two-call path (host-dict dedup +
    separate insert/remove programs). Reports batches/sec per engine, a
    static lax-vs-pallas per-round launch-count section
    (``launches_per_round``), a sliding-window expiry section
    (``temporal`` — see ``temporal_bench``), and writes
    ``out_json``. With
    ``scaling_device_counts`` / ``vertex_scaling_device_counts`` /
    ``frontier_scaling_device_counts`` the sharded / vertex-sharded /
    sparse-frontier engine is re-timed in subprocesses with that many
    forced host devices (the paper's time-vs-workers scaling axis;
    ``sharded_device_scaling``) — recorded as ``sharded_scaling`` /
    ``vertex_scaling`` / ``frontier_scaling`` rows with their
    ``n_devices``.

    Note on jit-cache hygiene: the unified engine's ``active_cap`` is a
    static pow2 bucket of the slot high-water mark. With the defaults
    here (m=6000, ~64 inserts/batch, 33 batches) the whole stream stays
    inside the 8192 bucket, so no recompile lands in the timed region;
    if you change the parameters, keep ``m + n_batches * batch_size/2``
    under the next power of two past ``m`` (or discount the first timed
    batch after a bucket crossing). The sharded engine always runs full
    capacity passes, so it never recompiles mid-stream.
    """
    from repro.core.api import plan_frontier_cap
    from repro.kernels.coremaint import default_interpret

    g = erdos_renyi(n, m, seed=12)
    # one extra untimed batch beyond warmup: see the post-harvest step
    # in the engine loop below
    events = list(
        mixed_stream(g, n_batches + warmup + 1, batch_size, seed=17)
    )
    per_engine: Dict[str, Dict[str, float]] = {}
    finals = {}
    overflow_per_batch: Dict[str, List[int]] = {}
    for engine in engines:
        mt = CoreMaintainer.from_graph(g, capacity=4 * m,
                                       **ENGINE_SPECS[engine])

        def step(ev):
            if engine == "host":  # seed path: one program per edit kind
                rm_st = mt.remove_edges(ev.removals)
                in_st = mt.insert_edges(ev.edges)
                return (rm_st, in_st)
            st = mt.apply_batch(insert_edges=ev.edges,
                                remove_edges=ev.removals)
            return (st,)

        # per-batch stats (device scalars — appending is free; the int()
        # reads happen after the timed region). max_frontier is the datum
        # the sparse frontier_cap planner is tuned from (§4.3), and
        # n_overflow counts the rounds that fell back dense — the warmup
        # batches are kept too, as the planner's blind "before" phase.
        all_stats = []
        for ev in events[:warmup]:  # compile both programs
            all_stats.extend(step(ev))
        mt.core.block_until_ready()
        # one more untimed batch AFTER the sync: the warmup stats are now
        # ready, so the adaptive planners (the sparse frontier cap tuned
        # from observed max_frontier) pick their steady-state bucket here
        # and its compile stays out of the timed region, exactly like the
        # warmup compiles
        all_stats.extend(step(events[warmup]))
        mt.core.block_until_ready()
        t0 = time.perf_counter()
        for ev in events[warmup + 1:]:
            all_stats.extend(step(ev))
        mt.core.block_until_ready()
        dt = time.perf_counter() - t0
        per_engine[engine] = {
            "seconds": dt,
            "batches_per_s": n_batches / dt,
            "edges_per_s": n_batches * batch_size / dt,
            "max_frontier": max(int(s.max_frontier) for s in all_stats),
        }
        # the host path's per-kind stats carry no overflow counter (no
        # halo exchange there) — treat those as zero
        overflow_per_batch[engine] = [
            int(getattr(s, "n_overflow", 0)) for s in all_stats
        ]
        if ENGINE_SPECS[engine].get("kernel_backend") == "pallas":
            # off-TPU the fused kernels run in pallas interpret mode, so
            # this wall-clock row measures the interpreter, not the
            # fusion: stamp it explicitly so the coherence gate can keep
            # the launch-count claim while ignoring the timing
            per_engine[engine]["interpret_mode"] = bool(default_interpret())
        finals[engine] = mt.cores()
    agree = all(
        bool((finals[e] == finals[engines[0]]).all()) for e in engines
    )
    result = {
        # the coherence gate (repro.analysis.benchcheck) refuses
        # artifacts that predate its expected schema stamp
        "schema": BENCH_SCHEMA,
        "graph": {"n": n, "m": m},
        "n_batches": n_batches,
        "batch_size": batch_size,
        "engines_agree": agree,
    }
    result.update(per_engine)
    if "host" in per_engine:
        for engine in engines:
            if engine != "host":
                result[f"speedup_{engine}_vs_host"] = (
                    per_engine["host"]["seconds"]
                    / per_engine[engine]["seconds"]
                )
    # static launch-count roofline term: per-round dispatch histograms
    # for both kernel backends (trace-only — cheap even when the timed
    # sweep above was). The coherence gate requires the pallas rounds to
    # launch strictly fewer kernels than lax.
    result["launches_per_round"] = round_launch_counts(n, 4 * m)
    # sliding-window expiry: structural removals by age over a temporal
    # (u, v, t) stream that drains to an empty graph — the coherence
    # gate requires the drain invariant (insertions == removals,
    # all-zero final cores) on top of the cross-engine agreement
    result["temporal"] = temporal_bench(
        n=n, arrivals=temporal_arrivals, window=temporal_window,
        stride=temporal_stride,
    )
    # the frontier_cap=0 auto-planner before/after: the blind pow2 cap
    # undershoots this stream's removal cascades (max_frontier ~2x the
    # batch multiple), so the early batches pay the dense overflow
    # fallback until the running p95 of the harvested max_frontier
    # grows the cap — the second half of the stream must overflow less
    if "frontier_sparse" in per_engine:
        ovf = overflow_per_batch["frontier_sparse"]
        half = len(ovf) // 2
        observed = per_engine["frontier_sparse"]["max_frontier"]
        result["frontier_autoplan"] = {
            "engine": "frontier_sparse",
            "frontier_cap": 0,  # 0 = auto-planned from observed stats
            "blind_cap": plan_frontier_cap("sparse", 0, batch_size, n),
            "tuned_cap": plan_frontier_cap("sparse", 0, batch_size, n,
                                           observed=observed),
            "overflow_rounds_before": sum(ovf[:half]),
            "overflow_rounds_after": sum(ovf[half:]),
            "overflow_rounds_per_batch": ovf,
        }
    # write the artifact BEFORE the scaling subprocesses and BEFORE
    # asserting: on a divergence or a failed/timed-out scaling run the
    # JSON (with engines_agree and all per-engine timings) survives as
    # the debugging evidence
    def _write():
        if out_json:
            with open(out_json, "w") as fh:
                json.dump(result, fh, indent=2)

    _write()
    if scaling_device_counts:
        result["sharded_scaling"] = sharded_device_scaling(
            scaling_device_counts, n=n, m=m,
            n_batches=min(n_batches, 10), batch_size=batch_size,
        )
        _write()
    if vertex_scaling_device_counts:
        result["vertex_scaling"] = sharded_device_scaling(
            vertex_scaling_device_counts, n=n, m=m,
            n_batches=min(n_batches, 10), batch_size=batch_size,
            vertex_sharding="range",
        )
        _write()
    if frontier_scaling_device_counts:
        result["frontier_scaling"] = sharded_device_scaling(
            frontier_scaling_device_counts, n=n, m=m,
            n_batches=min(n_batches, 10), batch_size=batch_size,
            vertex_sharding="range", frontier_exchange="sparse",
        )
        _write()
    if mesh_scaling_shapes:
        result["mesh_scaling"] = halo_mesh_scaling(
            mesh_scaling_shapes, n=n, m=m,
            n_batches=min(n_batches, 10), batch_size=batch_size,
        )
        _write()
    assert agree, "engines diverged on the same stream"
    tmp = result["temporal"]
    assert tmp["engines_agree"], "engines diverged on the temporal stream"
    assert tmp["drained"] and tmp["final_cores_zero"], (
        "sliding-window stream failed to drain"
    )
    return result


_SCALING_SCRIPT = """
import json, sys, time
import repro
import jax
from repro.core.api import CoreMaintainer
from repro.graph.generators import erdos_renyi
from repro.graph.stream import mixed_stream

n, m, n_batches, batch_size, warmup = map(int, sys.argv[1:6])
vertex_sharding = sys.argv[6]
frontier_exchange = sys.argv[7]
mesh_shape = None
if len(sys.argv) > 8 and sys.argv[8]:
    mesh_shape = tuple(int(t) for t in sys.argv[8].split("x"))
g = erdos_renyi(n, m, seed=12)
events = list(mixed_stream(g, n_batches + warmup, batch_size, seed=17))
kw = {} if mesh_shape is None else {"mesh_shape": mesh_shape}
mt = CoreMaintainer.from_graph(g, capacity=4 * m, engine="sharded",
                               vertex_sharding=vertex_sharding,
                               frontier_exchange=frontier_exchange, **kw)
for ev in events[:warmup]:
    mt.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
mt.core.block_until_ready()
t0 = time.perf_counter()
for ev in events[warmup:]:
    mt.apply_batch(insert_edges=ev.edges, remove_edges=ev.removals)
mt.core.block_until_ready()
dt = time.perf_counter() - t0
row = {
    "platform": jax.devices()[0].platform,
    "n_devices": len(jax.devices()),
    "vertex_sharding": vertex_sharding,
    "frontier_exchange": frontier_exchange,
    "n_batches": n_batches,
    "seconds": dt,
    "batches_per_s": n_batches / dt,
}
if mesh_shape is not None:
    row["mesh_shape"] = list(mesh_shape)
print(json.dumps(row))
"""


def _cpu_child_env(ndev: int) -> Dict[str, str]:
    """Environment of a forced-host-device child: pinned to the CPU, so
    it can never contend for an accelerator the parent process holds.
    XLA_FLAGS is appended to, not clobbered: the child runs under the
    parent's XLA settings plus the forced device count."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    src_path = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env["PYTHONPATH"] = src_path + os.pathsep + env.get("PYTHONPATH", "")
    return env


def sharded_device_scaling(
    device_counts: Sequence[int] = (1, 2, 4),
    n: int = 1500,
    m: int = 6000,
    n_batches: int = 10,
    batch_size: int = 128,
    warmup: int = 3,
    vertex_sharding: str = "replicated",
    frontier_exchange: str = "bitmask",
) -> List[Dict[str, float]]:
    """Time the sharded engine (replicated or range-sharded vertex state,
    bitmask or sparse frontier exchange) under forced host device counts
    (one subprocess per count — XLA fixes the device count at init).
    Every child is pinned to the CPU and its row says so (``platform``):
    the forced devices share the host's cores, so this is a rehearsal
    of collective overhead, never a device timing — the
    ``vertex_sharding="range"`` sweep is the
    one whose per-round vertex traffic stays O(n + frontier bits * d) as
    d grows (docs/DESIGN.md §4.2), and ``frontier_exchange="sparse"``
    shrinks the frontier term to O(cap * d) words (§4.3)."""
    rows: List[Dict[str, float]] = []
    for ndev in device_counts:
        out = subprocess.run(
            [sys.executable, "-c", _SCALING_SCRIPT,
             str(n), str(m), str(n_batches), str(batch_size), str(warmup),
             vertex_sharding, frontier_exchange],
            capture_output=True,
            text=True,
            env=_cpu_child_env(ndev),
            timeout=900,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"scaling run with {ndev} devices failed:\n"
                f"{out.stdout}\n{out.stderr}"
            )
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return rows


def halo_mesh_scaling(
    mesh_shapes: Sequence = ((1, 1), (2, 2), (4, 2), (2, 4)),
    n: int = 1500,
    m: int = 6000,
    n_batches: int = 10,
    batch_size: int = 128,
    warmup: int = 3,
) -> List[Dict[str, float]]:
    """Time the halo engine across 2-axis (edge x vertex) mesh
    factorizations of forced host devices (one subprocess per shape —
    d_e * d_v CPU devices each). The same CPU-rehearsal caveat as
    ``sharded_device_scaling`` applies; what
    the sweep pins everywhere is the SHAPE axis the flat engines don't
    have: at fixed device count, trading edge lanes (d_e) against
    vertex owners (d_v) moves per-device memory O(n/d_v + halo) and the
    halo exchange O(d_v * hcap) in opposite directions
    (docs/DESIGN.md §4.4)."""
    rows: List[Dict[str, float]] = []
    for d_e, d_v in mesh_shapes:
        out = subprocess.run(
            [sys.executable, "-c", _SCALING_SCRIPT,
             str(n), str(m), str(n_batches), str(batch_size), str(warmup),
             "halo", "bitmask", f"{d_e}x{d_v}"],
            capture_output=True,
            text=True,
            env=_cpu_child_env(d_e * d_v),
            timeout=900,
        )
        if out.returncode != 0:
            raise RuntimeError(
                f"mesh scaling run {d_e}x{d_v} failed:\n"
                f"{out.stdout}\n{out.stderr}"
            )
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))
    return rows


CHURN_ENGINES = ("host", "unified", "sharded")


def churn_bench(
    n: int = 1500,
    m: int = 6000,
    n_batches: int = 30,
    batch_size: int = 128,
    warmup: int = 3,
    capacity_mult: float = 1.2,
    out_json: str = "BENCH_stream.json",
    engines: Sequence[str] = CHURN_ENGINES,
) -> Dict[str, object]:
    """Steady-state churn throughput: in-program slot recycling ON (the
    device engines' free-list allocator) vs OFF (the host engine, whose
    tombstones are only reclaimed by host-side ``_compact``) on the SAME
    balanced 50/50 stream over a deliberately tight table
    (``capacity_mult * m``): the host path is forced through periodic
    compaction syncs while the device engines absorb every batch
    in-program. Reports batches/sec, reclaimed slots, defrag counts and
    final capacity per engine, and merges a ``churn`` section into
    ``out_json`` (alongside ``stream_bench``'s sections).
    """
    g, events = churn_workload(n, m, n_batches + warmup, batch_size)
    capacity = int(capacity_mult * g.m) + 64
    per_engine: Dict[str, Dict[str, float]] = {}
    finals = {}
    orig_defrag = CoreMaintainer._defrag_to
    for engine in engines:
        mt = CoreMaintainer.from_graph(g, capacity=capacity,
                                       **ENGINE_SPECS[engine])
        defrags = [0]

        def counting(self, new_cap, _d=defrags):
            _d[0] += 1
            return orig_defrag(self, new_cap)

        stats = []
        try:
            CoreMaintainer._defrag_to = counting
            for ev in events[:warmup]:
                mt.apply_batch(insert_edges=ev.edges,
                               remove_edges=ev.removals)
            mt.core.block_until_ready()
            defrags[0] = 0
            cap0 = mt.capacity
            t0 = time.perf_counter()
            for ev in events[warmup:]:
                # stats are device scalars — collecting them is free; the
                # int() reads happen after the timed region
                stats.append(
                    mt.apply_batch(insert_edges=ev.edges,
                                   remove_edges=ev.removals)
                )
            mt.core.block_until_ready()
            dt = time.perf_counter() - t0
        finally:
            CoreMaintainer._defrag_to = orig_defrag
        per_engine[engine] = {
            "seconds": dt,
            "batches_per_s": n_batches / dt,
            "recycled_slots": int(sum(int(s.n_recycled) for s in stats)),
            "host_defrags": defrags[0],
            "capacity_start": cap0,
            "capacity_final": mt.capacity,
            "high_water_final": int(stats[-1].high_water),
        }
        finals[engine] = mt.cores()
    agree = all(
        bool((finals[e] == finals[engines[0]]).all()) for e in engines
    )
    result: Dict[str, object] = {
        "graph": {"n": n, "m": g.m},
        "n_batches": n_batches,
        "batch_size": batch_size,
        "capacity": capacity,
        "engines_agree": agree,
    }
    result.update(per_engine)
    if "host" in per_engine and "unified" in per_engine:
        result["speedup_unified_vs_host"] = (
            per_engine["host"]["seconds"]
            / per_engine["unified"]["seconds"]
        )
    if out_json:
        blob = {}
        if os.path.exists(out_json):
            with open(out_json) as fh:
                blob = json.load(fh)
        blob["churn"] = result
        with open(out_json, "w") as fh:
            json.dump(blob, fh, indent=2)
    assert agree, "engines diverged on the churn stream"
    return result


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
