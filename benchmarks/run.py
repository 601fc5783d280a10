"""Benchmark harness. One section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus a human-readable
summary on stderr). Scaled for this 1-core CPU container; the same
harness drives the real-hardware runs.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--roofline-json F]
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--roofline-json", default="dryrun_results.json")
    ap.add_argument("--stream-json", default="BENCH_stream.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.quick and args.stream_json == "BENCH_stream.json":
        # --quick skips the device-scaling sweeps; never let it clobber
        # the committed artifact (CI asserts the sweep rows are present)
        args.stream_json = "BENCH_stream.quick.json"

    from . import core_maintenance as cm

    n_edges = 128 if args.quick else 512
    widths = (1, 32, n_edges)

    print("name,us_per_call,derived")

    fig4 = cm.fig4_runtime(n_edges=n_edges, widths=widths)
    for r in fig4:
        _emit(
            f"fig4/{r['graph']}/{r['algo']}/w{r['width']}",
            1e6 * r["seconds"] / n_edges,
            f"total_s={r['seconds']:.4f}",
        )

    for r in cm.tab2_speedups(fig4):
        _emit(
            f"tab2/{r['graph']}/{r['op']}",
            0.0,
            (
                f"batch_vs_w1={r['batch_vs_width1']:.2f}x;"
                f"vs_OI={r['vs_order_seq']:.2f}x;"
                f"vs_TI={r['vs_traversal_seq']:.2f}x"
            ),
        )

    for r in cm.fig5_vplus(n_edges=100 if args.quick else 400):
        _emit(
            f"fig5/{r['graph']}/{r['op']}",
            0.0,
            (
                f"frac|V+|<=10={r['frac_le_10']:.3f};med={r['median']:.0f};"
                f"p99={r['p99']:.0f};max={r['max']}"
            ),
        )

    sizes = (64, 128) if args.quick else (128, 256, 512, 1024)
    for r in cm.fig6_scalability(sizes=sizes):
        _emit(
            f"fig6/{r['graph']}/e{r['edges']}",
            1e6 * r["seconds"] / r["edges"],
            f"ratio={r['ratio_vs_smallest']:.2f}",
        )

    for r in cm.fig7_stability(n_batches=4 if args.quick else 8):
        _emit(
            f"fig7/{r['graph']}",
            1e6 * r["mean_s"],
            f"cv={r['cv']:.3f}",
        )

    for r in cm.rounds_depth(batch=n_edges):
        _emit(
            f"rounds/{r['graph']}/{r['op']}",
            0.0,
            f"rounds={r['rounds']};V*={r['v_star']};V+={r['v_plus']}",
        )

    # mixed-stream engine comparison (writes the BENCH_stream.json artifact)
    sb = cm.stream_bench(
        n_batches=10 if args.quick else 30,
        batch_size=64 if args.quick else 128,
        out_json=args.stream_json,
        scaling_device_counts=() if args.quick else (1, 2, 4),
        vertex_scaling_device_counts=() if args.quick else (1, 2, 4),
        frontier_scaling_device_counts=() if args.quick else (1, 2, 4),
        # 2-axis halo factorizations: degenerate, square, and both
        # proper edge x vertex splits of 8 devices
        mesh_scaling_shapes=(
            () if args.quick else ((1, 1), (2, 2), (4, 2), (2, 4))
        ),
        temporal_arrivals=1000 if args.quick else 3000,
    )
    for eng in cm.STREAM_ENGINES:
        interp = (";interpret_mode=true"
                  if sb[eng].get("interpret_mode") else "")
        _emit(
            f"stream/{eng}",
            1e6 * sb[eng]["seconds"] / sb["n_batches"],
            f"batches_per_s={sb[eng]['batches_per_s']:.2f}{interp}",
        )
    _emit(
        "stream/speedup",
        0.0,
        f"unified_vs_host={sb['speedup_unified_vs_host']:.2f}x;"
        f"sharded_vs_host={sb['speedup_sharded_vs_host']:.2f}x;"
        f"vertex_sharded_vs_host="
        f"{sb['speedup_vertex_sharded_vs_host']:.2f}x;"
        f"frontier_sparse_vs_host="
        f"{sb['speedup_frontier_sparse_vs_host']:.2f}x;"
        f"vertex_halo_vs_host={sb['speedup_vertex_halo_vs_host']:.2f}x;"
        f"weighted_vs_host={sb['speedup_weighted_vs_host']:.2f}x;"
        f"agree={sb['engines_agree']}",
    )
    # sliding-window expiry: structural removals by age, drains to empty
    tb = sb["temporal"]
    for eng in cm.TEMPORAL_ENGINES:
        _emit(
            f"temporal/{eng}",
            1e6 * tb[eng]["seconds"] / tb["n_events"],
            f"batches_per_s={tb[eng]['batches_per_s']:.2f}",
        )
    _emit(
        "temporal/invariants",
        0.0,
        (
            f"window={tb['window']};stride={tb['stride']};"
            f"events={tb['n_events']};"
            f"ins={tb['total_insertions']};rm={tb['total_removals']};"
            f"drained={tb['drained']};zero={tb['final_cores_zero']};"
            f"agree={tb['engines_agree']}"
        ),
    )
    fa = sb.get("frontier_autoplan")
    if fa:
        _emit(
            "stream/frontier_autoplan",
            0.0,
            (
                f"cap={fa['blind_cap']}->{fa['tuned_cap']};"
                f"overflow_rounds={fa['overflow_rounds_before']}->"
                f"{fa['overflow_rounds_after']}"
            ),
        )
    # static per-round kernel-launch counts (the fusion claim the
    # coherence gate enforces: pallas strictly below lax per round)
    lp = sb["launches_per_round"]
    _emit(
        "stream/launches_per_round",
        0.0,
        (
            f"removal={sum(lp['lax']['removal'].values())}->"
            f"{sum(lp['pallas']['removal'].values())};"
            f"promotion={sum(lp['lax']['promotion'].values())}->"
            f"{sum(lp['pallas']['promotion'].values())};"
            f"total={lp['lax']['total']}->{lp['pallas']['total']}"
        ),
    )
    for key in ("sharded_scaling", "vertex_scaling", "frontier_scaling"):
        for row in sb.get(key, ()):
            _emit(
                f"stream/{key}/dev{row['n_devices']}",
                1e6 * row["seconds"] / row["n_batches"],
                f"batches_per_s={row['batches_per_s']:.2f}",
            )
    for row in sb.get("mesh_scaling", ()):
        de, dv = row["mesh_shape"]
        _emit(
            f"stream/mesh_scaling/{de}x{dv}",
            1e6 * row["seconds"] / row["n_batches"],
            f"batches_per_s={row['batches_per_s']:.2f}",
        )

    # steady-state churn on a tight table: in-program slot recycling
    # (device engines) vs host-side _compact reclaim (appends the
    # "churn" section to the BENCH_stream.json artifact)
    cb = cm.churn_bench(
        n_batches=10 if args.quick else 30,
        batch_size=64 if args.quick else 128,
        out_json=args.stream_json,
    )
    for eng in cm.CHURN_ENGINES:
        r = cb[eng]
        _emit(
            f"churn/{eng}",
            1e6 * r["seconds"] / cb["n_batches"],
            (
                f"batches_per_s={r['batches_per_s']:.2f};"
                f"recycled={r['recycled_slots']};"
                f"defrags={r['host_defrags']};"
                f"cap={r['capacity_start']}->{r['capacity_final']}"
            ),
        )
    _emit(
        "churn/speedup",
        0.0,
        f"unified_vs_host={cb['speedup_unified_vs_host']:.2f}x;"
        f"agree={cb['engines_agree']}",
    )

    # roofline table (from the dry-run artifact, if present)
    if os.path.exists(args.roofline_json):
        with open(args.roofline_json) as fh:
            cells = json.load(fh)
        for c in cells:
            if c["mesh"] != "16x16":
                continue
            rf = c["roofline"]
            _emit(
                f"roofline/{c['arch']}/{c['shape']}",
                1e6 * max(rf["t_compute_s"], rf["t_memory_s"],
                          rf["t_collective_s"]),
                (
                    f"dom={rf['dominant']};tc={rf['t_compute_s']:.2e};"
                    f"tm={rf['t_memory_s']:.2e};"
                    f"tx={rf['t_collective_s']:.2e};"
                    f"useful={c.get('model_vs_hlo')}"
                ),
            )
    else:
        print(
            f"# roofline: {args.roofline_json} not found "
            "(run repro.launch.dryrun --all --out first)",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
