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
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

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
