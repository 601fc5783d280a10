"""End-to-end driver: a streaming core-maintenance service.

Consumes batches of edge events (the paper's workload: bursts of inserted/
removed edges that must be absorbed on time), maintains core numbers +
k-order, checkpoints atomically, and auto-resumes after a crash.

    PYTHONPATH=src python examples/stream_maintenance.py
    PYTHONPATH=src python examples/stream_maintenance.py --simulate-crash
    PYTHONPATH=src python examples/stream_maintenance.py --weighted --verify
    PYTHONPATH=src python examples/stream_maintenance.py --window 6
"""
import argparse
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.api import CoreMaintainer
from repro.core.oracle import bz_from_csr
from repro.core.weighted import weighted_core_oracle
from repro.graph.csr import build_csr
from repro.graph.generators import erdos_renyi
from repro.graph.stream import (mixed_stream, sliding_window_stream,
                                synthetic_stream)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=5000)
    ap.add_argument("--m", type=int, default=20000)
    ap.add_argument("--batches", type=int, default=40)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_stream_ckpt.npz")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--simulate-crash", action="store_true")
    ap.add_argument("--verify", action="store_true")
    ap.add_argument(
        "--mixed", action="store_true",
        help="mixed insert+remove batches, one compiled call per batch",
    )
    ap.add_argument(
        "--engine", default="unified",
        choices=("unified", "host", "sharded"),
        help="sharded = slot table sharded over all local devices "
             "(XLA_FLAGS=--xla_force_host_platform_device_count=8 to try "
             "multi-device on CPU)",
    )
    ap.add_argument(
        "--vertex-sharding", default="replicated",
        choices=("replicated", "range", "halo"),
        help="where the per-vertex state lives under --engine sharded: "
             "replicated (one psum per statistic), range (each device "
             "owns a vertex range; reduce_scatter stats + bit-packed "
             "frontier masks — docs/DESIGN.md §4.2), or halo (2-axis "
             "mesh, owned range + static halo working set — §4.4)",
    )
    ap.add_argument(
        "--mesh-shape", default=None, metavar="DExDV",
        help="(d_e, d_v) factorization for --vertex-sharding halo, "
             "e.g. 4x2; the product must cover all devices (defaults "
             "to all devices on the edge axis)",
    )
    ap.add_argument(
        "--weighted", action="store_true",
        help="maintain WEIGHTED coreness (weighted h-index, Zhou et al. "
             "WWW'21 — docs/DESIGN.md §4.5): random integer edge "
             "weights, verified against the weighted peeling oracle "
             "under --verify; needs a device engine",
    )
    ap.add_argument(
        "--window", type=int, default=None, metavar="W",
        help="replay a sliding-window TEMPORAL stream instead of the "
             "synthetic one: timestamped arrivals, each edge expiring W "
             "steps after its latest arrival (bulk removals by age), "
             "starting from an empty graph and draining back to empty",
    )
    ap.add_argument(
        "--frontier-exchange", default="bitmask",
        choices=("bitmask", "sparse"),
        help="how changed-vertex masks cross the mesh under "
             "--vertex-sharding range: bitmask (n/8 bytes per shard per "
             "round) or sparse (compacted frontier indices in a static "
             "capacity bucket, falling back to the bitmask per round on "
             "overflow — docs/DESIGN.md §4.3)",
    )
    args = ap.parse_args()
    enable_compile_cache()
    if args.weighted and args.engine == "host":
        ap.error("--weighted needs a device engine (unified | sharded)")
    if args.window is not None and args.window < 1:
        ap.error("--window must be >= 1")
    if args.vertex_sharding in ("range", "halo") and args.engine != "sharded":
        ap.error(f"--vertex-sharding {args.vertex_sharding} needs "
                 "--engine sharded")
    if (args.frontier_exchange == "sparse"
            and args.vertex_sharding not in ("range", "halo")):
        ap.error("--frontier-exchange sparse needs --vertex-sharding "
                 "range or halo")
    mesh_shape = None
    if args.mesh_shape:
        import re
        mm = re.fullmatch(r"(\d+)x(\d+)", args.mesh_shape)
        if not mm:
            ap.error(f"--mesh-shape must look like 4x2, got "
                     f"{args.mesh_shape!r}")
        mesh_shape = (int(mm.group(1)), int(mm.group(2)))
        if args.vertex_sharding != "halo":
            ap.error("--mesh-shape needs --vertex-sharding halo")

    if args.window is not None:
        # timestamped arrivals over a --batches-step horizon; the window
        # expiry turns them into mixed insert+removal events (removals
        # by AGE — the paper's temporal workload) that start from an
        # empty graph and drain it back to empty
        srng = np.random.default_rng(42)
        arrivals = args.batches * args.batch_size
        ewt = np.stack(
            [srng.integers(0, args.n, arrivals),
             srng.integers(0, args.n, arrivals),
             srng.integers(0, args.batches, arrivals)], axis=1,
        ).astype(np.int64)
        events = list(sliding_window_stream(ewt, window=args.window))
        g = build_csr(args.n, np.zeros((0, 2), np.int64))
    else:
        g = erdos_renyi(args.n, args.m, seed=0)
        stream = mixed_stream if args.mixed else synthetic_stream
        events = list(stream(g, args.batches, args.batch_size, seed=42))
    # the weight stream is regenerated from the same seed on resume, so
    # a restarted run replays identical per-batch insert weights
    wrng = np.random.default_rng(2)
    w0 = (wrng.integers(1, 8, g.m).astype(np.int32)
          if args.weighted else None)
    ins_w = ([wrng.integers(1, 8, len(ev.edges)).astype(np.int32)
              for ev in events] if args.weighted else None)
    state_path = args.ckpt
    meta_path = args.ckpt + ".meta"

    start_batch = 0
    if os.path.exists(state_path) and os.path.exists(meta_path):
        m = CoreMaintainer.load(state_path, engine=args.engine,
                                vertex_sharding=args.vertex_sharding,
                                mesh_shape=mesh_shape,
                                frontier_exchange=args.frontier_exchange,
                                weighted=args.weighted)
        start_batch = int(open(meta_path).read().strip()) + 1
        print(f"[resume] restored checkpoint, continuing at batch "
              f"{start_batch}")
    else:
        m = CoreMaintainer.from_graph(
            g, capacity=8 * args.m, engine=args.engine,
            vertex_sharding=args.vertex_sharding,
            mesh_shape=mesh_shape,
            frontier_exchange=args.frontier_exchange,
            weighted=args.weighted, weights=w0,
        )
    if args.engine == "sharded":
        import jax
        print(f"[mesh] edge slots sharded over {len(jax.devices())} "
              f"device(s), vertex state {args.vertex_sharding}, "
              f"frontier exchange {args.frontier_exchange}")

    t_all = time.perf_counter()
    edges_done = 0
    for i in range(start_batch, len(events)):
        ev = events[i]
        t0 = time.perf_counter()
        if ev.kind == "mixed":
            st = m.apply_batch(
                insert_edges=ev.edges, remove_edges=ev.removals,
                insert_weights=ins_w[i] if args.weighted else None,
            )
            extra = (f"+{int(st.n_inserted)}/-{int(st.n_removed)} "
                     f"|V*|={int(st.n_promoted) + int(st.n_dropped)} "
                     f"rounds={int(st.insert_rounds) + int(st.remove_rounds)} "
                     f"recycled={int(st.n_recycled)} "
                     f"hwm={int(st.high_water)}")
        elif ev.kind == "insert":
            st = m.insert_edges(
                ev.edges, weights=ins_w[i] if args.weighted else None)
            extra = f"|V*|={int(st.n_promoted)} rounds={int(st.rounds)}"
        else:
            st = m.remove_edges(ev.edges)
            extra = f"|V*|={int(st.n_dropped)} rounds={int(st.rounds)}"
        dt = time.perf_counter() - t0
        edges_done += ev.n_edits
        print(f"[batch {i:03d}] {ev.kind:6s} {ev.n_edits} edges "
              f"in {dt*1e3:7.1f} ms  {extra}")
        if i % args.ckpt_every == 0:
            tmp = state_path + ".tmp.npz"
            m.save(tmp)
            os.replace(tmp, state_path)  # atomic commit
            with open(meta_path + ".tmp", "w") as fh:
                fh.write(str(i))
            os.replace(meta_path + ".tmp", meta_path)
        if args.simulate_crash and i == len(events) // 2:
            print("[crash] simulating preemption — restart me to resume")
            raise SystemExit(17)

    total = time.perf_counter() - t_all
    print(f"\nprocessed {edges_done} edge events in {total:.2f}s "
          f"({edges_done/total:.0f} edges/s)")

    if args.verify:
        # rebuild the final graph on the host and compare with the oracle
        items = sorted(m.edge_slot.items())
        live = np.asarray(
            [[a, b] for (a, b), _ in items], dtype=np.int64
        ).reshape(-1, 2)
        if args.weighted:
            wcol = np.asarray(m.w)
            lw = np.asarray([wcol[s] for _, s in items], dtype=np.int64)
            expect = weighted_core_oracle(m.n, live, lw)
            assert (m.cores() == expect).all()
            print("final cores verified against the weighted peeling "
                  "oracle ✓")
        else:
            expect = bz_from_csr(build_csr(m.n, live))
            assert (m.cores() == expect).all()
            print("final cores verified against BZ ✓")
    # clean checkpoint on success
    for p in (state_path, meta_path):
        if os.path.exists(p):
            os.remove(p)


if __name__ == "__main__":
    main()
